"""Seconds of set-up in FD's Lanczos interval: the host-clock length of
the last program span ``lanczos`` that lies in set-up (its steps each
fetch two scalars to the host)."""

from program_spans import last_in_setup_s


def read(rec):
    return last_in_setup_s(rec, "lanczos")
