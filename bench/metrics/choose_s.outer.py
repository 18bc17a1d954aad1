"""Seconds per outer iteration the device stood idle while the host chose
the next filter (target and search intervals, the filter's degree and
coefficients): device-idle time inside the program's ``choose_filter``
spans over the window's ``step_analyze`` spans."""

from program_spans import per_iteration


def read(rec):
    return per_iteration(rec, "choose_filter", 1)
