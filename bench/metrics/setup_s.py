"""Set-up: process start, imports, device, matrix build, Lanczos, warm-up
and compilation (or persistent-cache reads), up to the window."""


def read(rec):
    return rec.setup_s
