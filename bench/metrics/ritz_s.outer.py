"""Seconds per outer iteration the device spent in Rayleigh-Ritz (the
block's SpMV, the Gram matrix, eigh, the residuals, and the fetch of the
Ritz values and residuals): device-busy time inside the program's
``ritz`` spans over the window's ``step_analyze`` spans."""

from program_spans import per_iteration


def read(rec):
    return per_iteration(rec, "ritz", 0)
