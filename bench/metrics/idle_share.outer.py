"""Share of the traced window in which the device ran no operation, in %:
1 - (union of the device's op intervals) / window."""


def read(rec):
    if rec.trace is None:
        return None
    lo, hi = rec.trace.window()
    return 100.0 * (1.0 - rec.trace.busy_s(lo, hi) / ((hi - lo) * 1e-9))
