"""Seconds per outer iteration the device spent in orthogonalization
(TSQR): device-busy time inside the program's ``orthogonalize`` spans,
which end on the orthogonal block while a trace records, over the
window's ``step_analyze`` spans."""

from program_spans import per_iteration


def read(rec):
    return per_iteration(rec, "orthogonalize", 0)
