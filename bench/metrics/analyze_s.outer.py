"""Seconds per outer iteration in step_analyze (orthogonalize, Rayleigh-
Ritz, intervals, filter choice): the harness's span around it, which ends
when the Ritz values reach the host."""


def read(rec):
    secs, n = rec.spans.total("analyze", *rec.window)
    return secs / n if n else None
