"""Seconds of set-up JAX spent tracing, lowering, compiling or reading
compiled programs from the persistent cache: the program's compile clock
(``jax.monitoring`` durations) over the events stamped in set-up."""

from program_spans import setup_compile_s


def read(rec):
    return setup_compile_s(rec)
