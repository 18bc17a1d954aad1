"""The benchmark's own tests, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They drive the harness at tiny sizes through an injected device check
(never a path of the benchmark itself), so nothing they time is a device
number. ``test_control.py`` runs at a cell's own size on a TPU only.
"""
import dataclasses
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

# tiny stand-ins of each configuration, same family and solver settings
TINY = {
    "spinchain22": dict(params={"n_sites": 12, "n_up": 6, "Jxy": 1.0,
                                "Jz": 1.0}, D=924, nnz=6468),
}
CONFIG_OF = {"spinchain22-outer": "spinchain22"}


@pytest.fixture(autouse=True)
def _no_cpu_compile_cache():
    """Nothing compiled for the CPU here is written to a persistent
    cache (a compile for a described TPU could not be read back)."""
    if jax.devices()[0].platform == "cpu":
        jax.config.update("jax_enable_compilation_cache", False)


def tiny_cell(name: str, **config):
    import harness

    cell = harness.load_cell(name)
    return dataclasses.replace(
        cell, config={**cell.config, **TINY[CONFIG_OF[name]], **config})


def run_tiny(cell, seed=20261018, seconds=0.5, traced=False, hook=None):
    import time

    import harness

    return harness.run_cell(cell, seed, seconds, traced, time.perf_counter(),
                            device_check=lambda chips: jax.devices(),
                            solver_hook=hook, log=lambda msg: None)
