"""The control of the check, at the cell's own size, on a TPU:

    python -m pytest -q -s bench/tests/test_control.py     # on the chip

The control computes the cell in the next precision below the one its
configuration states, and its check has to come out false on every seed:
``spinchain22`` states float64, so the control is the solver's own
float32 path.

Off a TPU the module skips: the cell's size is the chip's. The float32
path also runs at a tiny size on the CPU in test_loops.py.
"""
import dataclasses
import time

import jax
import pytest

import harness

SEEDS = [3_000_000_001, 3_000_000_002, 3_000_000_003]


@pytest.fixture(scope="module")
def tpu():
    devs = jax.devices()
    if devs[0].platform != "tpu":
        pytest.skip("the control runs at the cell's own size on a TPU")
    return devs


@pytest.mark.parametrize("seed", SEEDS)
def test_control_is_not_correct(tpu, seed):
    cell = harness.load_cell("spinchain22-outer")
    cell = dataclasses.replace(cell,
                               config={**cell.config, "dtype": "float32"})
    line = harness.run_cell(cell, seed, 10.0, False, time.perf_counter(),
                            log=lambda msg: None)
    print(f"\n[control] {cell.name} seed {seed}: correct={line['correct']} "
          f"checks={line['checks']}", flush=True)
    assert not line["correct"]
