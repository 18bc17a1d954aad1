"""The work count and the bytes function, against a tiny FilterDiag run
on the CPU (D = 924) and against the formula written out."""

import jax
import numpy as np
import pytest

from conftest import tiny_cell


def _outer_loop(cell, seed):
    import harness

    fd = harness.make_solver(cell, jax.devices())
    loop = harness.load_module(f"{harness.BENCH}/loops/outer_loop.py").Loop(
        fd, cell.traffic, seed, harness.Spans())
    return fd, loop


@pytest.mark.parametrize("traced", [False, True])
def test_window_counts_the_spmvs_filter_diag_counts(traced):
    cell = tiny_cell("spinchain22-outer")
    fd, loop = _outer_loop(cell, seed=2 ** 33 + 1)
    loop.warm()
    before = loop.state.total_spmvs
    counts = loop.window(0.3, traced)
    n_s = cell.config["solver"]["n_search"]
    assert counts["iterations"] >= 1
    assert counts["spmvs"] == loop.state.total_spmvs - before
    # every filter is degree 32 under the mix's cap: N_s Ritz + 32 N_s
    assert counts["filter_steps"] == 32 * counts["iterations"]
    assert counts["spmvs"] == counts["iterations"] * (n_s + 32 * n_s)


@pytest.mark.parametrize("traced", [False, True])
def test_window_drives_filter_diag_step(traced):
    """Untraced, the window calls FilterDiag.step itself and opens no span;
    traced, it runs the same two halves inside spans."""
    cell = tiny_cell("spinchain22-outer")
    fd, loop = _outer_loop(cell, seed=2 ** 33 + 3)
    loop.warm()
    inner, calls = fd.step, []
    fd.step = lambda state: calls.append(1) or inner(state)
    counts = loop.window(0.3, traced)
    names = {n for n, _, _ in loop.spans.items}
    if traced:
        assert not calls and names == {"analyze", "filter"}
    else:
        assert len(calls) >= counts["iterations"] >= 1 and not names


def test_seed_gives_the_same_keys():
    cell = tiny_cell("spinchain22-outer")
    keys = [[np.asarray(_outer_loop(cell, seed)[1]._key()) for _ in range(2)]
            for seed in (2 ** 33 + 7, 2 ** 33 + 7, 2 ** 33 + 8)]
    assert np.array_equal(keys[0], keys[1])
    assert not np.array_equal(keys[0], keys[2])


def test_step_bytes_is_the_formula():
    from roofline import step_bytes

    nnz, D, n = 8_465_184, 705_432, 16
    assert step_bytes(nnz, D, n, "float64") == 12 * nnz + 24 * D * n
    assert step_bytes(nnz, D, n, "complex64") == 12 * nnz + 24 * D * n
    assert step_bytes(nnz, D, n, "float32") == 8 * nnz + 12 * D * n


def test_metric_readers_on_a_record():
    """Each end-to-end reader of the outer cell from a hand-made record."""
    import harness

    cell = tiny_cell("spinchain22-outer")
    spans = harness.Spans()
    spans.items += [("analyze", 1.0, 3.0), ("filter", 3.0, 9.0),
                    ("analyze", 9.0, 10.0), ("analyze", 20.0, 30.0)]
    rec = harness.Record(cell=cell, kind="cpu", peaks={}, setup_s=5.0,
                         window=(0.5, 10.5),
                         counts={"iterations": 2, "spmvs": 1000},
                         spans=spans,
                         program_bytes={"a": 2e9, "b": 3e9})

    def read(name):
        return harness.load_module(
            f"{harness.BENCH}/metrics/{name}.py").read(rec)

    assert read("outer_gnzv_s") == cell.config["nnz"] * 1000 / 10.0 / 1e9
    assert read("hbm_gb") == 3.0
    assert read("setup_s") == 5.0
    assert read("analyze_s.outer") == 1.5       # the span at 20 s is outside
    assert read("spmv_roofline") is None       # untraced: nothing to read
