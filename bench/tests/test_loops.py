"""Each loop kind driven through a whole run at a tiny size on the CPU,
and the faults that the check has to catch: every one turns ``correct``
false."""
import jax
import pytest

from conftest import run_tiny, tiny_cell


@pytest.mark.parametrize("name", ["spinchain22-outer"])
@pytest.mark.parametrize("traced", [False, True])
def test_cell_runs_and_is_correct(name, traced):
    line = run_tiny(tiny_cell(name), traced=traced)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    want = {"spinchain22-outer": ["outer_gnzv_s", "hbm_gb", "setup_s"]}[name]
    if traced:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert "breakdown" in line
    else:
        assert sorted(line["metrics"]) == sorted(want)
        assert all(m["value"] > 0 for m in line["metrics"].values())


def _filter_unchanged(fd):
    fd.filter = jax.jit(lambda V, mu, alpha, beta: V)


def _filter_half_block(fd):
    inner = fd.filter

    def half(V, mu, alpha, beta):
        Y = inner(V, mu, alpha, beta)
        return Y.at[:, ::2].set(V[:, ::2])

    fd.filter = jax.jit(half)


def _tsqr_column_scaled(fd):
    """TSQR's block with one column 1e-6 longer than a unit vector."""
    inner = fd.orthogonalize

    def scaled(V):
        Q = inner(V)
        return Q.at[:, 0].multiply(1 + 1e-6)

    fd.orthogonalize = jax.jit(scaled)


@pytest.mark.parametrize("name, fault", [
    ("spinchain22-outer", _filter_unchanged),
    ("spinchain22-outer", _filter_half_block),
    ("spinchain22-outer", _tsqr_column_scaled),
])
def test_fault_is_not_correct(name, fault):
    line = run_tiny(tiny_cell(name), hook=fault)
    assert not line["correct"], line["checks"]


def test_float32_control_is_not_correct():
    """The outer cell's control: the solver's own float32 path in place of
    the float64 the configuration states."""
    line = run_tiny(tiny_cell("spinchain22-outer", dtype="float32"))
    assert not line["correct"]
    assert line["checks"]["filter_err"]["value"] > \
        line["checks"]["filter_err"]["limit"]

