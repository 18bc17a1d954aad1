"""Static communication verifier (src/repro/analysis) in the tier-1 loop.

Positive direction: the fast ``scripts/check_comm.py`` gate passes on the
repo as-is (plan lint, overlap checks, census cells, bench schema).
Negative direction: each pass catches its planted defect — a double-sent
neighbor pair (plan lint), a halo collective that depends on the local
contraction (overlap checker), and a spurious all-gather smuggled into a
compiled cell (census). The slow test compiles the full engine grid
(6 engine combos x 3 layouts x 2 balances) for all three bench families.
"""
import json
import os
import subprocess
import sys

import pytest

from tests.conftest import run_distributed

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")
sys.path.insert(0, SRC)


# ------------------------------------------------------------- plan lint --

def test_plan_lint_clean_spinchain():
    from repro.analysis.plan_lint import run_plan_lint
    from repro.matrices import SpinChainXXZ

    assert run_plan_lint(SpinChainXXZ(10, 5), label="spin/") == []


def test_lint_rounds_catches_double_send():
    import numpy as np

    from repro.analysis.plan_lint import lint_rounds

    pc = np.zeros((4, 4), dtype=np.int64)
    pc[0, 1] = 3
    pc[2, 3] = 2
    # pair (0, 1) scheduled twice — would corrupt the engine's contiguous
    # per-round receive-slot layout
    perms = (((0, 1), (2, 3)), ((0, 1),))
    errs = lint_rounds(pc, perms, (3, 3), label="planted")
    assert any("double-sent" in e for e in errs), errs


def test_lint_rounds_catches_invalid_round_and_dropped_pair():
    import numpy as np

    from repro.analysis.plan_lint import lint_rounds

    pc = np.zeros((4, 4), dtype=np.int64)
    pc[0, 1] = pc[2, 1] = pc[1, 0] = 2
    # round 0 sends two sources to device 1 (not a partial permutation)
    # and includes a self-send; pair (1, 0) is never scheduled
    perms = (((0, 1), (2, 1), (3, 3)),)
    errs = lint_rounds(pc, perms, (2,), label="planted")
    assert any("repeats a destination" in e for e in errs), errs
    assert any("self-send" in e for e in errs), errs
    assert any("scheduled in no round" in e for e in errs), errs


# ------------------------------------------------------ census attribution --

def _op(kind, nbytes, mult, name="op"):
    from repro.launch.hlo_analysis import CollectiveOp

    return CollectiveOp(kind=kind, bytes=nbytes, mult=mult, name=name,
                        computation="main")


def test_attribute_flags_spurious_and_missing():
    from repro.analysis.census import ExpectedTerm, attribute

    expected = [ExpectedTerm("halo", "all-to-all", 7680, 6),
                ExpectedTerm("gram", "all-reduce", 512, 1)]
    # exact match passes
    ok = attribute([_op("all-to-all", 7680, 6.0), _op("all-reduce", 512, 1.0)],
                   expected, cell="cell")
    assert ok.ok, ok.errors
    # a spurious all-gather is unattributed; a short halo term is missing
    bad = attribute([_op("all-to-all", 7680, 5.0), _op("all-reduce", 512, 1.0),
                     _op("all-gather", 2048, 1.0, name="all-gather.1")],
                    expected, cell="cell")
    assert not bad.ok
    assert any("unattributed" in e and "all-gather" in e for e in bad.errors)
    assert any("missing collective" in e and "halo" in e for e in bad.errors)


def test_attribute_accepts_alt_bytes():
    from repro.analysis.census import ExpectedTerm, attribute

    # XLA may print the moved subset instead of the full slice — both are
    # admissible for the same term, nothing else is
    term = ExpectedTerm("redist", "all-to-all", 2048, 2, alt_bytes=(1024,))
    assert attribute([_op("all-to-all", 1024, 2.0)], [term]).ok
    assert attribute([_op("all-to-all", 2048, 2.0)], [term]).ok
    assert not attribute([_op("all-to-all", 512, 2.0)], [term]).ok


# ----------------------------------------------------------- bench schema --

def test_schema_accepts_repo_artifact():
    from benchmarks.schema import check_artifact

    path = os.path.join(ROOT, "BENCH_spmv.json")
    if not os.path.exists(path):
        pytest.skip("no BENCH_spmv.json in the repo")
    assert check_artifact(path) == []


def test_schema_rejects_malformed_records():
    from benchmarks.schema import validate_artifact, validate_record

    assert validate_record({"table": "nope", "family": "x"})
    assert any("engine" in e for e in validate_record(
        {"table": "spmv_comm", "family": "x", "engine": "warp"}))
    assert any("nonnegative" in e for e in validate_record(
        {"table": "spmv_comm", "family": "x", "us_per_call": -1.0}))
    assert any("meas_bytes_per_device without" in e for e in validate_record(
        {"table": "spmv_comm", "family": "x", "meas_bytes_per_device": 8}))
    art = {"schema": "bench-spmv/v0", "records": [], "rows": [],
           "benches": ["spmv_comm", "bogus"]}
    errs = validate_artifact(art)
    assert any("schema is" in e for e in errs)
    assert any("bogus" in e for e in errs)


def test_run_refuses_to_write_malformed_artifact(tmp_path):
    """run.py --json must reject a merge that would persist a malformed
    record (records of non-rerun tables survive forever otherwise)."""
    # the malformed record belongs to a table that is NOT rerun, so the
    # merge would keep it — validation must catch it anyway
    bad = {"schema": "bench-spmv/v1", "generated_unix": 0,
           "benches": ["spmv_comm"],
           "records": [{"table": "spmv_comm", "family": "x",
                        "us_per_call": -5.0}],
           "rows": []}
    path = tmp_path / "BENCH_spmv.json"
    path.write_text(json.dumps(bad))
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.run",
         "--only", "table2", "--json", str(path)],
        capture_output=True, text=True, cwd=ROOT,
        env=dict(os.environ, PYTHONPATH=SRC), timeout=600)
    assert r.returncode == 2, r.stdout + r.stderr
    assert "SCHEMA ERROR" in r.stderr
    # the malformed artifact was not overwritten
    assert json.loads(path.read_text()) == bad


# -------------------------------------------------- overlap checker (jaxpr) --

def test_overlap_checker_positive_and_negative():
    out = run_distributed("""
from repro.analysis.overlap_check import check_split_phase
from repro.core import layouts as lo
from repro.core.planner import layout_on_mesh
from repro.core.spmv import build_dist_ell, make_spmv
from repro.matrices import SpinChainXXZ

matrix = SpinChainXXZ(10, 5)
mesh = lo.make_solver_mesh(4, 2)
panel_l = layout_on_mesh(mesh, "panel")
D_pad = -(-matrix.D // 8) * 8
V = jax.ShapeDtypeStruct((D_pad, 4), jax.numpy.float64)
for overlap in (True, False):
    ell = build_dist_ell(matrix, 4, d_pad=D_pad, split_halo=overlap)
    spmv = make_spmv(mesh, panel_l, ell, overlap=overlap, comm="compressed",
                     schedule="matching")
    with mesh:
        rep = check_split_phase(spmv, V)
    if overlap:
        assert rep.ok, rep.describe()
        assert rep.independent_contractions >= 1
    else:
        # plain engine: the single contraction consumes the received halo
        assert not rep.ok
        assert any("no contraction is independent" in e for e in rep.errors)
print("OVERLAP OK")
""")
    assert "OVERLAP OK" in out


def test_overlap_checker_catches_dependent_halo():
    out = run_distributed("""
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.analysis.overlap_check import check_split_phase
from repro.core import layouts as lo

mesh = lo.make_solver_mesh(4, 2)

# planted defect: the ppermute payload is the *output* of the local scan,
# so the exchange cannot start before local compute finishes
def bad_engine(x):
    def body(c, i):
        return c * 1.0001 + i, None
    y, _ = lax.scan(body, x, jax.numpy.arange(4.0))
    h = lax.ppermute(y, "row", [(i, (i + 1) % 4) for i in range(4)])
    return y + h

fn = shard_map(bad_engine, mesh=mesh, in_specs=P(None, None),
               out_specs=P(None, None), check_vma=False)
x = jax.ShapeDtypeStruct((16, 8), jax.numpy.float64)
with mesh:
    rep = check_split_phase(fn, x)
assert not rep.ok
assert any("depends on contraction" in e for e in rep.errors), rep.errors
print("DEPENDENT HALO CAUGHT")
""")
    assert "DEPENDENT HALO CAUGHT" in out


def test_round_pipeline_prefix_chain_proof():
    """check_round_pipeline proves the pipelined compressed engine's
    prefix-chain property (round-r contraction depends on no later
    round's collective; prefix lengths 0, n, and a strict intermediate
    all witnessed) and rejects both the unpipelined control body
    (pipeline=False — no strict prefix) and a planted out-of-order
    dependence (a contraction consuming round 2 without round 1)."""
    out = run_distributed("""
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.analysis.overlap_check import check_round_pipeline
from repro.core import layouts as lo
from repro.core.planner import layout_on_mesh
from repro.core.spmv import build_dist_ell, make_spmv
from repro.matrices import SpinChainXXZ

matrix = SpinChainXXZ(10, 5)
mesh = lo.make_solver_mesh(4, 2)
panel_l = layout_on_mesh(mesh, "panel")
D_pad = -(-matrix.D // 8) * 8
ell = build_dist_ell(matrix, 4, d_pad=D_pad, split_halo=True)
V = jax.ShapeDtypeStruct((D_pad, 4), jax.numpy.float64)
for use_kernel in (False, True):
    spmv = make_spmv(mesh, panel_l, ell, use_kernel=use_kernel,
                     overlap=True, comm="compressed", schedule="cyclic")
    with mesh:
        rep = check_round_pipeline(spmv, V)
    assert rep.ok, rep.describe()
    assert rep.n_rounds >= 2
    assert 0 in rep.prefix_lengths and rep.n_rounds in rep.prefix_lengths
    assert any(0 < k < rep.n_rounds for k in rep.prefix_lengths)
    # the unpipelined control witnesses only {0, n} and must fail
    flat = make_spmv(mesh, panel_l, ell, use_kernel=use_kernel,
                     overlap=True, comm="compressed", schedule="cyclic",
                     pipeline=False)
    with mesh:
        rep0 = check_round_pipeline(flat, V)
    assert not rep0.ok
    assert rep0.prefix_lengths == [0, rep0.n_rounds]
    assert any("not round-pipelined" in e for e in rep0.errors)

# planted defect: a contraction that consumes round 2's buffer without
# round 1's — the dependence set {c2} is not a prefix of (c1, c2)
def bad_engine(x):
    fwd = [(i, (i + 1) % 4) for i in range(4)]
    h1 = lax.ppermute(x, "row", fwd)
    h2 = lax.ppermute(x, "row", [(i, (i + 2) % 4) for i in range(4)])
    def body(c, w):
        return c + w * h2, None
    y, _ = lax.scan(body, x * 0.5, jnp.arange(3.0))
    return y + h1

fn = shard_map(bad_engine, mesh=mesh, in_specs=P(None, None),
               out_specs=P(None, None), check_vma=False)
x = jax.ShapeDtypeStruct((16, 8), jax.numpy.float64)
with mesh:
    bad = check_round_pipeline(fn, x)
assert not bad.ok
assert any("not a prefix" in e for e in bad.errors), bad.errors
print("PIPELINE PROOF OK")
""")
    assert "PIPELINE PROOF OK" in out


# --------------------------------------------------------- census (compile) --

def test_census_catches_spurious_allgather():
    out = run_distributed("""
from repro.analysis.census import run_census_cell
from repro.matrices import SpinChainXXZ

def wrap(iteration, mesh, stack_l):
    from jax import lax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    axes = stack_l.dist_axes
    def mutated(V):
        Vs, G = iteration(V)
        # planted defect: a resharding all-gather the comm plan never
        # predicted (kept live so XLA cannot elide it)
        gath = shard_map(lambda x: lax.all_gather(x, axes, tiled=True),
                        mesh=mesh, in_specs=P(axes, None),
                        out_specs=P(None, None), check_vma=False)(Vs)
        return Vs + 0.0 * gath[: Vs.shape[0]], G
    return mutated

rep = run_census_cell(SpinChainXXZ(10, 5), P_total=8, comm="a2a", wrap=wrap)
assert not rep.ok, rep.describe()
assert any("unattributed" in e and "all-gather" in e for e in rep.errors), \\
    rep.errors
# the clean cell still passes
clean = run_census_cell(SpinChainXXZ(10, 5), P_total=8, comm="a2a")
assert clean.ok, clean.describe()
print("SPURIOUS ALLGATHER CAUGHT")
""")
    assert "SPURIOUS ALLGATHER CAUGHT" in out


# ------------------------------------------------------------- gate script --

def test_check_comm_fast_gate():
    """The fast comm gate (the pre-commit loop entry point) passes."""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "check_comm.py"),
         "--fast"],
        capture_output=True, text=True, cwd=ROOT, timeout=600,
        env={k: v for k, v in os.environ.items() if k != "XLA_FLAGS"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "[check_comm] PASS" in r.stdout


def test_dryrun_verify_flag():
    """`dryrun --eigen ... --verify` attributes the production-mesh cell's
    collectives and exits zero when everything matches."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--eigen",
         "roadnet48k", "--layout", "panel", "--spmv-comm", "compressed",
         "--spmv-schedule", "matching", "--verify"],
        capture_output=True, text=True, cwd=ROOT, timeout=600,
        env=dict({k: v for k, v in os.environ.items() if k != "XLA_FLAGS"},
                 PYTHONPATH=SRC))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "census[" in r.stdout and ": OK" in r.stdout


@pytest.mark.slow
def test_census_full_engine_grid_all_families():
    """Full grid: 6 engine combos x {stack, panel, pillar} x {rows,
    commvol} on SpinChain, RoadNet-small, and HubNet-small — zero
    unattributed and zero missing collectives everywhere."""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "check_comm.py"),
         "--family", "spinchain", "--family", "roadnet",
         "--family", "hubnet"],
        capture_output=True, text=True, cwd=ROOT, timeout=3000,
        env={k: v for k, v in os.environ.items() if k != "XLA_FLAGS"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "[check_comm] PASS" in r.stdout
