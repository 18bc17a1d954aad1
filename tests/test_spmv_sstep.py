"""Communication-avoiding s-step filter axis (ISSUE 8).

The seventh engine axis ``spmv_sstep``: the degree-n Chebyshev filter
applied in ⌈n/s⌉ depth-s ghost exchanges (``build_sstep_ell`` +
``make_sstep_cheb``) instead of n per-SpMV halo exchanges.

  * property tests: the depth-s ghost set of every shard equals BFS
    reachability over the boolean pattern powers A^1..A^s (minus the
    owned rows), is monotone in s, and at s = 1 the builder round-trips
    bit-exactly to ``DistEll`` — on random patterns and on planned
    commvol/rcm RowMaps,
  * the full filter is bit-identical across depths s ∈ {1, 2, 3} for
    {a2a, compressed-cyclic, compressed-matching} x {plain, overlap}
    x {kernel off, kernel on} on SpinChainXXZ, RoadNet, and HubNet,
    including on planned RowMaps (degree >= 4: the degenerate degree-3
    tail regroups one FMA on the base path — see docs/s-step.md),
  * the depth-s ``comm_plan`` volumes match the built operator exactly,
    and scoring an s > 1 plan on a RowMap planned at depth 1 warns
    (stale cuts silently under-count the depth-s volumes),
  * ``MachineModel.fit`` recovers (κ, b_c, α) exactly from synthetic
    Eq. 12 + α·rounds samples once a tiny-halo cell breaks the
    rounds/bytes collinearity — and leaves α at 0 without rounds data,
  * the planner keeps s = 1 under the default (bandwidth-bound) machine
    and promotes an s > 1 candidate to the best halo-bearing
    configuration under the high-latency model,
  * the bench artifact schema enums the new ``s`` field and the
    merge-on-write path refuses to propagate a malformed record.
"""
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

from tests._hypothesis_compat import given, settings, st
from tests.conftest import NO_FMA, ROOT, run_distributed

from repro.core import perf_model as pm
from repro.core.partition import plan_rowmap
from repro.core.planner import comm_plan, plan_layout
from repro.core.spmv import build_dist_ell, build_sstep_ell, sstep_ghosts
from repro.matrices import HubNet, RoadNet, SpinChainXXZ
from repro.matrices.sparse import CSR

HUBNET_SMALL = dict(n=4000, w=2, h=4, m=192, k=4)
ROADNET_SMALL = dict(n=4000, w=2, m=256, k=4)


def _random_pattern_csr(rng, n, density) -> CSR:
    """Random sparse pattern with values: symmetric support plus the
    diagonal, so BFS depth has nontrivial growth."""
    a = rng.random((n, n)) < density
    a |= a.T
    np.fill_diagonal(a, True)
    indptr = np.zeros(n + 1, dtype=np.int64)
    indptr[1:] = a.sum(axis=1).cumsum()
    indices = np.concatenate([np.flatnonzero(a[i]) for i in range(n)])
    data = rng.standard_normal(indices.size)
    return CSR(indptr=indptr, indices=indices.astype(np.int64),
               data=data, shape=(n, n))


def _padded_pattern(csr: CSR, P: int):
    """Pattern CSR over the padded position space [0, P*R)."""
    D = csr.shape[0]
    R = -(-D // P)
    indptr = np.concatenate(
        [csr.indptr,
         np.full(P * R - D, csr.indptr[-1], dtype=np.int64)])
    return indptr, np.asarray(csr.indices, dtype=np.int64), R


@settings(max_examples=12)
@given(n=st.integers(8, 48), P=st.integers(2, 4), s=st.integers(1, 3),
       seed=st.integers(0, 10_000))
def test_sstep_ghosts_equal_bfs_reachability(n, P, s, seed):
    """Every shard's depth-d ghost set (d <= s) equals the boolean-power
    reachability of A^d from its owned rows, minus the owned rows — and
    the depths recorded are the FIRST-reached depths."""
    rng = np.random.default_rng(seed)
    csr = _random_pattern_csr(rng, n, density=rng.uniform(0.03, 0.25))
    indptr, cols, R = _padded_pattern(csr, P)
    ghosts = sstep_ghosts(indptr, cols, P, R, s)
    D = csr.shape[0]
    B = np.zeros((P * R, P * R), dtype=bool)
    for i in range(D):
        B[i, cols[indptr[i]:indptr[i + 1]]] = True
    for p, (gpos, gdep) in enumerate(ghosts):
        owned = np.zeros(P * R, dtype=bool)
        owned[p * R:(p + 1) * R] = True
        reach = owned.copy()
        first_depth = {}
        for d in range(1, s + 1):
            nxt = (reach @ B) | reach
            for j in np.flatnonzero(nxt & ~reach):
                first_depth[int(j)] = d
            reach = nxt
        want = np.array(sorted(first_depth), dtype=np.int64)
        assert np.array_equal(gpos, want), (p, s)
        assert np.array_equal(gdep,
                              np.array([first_depth[int(j)] for j in want],
                                       dtype=np.int64)), p


@settings(max_examples=8)
@given(n=st.integers(8, 40), P=st.integers(2, 4), seed=st.integers(0, 10_000))
def test_sstep_ghosts_monotone_in_depth(n, P, seed):
    """Ghost sets grow monotonically with s, and the depth-d slice of a
    deeper BFS equals the depth-d BFS (the plan at s is a refinement,
    never a recomputation, of the plan at s-1)."""
    rng = np.random.default_rng(seed)
    csr = _random_pattern_csr(rng, n, density=rng.uniform(0.03, 0.25))
    indptr, cols, R = _padded_pattern(csr, P)
    per_s = [sstep_ghosts(indptr, cols, P, R, s) for s in (1, 2, 3)]
    for p in range(P):
        prev: set = set()
        for si, s in enumerate((1, 2, 3)):
            gpos, gdep = per_s[si][p]
            cur = set(gpos.tolist())
            assert prev <= cur, (p, s)
            prev = cur
            # depth-d slice agrees with the shallower BFS
            for sj in range(si):
                gp_j, _ = per_s[sj][p]
                mask = gdep <= sj + 1
                assert np.array_equal(np.sort(gpos[mask]), gp_j), (p, s)


def test_sstep_s1_roundtrips_to_dist_ell():
    """s = 1 is the existing engine: ``build_sstep_ell(..., 1)``
    re-expressed via ``as_dist_ell`` is bit-identical to
    ``build_dist_ell`` — cols, vals, send plan, pair counts — on random
    patterns, on SpinChain, and on a planned commvol+rcm RowMap."""
    rng = np.random.default_rng(5)
    cases = []
    for _ in range(3):
        csr = _random_pattern_csr(rng, int(rng.integers(16, 60)),
                                  density=rng.uniform(0.05, 0.2))
        cases.append((csr, None))
    hub = HubNet(**HUBNET_SMALL)
    cases.append((SpinChainXXZ(8, 4).build_csr(), None))
    cases.append((hub.build_csr(),
                  plan_rowmap(hub, 4, balance="commvol", reorder="rcm")))
    for csr, rm in cases:
        ell = build_dist_ell(csr, 4, rowmap=rm)
        sell = build_sstep_ell(csr, 4, 1, rowmap=rm)
        assert (sell.R, sell.L, sell.G) == (ell.R, ell.L, int(ell.n_vc.max()))
        back = sell.as_dist_ell()
        assert np.array_equal(np.asarray(back.cols), np.asarray(ell.cols))
        assert np.array_equal(np.asarray(back.vals), np.asarray(ell.vals))
        assert np.array_equal(np.asarray(back.send_idx),
                              np.asarray(ell.send_idx))
        assert np.array_equal(back.pair_counts, ell.pair_counts)


def test_sstep_comm_plan_matches_builder():
    """The pattern-only depth-s plan and the built operator agree on L,
    per-pair volumes, and ghost counts — equal partition and planned
    RowMap — so the census/byte predictions are exact by construction."""
    hub = HubNet(**HUBNET_SMALL)
    for s in (2, 3):
        for rm in (None, plan_rowmap(hub, 4, balance="commvol", sstep=s)):
            cp = comm_plan(hub, 4, rowmap=rm, sstep=s)
            sell = build_sstep_ell(hub, 4, s, rowmap=rm)
            assert cp.L == sell.L, (s, rm)
            assert np.array_equal(cp.pair_counts, sell.pair_counts)
            assert np.array_equal(np.asarray(cp.n_vc), np.asarray(sell.n_vc))
            assert cp.ghost_cum == sell.ghost_cum
            assert cp.ghost_cum[s] == int(np.asarray(sell.n_vc).max())


def test_sstep_plan_warns_on_stale_rowmap_depth():
    """Satellite 6: scoring an s > 1 plan on a RowMap planned at depth 1
    warns (its cuts never optimized the depth-s volumes); a map planned
    at the right depth stays silent."""
    mat = SpinChainXXZ(10, 5)
    rm1 = plan_rowmap(mat, 4, balance="commvol")
    with pytest.warns(UserWarning, match="sstep"):
        comm_plan(mat, 4, rowmap=rm1, sstep=2)
    rm2 = plan_rowmap(mat, 4, balance="commvol", sstep=2)
    with warnings.catch_warnings():
        warnings.simplefilter("error", UserWarning)
        comm_plan(mat, 4, rowmap=rm2, sstep=2)
        comm_plan(mat, 4, rowmap=rm1)  # depth-1 scoring never warns


def test_sstep_bit_identity_engine_grid():
    """The depth-s filter is bit-identical to the s = 1 reference across
    the engine grid on SpinChainXXZ: {a2a, compressed-cyclic,
    compressed-matching} x {plain, overlap} x s ∈ {2, 3}, with the
    kernelized (Pallas interpret) cells at both depths."""
    out = run_distributed(f"""
import numpy as np, jax, jax.numpy as jnp
from repro.matrices import SpinChainXXZ
from repro.core import make_solver_mesh, panel, build_dist_ell, make_spmv
from repro.core.spmv import build_sstep_ell, make_sstep_cheb
from repro.core.chebyshev import chebyshev_filter
mat = SpinChainXXZ(10, 5)
csr = mat.build_csr()
D = csr.shape[0]
D_pad = -(-D // 8) * 8
mesh = make_solver_mesh(4, 2)
lay = panel(mesh)
degree = 8
mu = np.linspace(1.0, 0.5, degree + 1)
rng = np.random.default_rng(0)
X = np.zeros((D_pad, 8)); X[:D] = rng.standard_normal((D, 8))
ENGINES = [("a2a", "cyclic"), ("compressed", "cyclic"),
           ("compressed", "matching")]
with mesh:
    Xs = jax.device_put(jnp.asarray(X), lay.vec_sharding(mesh))
    ell = build_dist_ell(csr, 4, d_pad=D_pad)
    spmv = make_spmv(mesh, lay, ell)
    ref = np.asarray(jax.jit(
        lambda V: chebyshev_filter(spmv, mu, 0.5, 0.1, V))(Xs))
    for s in (2, 3):
        sell = build_sstep_ell(csr, 4, s, d_pad=D_pad)
        for comm, sched in ENGINES:
            for ov in (False, True):
                for krn in ((False, True) if (comm, ov) in
                            (("a2a", False), ("compressed", True))
                            else (False,)):
                    app = make_sstep_cheb(mesh, lay, sell, comm=comm,
                                          schedule=sched, overlap=ov,
                                          use_kernel=krn)
                    y = np.asarray(jax.jit(
                        lambda V: app(V, mu, 0.5, 0.1))(Xs))
                    assert np.array_equal(y, ref), (s, comm, sched, ov,
                                                    krn)
        print(f"s={{s}} grid ok")
print("SSTEP GRID OK")
""", timeout=1500)
    assert "SSTEP GRID OK" in out


def test_sstep_bit_identity_families_and_planned_rowmap():
    """Depth-2/3 bit-identity on the comm-imbalanced families — RoadNet
    and HubNet — including HubNet on a planned commvol RowMap (the map
    planned at the same depth the engine ships)."""
    out = run_distributed(f"""
import numpy as np, jax, jax.numpy as jnp
from repro.matrices import HubNet, RoadNet
from repro.core import make_solver_mesh, panel, build_dist_ell, make_spmv
from repro.core.partition import plan_rowmap
from repro.core.spmv import build_sstep_ell, make_sstep_cheb
from repro.core.chebyshev import chebyshev_filter
mesh = make_solver_mesh(4, 2)
lay = panel(mesh)
degree = 8
mu = np.linspace(1.0, 0.5, degree + 1)
rng = np.random.default_rng(0)
cases = [(RoadNet(**{ROADNET_SMALL!r}), None, 2),
         (HubNet(**{HUBNET_SMALL!r}), None, 3),
         (HubNet(**{HUBNET_SMALL!r}), "commvol", 2)]
for mat, bal, s in cases:
    csr = mat.build_csr()
    rm = plan_rowmap(mat, 4, balance=bal, sstep=s) if bal else None
    D_pad = rm.D_pad if rm else -(-csr.shape[0] // 8) * 8
    ell = build_dist_ell(csr, 4, d_pad=None if rm else D_pad, rowmap=rm)
    sell = build_sstep_ell(csr, 4, s, d_pad=None if rm else D_pad,
                           rowmap=rm)
    X = np.zeros((D_pad, 8))
    X0 = rng.standard_normal((csr.shape[0], 8))
    X[:csr.shape[0]] = X0
    Xp = rm.embed(X0) if rm else X
    with mesh:
        Xs = jax.device_put(jnp.asarray(Xp), lay.vec_sharding(mesh))
        spmv = make_spmv(mesh, lay, ell)
        ref = np.asarray(jax.jit(
            lambda V: chebyshev_filter(spmv, mu, 0.5, 0.1, V))(Xs))
        for comm, sched in (("a2a", "cyclic"), ("compressed", "matching")):
            app = make_sstep_cheb(mesh, lay, sell, comm=comm,
                                  schedule=sched)
            y = np.asarray(jax.jit(lambda V: app(V, mu, 0.5, 0.1))(Xs))
            assert np.array_equal(y, ref), (mat.name, bal, s, comm)
    print(f"{{type(mat).__name__}} bal={{bal}} s={{s}} ok")
print("SSTEP FAMILIES OK")
""", timeout=1500)
    assert "SSTEP FAMILIES OK" in out


def test_machine_fit_recovers_alpha():
    """Satellite 1: κ, b_c, and α are recovered exactly from synthetic
    Eq. 12 + α·rounds samples. The tiny-halo cell (rounds > 0 at χ = 0)
    is what de-collinearizes the latency column from the bytes column —
    exactly the cell ``dryrun --fit-machine`` emits."""
    true = dict(b_m=8.0e11, b_c=4.5e10, kappa=6.5, alpha=25e-6)
    D, N_p, n_nzr, S_d, S_i = 1 << 20, 8, 13.0, 8, 4
    cells = [(0.0, 0.0, 8), (0.4, 1.0, 8), (0.9, 3.0, 8), (0.4, 1.0, 2),
             (0.0, 2.0, 8), (1.5, 1.0, 4), (0.2, 5.0, 8)]
    samples = []
    for chi, rounds, n_b in cells:
        scale = n_b * D / N_p
        t = (scale * (S_d + S_i) * n_nzr / n_b / true["b_m"]
             + true["kappa"] * scale * S_d / true["b_m"]
             + scale * chi * S_d / true["b_c"]
             + true["alpha"] * rounds)
        samples.append(dict(t=t, D=D, N_p=N_p, n_b=n_b, chi=chi,
                            n_nzr=n_nzr, S_d=S_d, rounds=rounds))
    fit = pm.MachineModel.fit(samples, b_m=true["b_m"], S_i=S_i)
    assert fit.kappa == pytest.approx(true["kappa"], rel=1e-8)
    assert fit.b_c == pytest.approx(true["b_c"], rel=1e-8)
    assert fit.alpha == pytest.approx(true["alpha"], rel=1e-8)
    # without any rounds data the latency column is dropped, alpha = 0
    no_rounds = [dict(s, rounds=0.0) for s in samples]
    fit0 = pm.MachineModel.fit(no_rounds, b_m=true["b_m"], S_i=S_i)
    assert fit0.alpha == 0.0


def test_machine_model_roundtrips_alpha(tmp_path):
    """save/load keeps the α field; older JSON without it loads as 0."""
    m = pm.MachineModel("x", b_m=1e12, b_c=5e10, kappa=7.0, alpha=3e-5)
    path = tmp_path / "m.json"
    pm.save_machine(m, str(path))
    assert pm.load_machine(str(path)).alpha == m.alpha
    legacy = json.loads(path.read_text())
    legacy.pop("alpha")
    path.write_text(json.dumps(legacy))
    assert pm.load_machine(str(path)).alpha == 0.0


def test_planner_sstep_default_vs_high_latency():
    """Acceptance: under the default bandwidth-bound machine the best
    plan keeps s = 1; under the high-latency model the best *halo-
    bearing* candidate is an s > 1 cell (comm-free pillar splits, which
    pay no α at all, are allowed to stay on top overall)."""
    hub = HubNet(**HUBNET_SMALL)
    default = plan_layout(hub, 8, n_search=16, sstep=(1, 2, 3))
    assert default.best.sstep == 1, default.report()
    high = plan_layout(hub, 8, n_search=16, sstep=(1, 2, 3),
                       machine=pm.TPU_V5E_HIGHLAT)
    halo = [c for c in high.candidates if c.comm_bytes_per_device > 0]
    assert halo, high.report()
    assert halo[0].sstep > 1, high.report()


def test_bench_schema_s_field():
    """Satellite 2: the ``s`` field is enum'd {1, 2, 3} and nonnegative;
    malformed depths are schema errors."""
    from benchmarks.schema import SSTEP_VALUES, validate_record

    assert SSTEP_VALUES == {1, 2, 3}
    base = dict(table="sstep", family="hubnet", s=2,
                pred_bytes_per_device=10, meas_bytes_per_device=10)
    assert validate_record(base) == []
    for bad in (5, -1, 0, True, 2.0, "2"):
        errs = validate_record(dict(base, s=bad))
        assert errs, bad
        assert any("s" in e for e in errs)


def test_bench_merge_refuses_malformed_sstep_record(tmp_path):
    """Satellite 2 negative test: the merge-on-write path re-validates
    the FULL artifact (old + new records); a malformed ``s`` record
    already in the trajectory of a bench NOT being rerun makes run.py
    refuse to write (exit 2) and leave the file untouched."""
    art = {"schema": "bench-spmv/v1", "generated_unix": 1,
           "benches": ["sstep"],
           "records": [{"table": "sstep", "family": "hubnet", "s": 99}],
           "rows": [{"bench": "sstep", "name": "sstep_x", "us_per_call": 1.0,
                     "derived": ""}]}
    path = tmp_path / "BENCH_spmv.json"
    path.write_text(json.dumps(art))
    before = path.read_text()
    env = dict(os.environ, PYTHONPATH=ROOT)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--only", "table2", "--json", str(path)],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 2, r.stdout + r.stderr
    assert "SCHEMA ERROR" in r.stderr
    assert "s = 99" in r.stderr
    assert path.read_text() == before


def test_fdconfig_rejects_invalid_sstep():
    """FilterDiag validates the axis up front."""
    import jax

    from repro.core import FDConfig, FilterDiag, make_solver_mesh

    jax.config.update("jax_enable_x64", True)
    mat = SpinChainXXZ(8, 4)
    mesh = make_solver_mesh(1, 1)
    with mesh, pytest.raises(ValueError, match="spmv_sstep"):
        FilterDiag(mat.build_csr(), mesh, FDConfig(spmv_sstep=0))


@pytest.mark.slow
def test_fd_solve_sstep_bit_identical_8dev():
    """Full FD solve with spmv_sstep ∈ {2, 3} walks the bit-identical
    iteration path as the s = 1 solver on the 4x2 mesh. The child runs
    with FMA off: XLA's CPU backend contracts the s-step and the s = 1
    filter programs differently, and without it the last-bit differences
    change the s = 2 solve's iteration count."""
    out = run_distributed(f"""
import numpy as np, jax
from repro.core import FDConfig, FilterDiag, make_solver_mesh
from repro.matrices import SpinChainXXZ
mat = SpinChainXXZ(10, 5)
csr = mat.build_csr()
w = np.linalg.eigvalsh(csr.to_dense())
tau = float(w[len(w) // 2])
mesh = make_solver_mesh(4, 2)
res = {{}}
for s in (1, 2, 3):
    cfg = FDConfig(n_target=4, n_search=16, target=tau, tol=1e-8,
                   max_iters=12, spmv_sstep=s)
    with mesh:
        res[s] = FilterDiag(csr, mesh, cfg).solve()
for s in (2, 3):
    assert res[s].iterations == res[1].iterations, s
    assert np.array_equal(res[s].eigenvalues, res[1].eigenvalues), s
print("FD SSTEP OK", res[1].iterations)
""", timeout=1500, xla_flags=NO_FMA)
    assert "FD SSTEP OK" in out
