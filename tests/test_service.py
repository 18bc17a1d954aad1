"""Eigensolve service: plan cache, batching demux, fault-injection resume.

Three concerns, matching the service contract:

  * the **plan cache** — pattern hashing is slot-order invariant and
    size/family distinct, Plan JSON round-trips losslessly (verified down
    to ``comm_plan`` bytes recomputed from the restored RowMap), a cache
    hit never calls the planner, and a corrupt store degrades to a miss
    on read / an explicit refusal on write;
  * **batching** — compatible requests share one panel and demux
    bit-identically to solo solves (in-process here; on the real
    8-device mesh in the slow subprocess test);
  * **fault injection** — a job killed at an injected iteration (and, in
    the harsher variant, with the newest checkpoint's ``_COMMITTED``
    marker destroyed) resumes from the last committed step and converges
    to bit-identical Ritz values.
"""
import json
import os

import numpy as np
import pytest

import jax

from repro.core import FDConfig, FilterDiag, make_solver_mesh
from repro.core import perf_model as pm
from repro.core.planner import comm_plan, plan_layout
from repro.matrices import get_family
from repro.matrices.sparse import CSR
from repro.runtime import StragglerWatchdog, Supervisor, SupervisorConfig
from repro.service import (
    CACHE_VERSION,
    EigenService,
    FilterDiagJob,
    PlanCache,
    SolveRequest,
    cache_key,
    cached_plan_layout,
    machine_fingerprint,
    pattern_hash,
    plan_from_json,
    plan_to_json,
)
from repro.service import plan_cache as plan_cache_mod
from tests._hypothesis_compat import given, settings, st


# ------------------------------------------------------- pattern hash --


def _random_csr(D: int, seed: int, avg_deg: int = 4) -> CSR:
    rng = np.random.default_rng(seed)
    rows, cols = [], []
    for r in range(D):
        deg = rng.integers(1, 2 * avg_deg)
        c = rng.integers(0, D, size=deg)
        rows.append(np.full(len(c), r)), cols.append(c)
    rows, cols = np.concatenate(rows), np.concatenate(cols)
    indptr = np.zeros(D + 1, dtype=np.int64)
    np.add.at(indptr, rows + 1, 1)
    return CSR(indptr=np.cumsum(indptr), indices=cols.astype(np.int64),
               data=None, shape=(D, D))


def _shuffle_within_rows(m: CSR, seed: int) -> CSR:
    """Same pattern, different ELL slot order: permute each row's entries."""
    rng = np.random.default_rng(seed)
    idx = np.concatenate([
        m.indptr[r] + rng.permutation(m.indptr[r + 1] - m.indptr[r])
        for r in range(m.shape[0])
    ]) if m.shape[0] else np.zeros(0, dtype=np.int64)
    return CSR(indptr=m.indptr, indices=m.indices[idx], data=None,
               shape=m.shape)


@settings(max_examples=8)
@given(D=st.integers(min_value=2, max_value=60),
       seed=st.integers(min_value=0, max_value=10**6))
def test_pattern_hash_slot_order_invariant(D, seed):
    """The hash sees the canonical pattern, not the storage order."""
    m = _random_csr(D, seed)
    assert pattern_hash(m) == pattern_hash(_shuffle_within_rows(m, seed + 1))
    # duplicated entries collapse to the same canonical pattern too
    dup = CSR(indptr=m.indptr * 2,
              indices=np.repeat(m.indices, 2),
              data=None, shape=m.shape)
    assert pattern_hash(m) == pattern_hash(dup)


def test_pattern_hash_distinct_across_families_and_sizes():
    mats = [
        get_family("SpinChainXXZ", n_sites=8, n_up=4),
        get_family("SpinChainXXZ", n_sites=10, n_up=5),
        get_family("RoadNet", n=500, w=2, m=64, k=4),
        get_family("HubNet", n=500, w=2, h=4, m=48, k=4),
    ]
    hashes = [pattern_hash(m) for m in mats]
    assert len(set(hashes)) == len(hashes)


# -------------------------------------------------- plan serialization --


# deadline=None: one planning pass takes ~0.4 s, over Hypothesis's
# 200 ms default; the test checks a round trip, not a time
@settings(max_examples=3, deadline=None)
@given(spec=st.sampled_from([
    ("SpinChainXXZ", dict(n_sites=8, n_up=4)),
    ("RoadNet", dict(n=500, w=2, m=64, k=4)),
    ("HubNet", dict(n=500, w=2, h=4, m=48, k=4)),
]))
def test_plan_roundtrip_lossless(spec):
    """plan -> JSON -> plan preserves every candidate (scalars AND the
    RowMap), verified independently by recomputing the comm plan from the
    restored RowMap: byte counts must reproduce exactly."""
    family, params = spec
    mat = get_family(family, **params)
    D = mat.shape[0] if hasattr(mat, "shape") else mat.D
    plan = plan_layout(mat, 8, n_search=16, d_pad=-(-D // 8) * 8)
    plan2 = plan_from_json(json.loads(json.dumps(plan_to_json(plan))))
    assert plan2.candidates == plan.candidates  # scalar fields (frozen eq)
    for c, c2 in zip(plan.candidates, plan2.candidates):
        if c.rowmap is None:
            assert c2.rowmap is None
            continue
        np.testing.assert_array_equal(c.rowmap.perm, c2.rowmap.perm)
        np.testing.assert_array_equal(c.rowmap.boundaries,
                                      c2.rowmap.boundaries)
        assert (c.rowmap.R, c.rowmap.sstep) == (c2.rowmap.R, c2.rowmap.sstep)
    best, best2 = plan.best, plan2.best
    if best2.rowmap is not None:       # mirror plan_layout's comm_plan calls
        cp = comm_plan(mat, best2.n_row, rowmap=best2.rowmap)
    else:
        cp = comm_plan(mat, best2.n_row, d_pad=-(-D // 8) * 8,
                       sstep=best2.sstep)
    S_d = getattr(mat, "S_d", 8)
    n_b = plan.n_search // best2.n_col
    assert (cp.comm_bytes_per_device(best2.comm, n_b, S_d, best2.schedule)
            == best.comm_bytes_per_device)


# ------------------------------------------------------- cache behavior --


def _spin_mat():
    return get_family("SpinChainXXZ", n_sites=8, n_up=4)


def test_cache_hit_skips_planner(tmp_path, monkeypatch):
    """Second identical request comes from disk: plan_layout not called,
    and the cached plan selects the byte-identical engine cell."""
    calls = {"n": 0}
    real = plan_cache_mod.planner.plan_layout

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(plan_cache_mod.planner, "plan_layout", counting)
    cache = PlanCache(str(tmp_path / "plans.json"))
    mat = _spin_mat()
    plan1, hit1 = cached_plan_layout(mat, 4, n_search=8, cache=cache)
    plan2, hit2 = cached_plan_layout(mat, 4, n_search=8, cache=cache)
    assert (hit1, hit2) == (False, True)
    assert calls["n"] == 1 and cache.plan_calls == 1
    assert cache.hits == 1 and cache.misses == 1
    assert plan2.candidates == plan1.candidates
    assert plan2.best == plan1.best
    # different n_search is a different key -> planner runs again
    _, hit3 = cached_plan_layout(mat, 4, n_search=16, cache=cache)
    assert not hit3 and calls["n"] == 2


def test_cache_version_bump_invalidates(tmp_path, monkeypatch):
    cache = PlanCache(str(tmp_path / "plans.json"))
    mat = _spin_mat()
    _, hit = cached_plan_layout(mat, 4, n_search=8, cache=cache)
    assert not hit
    monkeypatch.setattr(plan_cache_mod, "CACHE_VERSION", CACHE_VERSION + 1)
    _, hit = cached_plan_layout(mat, 4, n_search=8, cache=cache)
    assert not hit, "a version bump must miss, never misapply old plans"


def test_cache_key_machine_fingerprint(tmp_path):
    """A re-calibrated machine model (same name, new constants) must not
    hit plans fit to the old constants."""
    m1 = pm.TPU_V5E
    m2 = pm.MachineModel(name=m1.name, b_m=m1.b_m, b_c=m1.b_c,
                         kappa=m1.kappa * 1.01, alpha=m1.alpha)
    assert machine_fingerprint(m1) != machine_fingerprint(m2)
    assert (cache_key("ph", 8, m1, n_search=16)
            != cache_key("ph", 8, m2, n_search=16))


def test_corrupt_store_miss_on_get_refuse_on_put(tmp_path):
    path = tmp_path / "plans.json"
    mat = _spin_mat()
    cache = PlanCache(str(path))
    plan, _ = cached_plan_layout(mat, 4, n_search=8, cache=cache)
    # truncated write / garbage: reads degrade to a miss ...
    path.write_text("{not json")
    assert cache.get("anything") is None
    # ... and writes refuse to merge into corruption
    with pytest.raises(ValueError, match="refusing to merge"):
        cache.put("k", plan)
    # schema-invalid (valid JSON): same contract
    path.write_text(json.dumps({"schema": "bogus", "entries": {}}))
    assert cache.get("anything") is None
    with pytest.raises(ValueError, match="refusing to merge"):
        cache.put("k", plan)


def test_merge_on_write_keeps_existing_entries(tmp_path):
    cache = PlanCache(str(tmp_path / "plans.json"))
    mat = _spin_mat()
    cached_plan_layout(mat, 4, n_search=8, cache=cache)
    cached_plan_layout(mat, 4, n_search=16, cache=cache)
    with open(cache.path) as f:
        store = json.load(f)
    assert len(store["entries"]) == 2


def test_concurrent_writers_lose_no_records(tmp_path):
    """Several *processes* merging into one store concurrently: every
    record survives (the put path read-merge-writes under an exclusive
    lock) and no reader ever observes a torn file (writes land via
    atomic tmp+rename, so a concurrent load parses a complete store or
    none)."""
    import subprocess
    import sys

    from tests.conftest import SRC

    path = tmp_path / "plans.json"
    cache = PlanCache(str(path))
    mat = _spin_mat()
    plan, _ = cached_plan_layout(mat, 4, n_search=8, cache=cache)
    plan_json = json.dumps(plan_to_json(plan))
    (tmp_path / "plan.json").write_text(plan_json)
    n_writers, n_keys = 6, 5
    script = (
        "import json, sys\n"
        "from repro.service import PlanCache, plan_from_json\n"
        "wid = int(sys.argv[1])\n"
        f"plan = plan_from_json(json.load(open({str(tmp_path / 'plan.json')!r})))\n"
        f"cache = PlanCache({str(path)!r})\n"
        f"for j in range({n_keys}):\n"
        "    cache.put(f'writer{wid}-key{j}', plan)\n"
    )
    procs = [subprocess.Popen([sys.executable, "-c", script, str(i)],
                              env=dict(os.environ, PYTHONPATH=SRC),
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for i in range(n_writers)]
    # poll the store while the writers race: every observed state must
    # be complete, parseable JSON (the atomic-rename contract)
    while any(p.poll() is None for p in procs):
        if path.exists():
            try:
                store = json.loads(path.read_text())
            except ValueError as e:  # pragma: no cover - the defect
                for p in procs:
                    p.kill()
                raise AssertionError(f"torn store observed mid-race: {e}")
            assert "entries" in store
    for p in procs:
        out, err = p.communicate()
        assert p.returncode == 0, f"writer failed:\n{out}\n{err}"
    store = json.loads(path.read_text())
    keys = {f"writer{i}-key{j}"
            for i in range(n_writers) for j in range(n_keys)}
    missing = keys - set(store["entries"])
    assert not missing, f"concurrent merge lost {len(missing)}: {missing}"
    # and every record is still a loadable, well-formed plan
    fresh = PlanCache(str(path))
    for k in sorted(keys):
        got = fresh.get(k)
        assert got is not None and got.best == plan.best, k


def test_sampled_plan_keys_distinct_from_exact(tmp_path):
    """plan_mode is part of the cache key: a sampled plan of a pattern
    never hits the exact plan of the same pattern (and vice versa),
    while each mode hits itself."""
    cache = PlanCache(str(tmp_path / "plans.json"))
    mat = _spin_mat()
    _, hit_e = cached_plan_layout(mat, 4, n_search=8, cache=cache,
                                  plan_mode="exact")
    _, hit_s = cached_plan_layout(mat, 4, n_search=8, cache=cache,
                                  plan_mode="sampled")
    assert (hit_e, hit_s) == (False, False), \
        "sampled plan hit the exact entry of the same pattern"
    _, hit_e2 = cached_plan_layout(mat, 4, n_search=8, cache=cache,
                                   plan_mode="exact")
    _, hit_s2 = cached_plan_layout(mat, 4, n_search=8, cache=cache,
                                   plan_mode="sampled")
    assert (hit_e2, hit_s2) == (True, True)
    assert cache.plan_calls == 2
    assert cache_key("ph", 4, pm.TPU_V5E, n_search=8, plan_mode="exact") \
        != cache_key("ph", 4, pm.TPU_V5E, n_search=8, plan_mode="sampled")


def test_probe_pattern_hash_above_threshold():
    """Families past PATTERN_HASH_PROBE_D hash from a deterministic row
    probe (milliseconds at D = 10⁷): stable across calls, distinct
    across sizes and families, and orthogonal to the full-pattern hash
    space used below the threshold."""
    from repro.matrices import HubNet, RoadNet

    big = RoadNet(n=3_000_000, w=1, m=400, k=2)
    assert big.D > plan_cache_mod.PATTERN_HASH_PROBE_D
    h1, h2 = pattern_hash(big), pattern_hash(big)
    assert h1 == h2
    assert h1 != pattern_hash(RoadNet(n=3_000_001, w=1, m=400, k=2))
    assert h1 != pattern_hash(HubNet(n=3_000_000, w=1, h=4, m=400, k=2))
    # below the threshold the full pattern pass is used — the small
    # family's hash is unaffected by the probe fast path
    small = RoadNet(n=4000, w=2, m=256, k=4)
    assert pattern_hash(small) == pattern_hash(small.build_csr())


# --------------------------------------------- fault-injection resume --


def _make_fd(n_search=8, max_iters=30, seed=3):
    mat = _spin_mat()
    cfg = FDConfig(n_search=n_search, n_target=4, target=-1.5, tol=1e-8,
                   max_iters=max_iters, seed=seed)
    mesh = make_solver_mesh(1, 1)
    return FilterDiag(mat, mesh, cfg)


def test_fault_injection_resume_bit_identical(tmp_path):
    """Kill the job at injected iteration k; the supervisor restores the
    last committed step and the finished Ritz values match the
    uninterrupted run exactly (same ops on bit-identically restored
    state)."""
    clean = _make_fd().solve()
    assert clean.n_converged == 4

    faults = {"armed": True}

    def fault_hook(step):
        if step == 4 and faults["armed"]:
            faults["armed"] = False
            raise RuntimeError("simulated node failure mid-sweep")

    sup = Supervisor(str(tmp_path), SupervisorConfig(checkpoint_interval=1,
                                                     max_restarts=2))
    job = FilterDiagJob(_make_fd())
    state = sup.run_job(job, fault_hook=fault_hook)
    assert sup.restarts == 1 and not faults["armed"]
    res = job.result(state)
    np.testing.assert_array_equal(res.eigenvalues, clean.eigenvalues)
    np.testing.assert_array_equal(res.residuals, clean.residuals)
    assert res.iterations == clean.iterations


def test_crash_mid_checkpoint_falls_back_to_committed(tmp_path):
    """The harsher crash: the failure also destroys the newest
    checkpoint's ``_COMMITTED`` marker (a mid-write crash). Resume must
    use the previous committed step — and still finish bit-identically."""
    clean = _make_fd().solve()

    faults = {"armed": True}

    def fault_hook(step):
        if step >= 5 and faults["armed"]:
            faults["armed"] = False
            newest = max(n for n in os.listdir(tmp_path)
                         if n.startswith("step_") and not n.endswith(".tmp"))
            os.remove(tmp_path / newest / "_COMMITTED")
            raise RuntimeError("node died while committing")

    sup = Supervisor(str(tmp_path), SupervisorConfig(checkpoint_interval=1,
                                                     max_restarts=2))
    job = FilterDiagJob(_make_fd())
    state = sup.run_job(job, fault_hook=fault_hook)
    assert sup.restarts == 1
    res = job.result(state)
    np.testing.assert_array_equal(res.eigenvalues, clean.eigenvalues)
    np.testing.assert_array_equal(res.residuals, clean.residuals)


def test_resume_refuses_mismatched_rowmap(tmp_path):
    """A checkpoint written under one row decomposition must not silently
    continue under another."""
    from repro.core.partition import plan_rowmap
    from repro.service.jobs import pack_state, unpack_state

    fd = _make_fd()
    state = fd.init_state()
    tree, extra = pack_state(state, fd)
    mat = _spin_mat()
    rm = plan_rowmap(mat, 2, balance="commvol")
    cfg = FDConfig(n_search=8, spmv_balance="commvol")
    fd2 = FilterDiag(mat, make_solver_mesh(1, 1), cfg, rowmap=rm)
    with pytest.raises(ValueError, match="rowmap"):
        unpack_state(tree, extra, fd2)


def test_straggler_watchdog_flags_spike():
    wd = StragglerWatchdog(k_sigma=3.0, warmup=3, min_slack=1e-3)
    assert not any(wd.observe(i, 0.1) for i in range(8))
    assert wd.observe(8, 0.5)          # 5x spike after a steady baseline
    assert wd.flagged and wd.flagged[-1][0] == 8


# ----------------------------------------------------- batching demux --


_REQS = dict(family="SpinChainXXZ", params=dict(n_sites=8, n_up=4),
             n_target=3, n_search=8, tol=1e-8, max_iters=30)


def test_duplicate_request_id_rejected():
    svc = EigenService()
    svc.submit(SolveRequest("a", **_REQS))
    with pytest.raises(ValueError, match="duplicate"):
        svc.submit(SolveRequest("a", **_REQS))


def test_batched_demux_matches_solo_inprocess(tmp_path):
    """Two co-batched requests (different targets/seeds/degrees) demux to
    the exact solo results; the shared plan comes through the cache."""
    cache = PlanCache(str(tmp_path / "plans.json"))

    def run(ids):
        svc = EigenService(plan_cache=cache,
                           ckpt_root=str(tmp_path / ("_".join(ids))))
        reqs = {"a": SolveRequest("a", **_REQS, target=-1.5, seed=11),
                "b": SolveRequest("b", **_REQS, target=0.5, seed=22)}
        for i in ids:
            svc.submit(reqs[i])
        return svc.drain()

    both = run(["a", "b"])
    solo_a = run(["a"])["a"]
    solo_b = run(["b"])["b"]
    for solo, rid in ((solo_a, "a"), (solo_b, "b")):
        np.testing.assert_array_equal(both[rid].eigenvalues, solo.eigenvalues)
        np.testing.assert_array_equal(both[rid].residuals, solo.residuals)
        assert both[rid].iterations == solo.iterations
        assert both[rid].total_spmvs == solo.total_spmvs
    # one pattern, three drains: planned exactly once
    assert cache.plan_calls == 1 and cache.hits >= 2


@pytest.mark.slow
def test_batched_demux_bit_identical_8dev():
    """Acceptance: on the 8-device mesh the batched panel demuxes
    bit-identically to solo solves — same planned engine cell, extra
    columns only."""
    from tests.conftest import run_distributed

    out = run_distributed("""
import numpy as np
from repro.service import EigenService, SolveRequest

REQS = dict(family="SpinChainXXZ", params=dict(n_sites=10, n_up=5),
            n_target=3, n_search=16, tol=1e-8, max_iters=30)

def run(ids):
    svc = EigenService()
    reqs = {"a": SolveRequest("a", **REQS, target=-3.0, seed=11),
            "b": SolveRequest("b", **REQS, target=0.0, seed=22)}
    for i in ids:
        svc.submit(reqs[i])
    return svc.drain()

both = run(["a", "b"])
solo = {"a": run(["a"])["a"], "b": run(["b"])["b"]}
for rid in ("a", "b"):
    assert np.array_equal(both[rid].eigenvalues, solo[rid].eigenvalues), rid
    assert np.array_equal(both[rid].residuals, solo[rid].residuals), rid
    assert both[rid].iterations == solo[rid].iterations
print("DEMUX OK", both["a"].iterations, both["b"].iterations)
""", timeout=1800)
    assert "DEMUX OK" in out
