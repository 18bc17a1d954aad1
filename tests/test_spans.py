"""The solver's spans (``core/spans.py``): the tree FD records, syncs only
while a profiler trace records, the bounded ring, the compile clock, the
named scopes in the compiled programs, and manifests written while the
state still carried its timers."""
import json
import time

import numpy as np
import pytest

import jax

from conftest import run_distributed
from repro.core import FDConfig, FilterDiag, make_solver_mesh, spans
from repro.matrices import SpinChainXXZ


def _since(mark: int) -> list:
    """Spans recorded after the span id ``mark``."""
    return [s for s in spans.recorded() if s.id > mark]


def _mark() -> int:
    with spans.span("mark") as s:
        pass
    return s.id


def _children(recs) -> dict:
    """span id -> sorted names of the spans recorded inside it."""
    out = {s.id: [] for s in recs}
    for s in recs:
        if s.parent in out:
            out[s.parent].append(s.name)
    return {k: sorted(v) for k, v in out.items()}


@pytest.fixture(scope="module")
def fd():
    cfg = FDConfig(n_target=2, n_search=8, target=-3.0, tol=1e-8,
                   max_iters=40, layout="stack")
    return FilterDiag(SpinChainXXZ(8, 4), make_solver_mesh(1, 1), cfg)


def test_span_tree_parents_and_counts():
    mark = _mark()
    with spans.span("outer", columns=4) as outer:
        with spans.span("a") as a:
            a.counts["steps"] = 3
        with spans.span("b"):
            with spans.span("c"):
                pass
    recs = _since(mark)
    assert [s.name for s in recs] == ["a", "c", "b", "outer"]
    by = {s.name: s for s in recs}
    assert by["outer"].parent is None and by["outer"].id == outer.id
    assert by["a"].parent == by["b"].parent == outer.id
    assert by["c"].parent == by["b"].id
    assert by["a"].counts == {"steps": 3}
    assert by["outer"].counts == {"columns": 4}
    for s in recs:
        assert s.start <= s.end
    assert by["outer"].start <= by["a"].start <= by["c"].end \
        <= by["outer"].end


def test_span_closes_on_an_exception():
    mark = _mark()
    with pytest.raises(ValueError):
        with spans.span("outer"):
            with spans.span("inner"):
                raise ValueError
    assert [s.name for s in _since(mark)] == ["inner", "outer"]
    with spans.span("after") as s:
        pass
    assert s.parent is None


def test_ring_stays_bounded():
    mark = _mark()
    for _ in range(spans.RING + 10):
        with spans.span("fill"):
            pass
    recs = spans.recorded()
    assert len(recs) == spans.RING
    assert all(s.name == "fill" for s in recs)
    assert recs[0].id == mark + 11 and recs[-1].id == mark + spans.RING + 10


@pytest.fixture
def syncs(monkeypatch):
    """Counts ``jax.block_until_ready`` calls."""
    calls = []
    inner = jax.block_until_ready

    def count(x):
        calls.append(1)
        return inner(x)

    monkeypatch.setattr(jax, "block_until_ready", count)
    return calls


def test_end_on_syncs_only_while_tracing(syncs, tmp_path):
    x = jax.numpy.ones(3)
    with spans.span("untraced") as s:
        assert s.end_on(x) is x
    assert not spans.tracing() and syncs == []
    with jax.profiler.trace(str(tmp_path)):
        assert spans.tracing()
        with spans.span("traced") as s:
            assert s.end_on(x) is x
    assert syncs == [1]


def test_solver_syncs_only_while_tracing(fd, syncs, tmp_path):
    """An untraced outer iteration makes no sync of its own; a traced one
    ends ``orthogonalize`` and ``filter`` on their outputs."""
    state = fd.init_state(jax.random.PRNGKey(3))
    state = fd.step(state)
    assert syncs == []
    mark = _mark()
    with jax.profiler.trace(str(tmp_path)):
        state = fd.step(state)
    assert len(syncs) == 2
    recs = _since(mark)
    kids = _children(recs)
    (an,) = [s for s in recs if s.name == "step_analyze"]
    (fl,) = [s for s in recs if s.name == "step_filter"]
    assert kids[an.id] == ["choose_filter", "orthogonalize", "ritz"]
    assert kids[fl.id] == ["filter"]
    by = {s.name: s for s in recs}
    assert by["filter"].counts == {"degree": fl.counts["degree"],
                                   "columns": 8}
    assert by["choose_filter"].counts["degree"] == fl.counts["degree"]
    assert by["ritz"].counts == {"columns": 8, "fetches": 2}
    assert an.end <= fl.start


def test_solve_records_lanczos_and_one_tree_per_iteration(fd):
    mark = _mark()
    res = fd.solve(jax.random.PRNGKey(5))
    recs = _since(mark)
    names = [s.name for s in recs]
    (lz,) = [s for s in recs if s.name == "lanczos"]
    assert lz.counts == {"steps": fd.cfg.lanczos_steps,
                         "fetches": 2 * fd.cfg.lanczos_steps}
    # the last analysis converges: no filter after it
    assert names.count("step_analyze") == res.iterations + 1
    assert names.count("step_filter") == res.iterations
    assert names.count("filter") == res.iterations
    assert all(s.parent is None for s in recs
               if s.name in ("lanczos", "step_analyze", "step_filter"))


def test_batched_filter_span(tmp_path):
    from repro.service import EigenService, PlanCache, SolveRequest

    svc = EigenService(plan_cache=PlanCache(str(tmp_path / "plans.json")),
                       ckpt_root=str(tmp_path / "ckpt"))
    req = dict(family="SpinChainXXZ", params={"n_sites": 8, "n_up": 4},
               n_target=2, n_search=8, tol=1e-8, max_iters=40)
    svc.submit(SolveRequest("a", target=-3.0, seed=1, **req))
    svc.submit(SolveRequest("b", target=0.5, seed=2, **req))
    mark = _mark()
    svc.drain()
    recs = _since(mark)
    batches = [s for s in recs if s.name == "batch_filter"]
    assert batches
    kids = _children(recs)
    assert all(kids[b.id] == ["filter"] for b in batches)
    assert all(b.counts["columns"] == 8 * b.counts["requests"]
               for b in batches)
    assert any(b.counts["requests"] == 2 for b in batches)


def test_compile_clock_counts_a_fresh_compile():
    clock = spans.COMPILES
    secs, n = clock.secs, len(clock.events)
    lo = time.perf_counter()
    f = jax.jit(lambda x: jax.numpy.sin(x) * 3.25 + x)
    f(np.arange(7.0)).block_until_ready()
    hi = time.perf_counter()
    assert clock.secs > secs
    assert len(clock.events) > n
    assert clock.between(lo, hi) > 0
    assert {e for t, e, _, _ in clock.events if lo <= t <= hi} \
        <= set(clock.EVENTS)
    assert clock.between(hi + 1.0, hi + 2.0) == 0.0


def test_compile_clock_counts_nested_events_once():
    """A ``jit`` traced inside another's trace, and a persistent-cache read
    inside the backend compile that asked for it, add no seconds: the
    clock reads the outermost trace, the lowering and the backend compile
    of each call, cold and from the cache."""
    out = run_distributed("""
import json, tempfile, time
import numpy as np
import jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", tempfile.mkdtemp())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from repro.core.spans import COMPILES as clock
raw = []
jax.monitoring.register_event_duration_secs_listener(
    lambda e, s, **_: raw.append((e.split("/")[-1], s))
    if e in clock.EVENTS else None)

@jax.jit
def inner(x):
    for _ in range(100):
        x = jnp.sin(x) * 1.01 + x
    return x

for _ in range(2):
    raw.clear()
    cache_s = clock.cache_s
    lo = time.perf_counter()
    jax.jit(lambda x: inner(x) * 2 + inner(x + 1))(np.arange(8.0)).block_until_ready()
    got = clock.between(lo, time.perf_counter())
    print("CALL", json.dumps(dict(raw=raw, got=got, hits=clock.hits,
                                  cache_s=clock.cache_s - cache_s)))
    jax.clear_caches()
""", n_devices=1)
    calls = [json.loads(line[5:]) for line in out.splitlines()
             if line.startswith("CALL ")]
    assert [c["hits"] for c in calls] == [0, 1]
    for c in calls:
        secs = {}
        for e, s in c["raw"]:
            secs.setdefault(e, []).append(s)
        (lower,), (compile_,) = (secs["jaxpr_to_mlir_module_duration"],
                                 secs["backend_compile_duration"])
        outermost = max(secs["jaxpr_trace_duration"])
        want = outermost + lower + compile_
        assert c["got"] == pytest.approx(want, abs=2e-4)
        # the inner traces alone are well above that margin
        assert sum(secs["jaxpr_trace_duration"]) - outermost > 5e-3
        assert c["cache_s"] == sum(secs.get("cache_retrieval_time_sec", []))
    # the read from the cache lies inside its compile, above the margin
    read = dict(calls[1]["raw"])
    assert 2e-4 < read["cache_retrieval_time_sec"] \
        < read["backend_compile_duration"]


def test_programs_carry_named_scopes(fd):
    """The scopes are op metadata in the compiled programs."""
    V = fd.random_search_vectors(jax.random.PRNGKey(0))
    ortho = fd.orthogonalize.lower(V).compile().as_text()
    assert "fd.orthogonalize/" in ortho and "fd.local_qr" in ortho
    ritz = fd.ritz.func.lower(*fd.ritz.args, V).compile().as_text()
    for scope in ("fd.ritz/", "fd.spmv", "fd.gram", "fd.eigh",
                  "fd.residuals"):
        assert scope in ritz, scope
    mu = jax.numpy.zeros(33)
    filt = fd.filter.func.lower(*fd.filter.args, V, mu, 0.1, 0.0)
    text = filt.compile().as_text()
    assert "fd.filter/" in text and "fd.spmv" in text \
        and "fd.cheb_update" in text


def test_manifest_with_removed_timers_loads(fd):
    """A checkpoint manifest written while the state still carried
    ``wall_time``/``redist_time`` restores; the timers are dropped."""
    from repro.service.jobs import pack_state, unpack_state

    state = fd.init_state(jax.random.PRNGKey(9))
    tree, extra = pack_state(fd.step(state), fd)
    assert "wall_time" not in extra and "redist_time" not in extra
    old = dict(extra, wall_time=1.5, redist_time=0.25)
    back = unpack_state(tree, old, fd)
    assert back.iteration == 1 and back.total_spmvs == state.total_spmvs
    assert not hasattr(back, "wall_time")


def test_redistribution_spans_two_columns():
    """On a 1x2 panel each filter sits between a ``to_panel`` and a
    ``to_stack`` span, and the state counts both redistributions."""
    out = run_distributed("""
import jax
from repro.core import FDConfig, FilterDiag, make_solver_mesh, spans
from repro.matrices import SpinChainXXZ
cfg = FDConfig(n_target=2, n_search=8, target=-3.0, tol=1e-8,
               max_iters=3, layout="panel")
fd = FilterDiag(SpinChainXXZ(8, 4), make_solver_mesh(1, 2), cfg)
state = fd.step(fd.init_state(jax.random.PRNGKey(1)))
recs = spans.recorded()
(fl,) = [s for s in recs if s.name == "step_filter"]
kids = [s.name for s in recs if s.parent == fl.id]
print("KIDS", ",".join(kids), state.redistributions)
""", n_devices=2)
    assert "KIDS to_panel,filter,to_stack 2" in out
