"""Eigensolver launcher: FD on a ScaMaC-style matrix with selectable
vector layout (the paper's production entry point).

  PYTHONPATH=src python -m repro.launch.solve --family SpinChainXXZ \
      --params n_sites=14,n_up=7 --n-target 8 --target -0.16 \
      --n-row 4 --n-col 2

``--layout auto`` hands the choice to the χ-driven planner
(``core/planner.py``): it enumerates every (n_row x n_col) mesh split,
layout, comm engine (padded ``a2a`` vs sparsity-``compressed`` neighbor
ppermute), round scheduler (``cyclic`` shifts vs greedy ``matching``
rounds for the compressed engine), overlap option, and row partition
(equal ``rows`` vs planned ``commvol`` boundaries,
``core/partition.py``), scores each with the analytic perf model from
the sparsity pattern alone, prints the ranking, and runs the
minimum-predicted-time configuration (``--n-row/--n-col`` are then
ignored; ``--spmv-overlap``, ``--spmv-comm``, ``--spmv-schedule``,
``--spmv-balance``, and ``--spmv-reorder`` are decided by the plan —
an explicitly requested ``--spmv-reorder rcm`` widens the planner's
partition axis).
``--machine`` points the planner at calibrated constants
(``dryrun --fit-machine``) instead of the built-in TPU-v5e model.

``--degraded-ok`` continues with a reduced search space if a column group
is lost (the vertical layer is fault-isolating: bundles of search vectors
are statistically interchangeable).

``--plan-cache PATH`` puts a persistent plan cache in front of the
``--layout auto`` planner (``service/plan_cache.py``): repeat matrices
skip ``plan_layout`` entirely and run the byte-identical cached engine
plan. ``--serve REQUESTS.json`` switches to service mode
(``service/batcher.py``): the JSON lists eigensolve requests; compatible
requests (same sparsity pattern, same planned engine cell) are batched
into one panel as extra vector columns and demuxed bit-identically to
solo solves — see docs/service.md.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os

import numpy as np
import jax

from ..core import FDConfig, FilterDiag, make_solver_mesh, panel
from ..core.layouts import Layout
from ..matrices import get_family


#: JAX's persistent compilation cache when ``JAX_COMPILATION_CACHE_DIR``
#: is not set: a fixed directory in the checkout (the path is part of
#: the cache key, so it must not move between runs).
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    A set ``JAX_COMPILATION_CACHE_DIR`` is read by JAX itself and is
    never overridden here; otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def parse_params(s: str) -> dict:
    out = {}
    for kv in (s or "").split(","):
        if not kv:
            continue
        k, v = kv.split("=")
        try:
            out[k] = int(v)
        except ValueError:
            out[k] = float(v)
    return out


def solve(family: str, params: dict, fd: FDConfig, n_row: int, n_col: int,
          verbose: bool = True, degraded_ok: bool = False,
          machine=None, plan_cache: str | None = None):
    jax.config.update("jax_enable_x64", True)
    n_dev = len(jax.devices())
    mat = get_family(family, **params)
    rowmap = None
    if fd.layout == "auto":
        # χ-driven planner: pick the mesh split AND both SpMV engine axes
        # (overlap, comm) from the sparsity pattern before any mesh is
        # built (core/planner.py). The caller's config is left untouched
        # so it can be reused for another matrix (the plan depends on the
        # pattern). With --plan-cache the result persists keyed by the
        # pattern hash — a repeat matrix skips the planner entirely.
        from ..core import perf_model as pm
        from ..service.plan_cache import PlanCache, cached_plan_layout

        cache = PlanCache(plan_cache) if plan_cache else None
        plan, hit = cached_plan_layout(
            mat, n_dev, n_search=fd.n_search,
            cache=cache,
            d_pad=-(-mat.D // n_dev) * n_dev,
            machine=machine or pm.TPU_V5E,
            reorder=tuple(dict.fromkeys(("none", fd.spmv_reorder))),
            kernel=tuple(dict.fromkeys((False, fd.spmv_kernel))),
            sstep=tuple(dict.fromkeys((1, fd.spmv_sstep))),
            plan_mode=fd.plan_mode)
        if verbose and cache is not None:
            print(f"[plan-cache] {'hit' if hit else 'miss'} "
                  f"({plan_cache})")
        best = plan.best
        if verbose:
            print(plan.report())
            print(f"[auto] running {best.describe()} "
                  f"(spmv_overlap={best.overlap}, spmv_comm={best.comm}, "
                  f"spmv_schedule={best.schedule}, "
                  f"spmv_balance={best.balance}, "
                  f"spmv_reorder={best.reorder}, "
                  f"spmv_kernel={best.kernel}, "
                  f"spmv_sstep={best.sstep})")
        n_row, n_col = best.n_row, best.n_col
        # the chosen split realizes the planned layout; the winning
        # candidate's rowmap (planned at P = n_row·n_col) is handed to
        # FilterDiag verbatim so the map is never re-planned
        rowmap = best.rowmap
        fd = dataclasses.replace(fd, layout="panel", spmv_overlap=best.overlap,
                                 spmv_comm=best.comm,
                                 spmv_schedule=best.schedule,
                                 spmv_balance=best.balance,
                                 spmv_reorder=best.reorder,
                                 spmv_kernel=best.kernel,
                                 spmv_sstep=best.sstep)
    if n_row * n_col > n_dev:
        raise RuntimeError(f"mesh {n_row}x{n_col} needs {n_row*n_col} devices, "
                           f"have {n_dev}")
    mesh = make_solver_mesh(n_row, n_col)
    try:
        with mesh:
            fdd = FilterDiag(mat, mesh, fd, rowmap=rowmap)
            return fdd.solve(verbose=verbose)
    except Exception:
        if not degraded_ok or n_col == 1:
            raise
        # degraded mode: drop one column group worth of search vectors.
        # The device count changed, so any auto-planned rowmap is stale —
        # FilterDiag re-plans one from fd2's balance/reorder fields.
        fd2 = FDConfig(**{**fd.__dict__,
                          "n_search": fd.n_search - fd.n_search // n_col})
        mesh2 = make_solver_mesh(n_row, n_col - 1) if n_col > 1 else mesh
        with mesh2:
            fdd = FilterDiag(mat, mesh2, fd2)
            return fdd.solve(verbose=verbose)


def serve(requests_path: str, plan_cache: str | None = None,
          machine=None, verbose: bool = True):
    """Service mode: solve a JSON batch of eigensolve requests.

    The file is ``{"requests": [{...}], "checkpoint_root": optional,
    "service_seed": optional}``; each request gives ``req_id``,
    ``family``/``params`` and the per-tenant fields (``n_target``,
    ``n_search``, ``target``, ``tol``, ``max_iters``, ``seed``).
    Compatible requests are batched into one panel (docs/service.md).
    """
    jax.config.update("jax_enable_x64", True)
    from ..service import EigenService, SolveRequest
    from ..service.plan_cache import PlanCache

    with open(requests_path) as f:
        spec = json.load(f)
    cache = PlanCache(plan_cache) if plan_cache else None
    svc = EigenService(plan_cache=cache, machine=machine,
                       ckpt_root=spec.get("checkpoint_root"),
                       service_seed=int(spec.get("service_seed", 0)),
                       verbose=verbose)
    for r in spec["requests"]:
        svc.submit(SolveRequest(
            req_id=str(r["req_id"]), family=r["family"],
            params=dict(r.get("params", {})),
            n_target=int(r.get("n_target", 4)),
            n_search=int(r.get("n_search", 16)),
            target=float(r.get("target", 0.0)),
            tol=float(r.get("tol", 1e-9)),
            max_iters=int(r.get("max_iters", 40)),
            seed=int(r.get("seed", 7))))
    results = svc.drain()
    if verbose:
        if cache is not None:
            print(f"[plan-cache] hits={cache.hits} misses={cache.misses} "
                  f"plan_calls={cache.plan_calls}")
        for rid in sorted(results):
            r = results[rid]
            print(f"[{rid}] converged {r.n_converged} in {r.iterations} "
                  f"iterations / {r.total_spmvs} SpMVs; eigenvalues "
                  f"{np.array2string(r.eigenvalues, precision=8)}")
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--family")
    ap.add_argument("--params", default="")
    ap.add_argument("--n-target", type=int, default=8)
    ap.add_argument("--n-search", type=int, default=32)
    ap.add_argument("--target", type=float, default=0.0)
    ap.add_argument("--tol", type=float, default=1e-10)
    ap.add_argument("--max-iters", type=int, default=40)
    ap.add_argument("--n-row", type=int, default=1,
                    help="horizontal-layer width N_row (D sliced over "
                         "N_row row shards; SpMV halo exchange runs here)")
    ap.add_argument("--n-col", type=int, default=1,
                    help="vertical-layer width N_col (search vectors split "
                         "into N_col bundles; no SpMV communication)")
    ap.add_argument("--layout", default="panel",
                    choices=["stack", "panel", "pillar", "auto"],
                    help="filter-phase vector layout on the mesh: 'stack' "
                         "(N_col=1, D over all devices), 'panel' (N_row x "
                         "N_col grid), 'pillar' (N_row=1, comm-free SpMV), "
                         "or 'auto' — the χ-driven planner picks the mesh "
                         "split AND the overlap engine from the sparsity "
                         "pattern (overrides --n-row/--n-col/--spmv-overlap)")
    ap.add_argument("--spmv-overlap", action="store_true",
                    help="split-phase SpMV engine: issue the halo "
                         "exchange first and contract the local ELL block "
                         "while the bytes are in flight (the dry-run's "
                         "'+ov' layout suffix; T = max(T_comm, T_local) + "
                         "T_halo instead of additive Eq. 12)")
    ap.add_argument("--spmv-comm", default="a2a",
                    choices=["a2a", "compressed"],
                    help="halo-exchange engine: 'a2a' (one all_to_all "
                         "padded to the global max pair volume — moved "
                         "bytes scale with chi3) or 'compressed' "
                         "(neighbor ppermute rounds padded per round, "
                         "empty pairs skipped — moved bytes ~ chi2; the "
                         "dry-run's '+cmp' suffix; decided by --layout "
                         "auto)")
    ap.add_argument("--spmv-schedule", default="cyclic",
                    choices=["cyclic", "matching"],
                    help="round scheduler of the compressed halo "
                         "exchange: 'cyclic' (one ppermute round per "
                         "nonzero cyclic shift, pad = that shift's max "
                         "pair) or 'matching' (greedy max-weight "
                         "matchings — hot pairs of different shifts "
                         "share one round's pad, H_matching <= "
                         "H_cyclic; the dry-run's '+mat' suffix; "
                         "decided by --layout auto)")
    ap.add_argument("--spmv-balance", default="rows",
                    choices=["rows", "commvol"],
                    help="row partition of the horizontal layer: 'rows' "
                         "(the paper's equal row blocks) or 'commvol' "
                         "(core/partition.py plans non-uniform shard "
                         "boundaries that minimize the engines' wire "
                         "volumes — per-row cost alpha*nnz + beta*cut, "
                         "prefix-balanced then refined by greedy cut "
                         "descent; the dry-run's '+cv' suffix; decided "
                         "by --layout auto)")
    ap.add_argument("--spmv-reorder", default="none",
                    choices=["none", "rcm"],
                    help="row order applied before partitioning: 'none' "
                         "or 'rcm' (reverse-Cuthill-McKee bandwidth "
                         "reduction — eigenvalues unchanged, "
                         "eigenvectors un-permuted on output; the "
                         "dry-run's '+rcm' suffix; with --layout auto "
                         "an explicit 'rcm' widens the planner's "
                         "partition axis)")
    ap.add_argument("--spmv-kernel", action="store_true",
                    help="Pallas kernel engine: dispatch the local ELL "
                         "contraction to the ell_gather tile kernel and "
                         "the fused Chebyshev recurrence step to the "
                         "cheb_dia kernel where the operator is comm-free "
                         "diagonal-structured (interpret mode off-TPU; "
                         "bit-identical to the jnp engines — see "
                         "docs/kernels.md; with --layout auto an explicit "
                         "kernel request widens the planner's kernel "
                         "axis, scored with the fused kappa=5 term)")
    ap.add_argument("--spmv-sstep", type=int, default=1,
                    help="communication-avoiding s-step filter (seventh "
                         "engine axis): apply the degree-n Chebyshev "
                         "filter in ceil(n/s) depth-s ghost exchanges "
                         "instead of n per-SpMV halo exchanges — the "
                         "exchange ships the depth-s BFS ghost zone once "
                         "and s recurrence steps run on the extended "
                         "block (redundant ghost-row work, fewer "
                         "latency-bound rounds; the dry-run's '+s2'/'+s3' "
                         "cell suffixes; bit-identical to the s=1 engines "
                         "— see docs/s-step.md). With --layout auto an "
                         "explicit s > 1 widens the planner's s-step "
                         "axis, scored with the alpha-latency machine "
                         "term (s > 1 wins only when rounds, not bytes, "
                         "dominate)")
    ap.add_argument("--plan-mode", default="auto",
                    choices=["exact", "sampled", "auto"],
                    help="pattern-pass strategy for planning (partition "
                         "boundaries, chi counts, comm plans): 'exact' "
                         "(full pattern scans; the partition axis is "
                         "silently dropped past the size gate), 'sampled' "
                         "(core/sketch.py plans from a seeded row "
                         "subsample — Horvitz-Thompson chi/L estimates "
                         "with a confidence band and a coarsened commvol "
                         "descent; D >= 1e7 matrix-free instances plan in "
                         "seconds), or 'auto' (exact below the gate, "
                         "sampled above it)")
    ap.add_argument("--machine", default="tpu-v5e",
                    help="machine model for --layout auto planning: "
                         "'tpu-v5e', 'meggie', or a path to a JSON model "
                         "saved by `dryrun --fit-machine` (calibrated "
                         "b_c/kappa)")
    ap.add_argument("--degraded-ok", action="store_true")
    ap.add_argument("--plan-cache", default=None, metavar="PATH",
                    help="persistent plan cache (service/plan_cache.py): "
                         "a merge-on-write JSON store of --layout auto "
                         "planner results keyed by (pattern hash, P, "
                         "machine fingerprint) — a repeat matrix skips "
                         "plan_layout and runs the byte-identical cached "
                         "engine plan; bumped cache_version invalidates "
                         "old entries wholesale")
    ap.add_argument("--serve", default=None, metavar="REQUESTS.json",
                    help="service mode (service/batcher.py): solve a JSON "
                         "batch of eigensolve requests; compatible "
                         "requests (same sparsity pattern, same planned "
                         "engine cell) share one SpMV panel as extra "
                         "vector columns and demux bit-identically to "
                         "solo solves (--family etc. are ignored; "
                         "see docs/service.md)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.spmv_schedule != "cyclic" and args.spmv_comm != "compressed" \
            and args.layout != "auto":
        ap.error(f"--spmv-schedule {args.spmv_schedule} requires "
                 "--spmv-comm compressed (or --layout auto, which picks "
                 "both)")
    from ..core import perf_model as pm

    machine = pm.resolve_machine(args.machine)
    if args.serve:
        serve(args.serve, plan_cache=args.plan_cache, machine=machine)
        return
    if not args.family:
        ap.error("--family is required (unless --serve is given)")
    fd = FDConfig(n_target=args.n_target, n_search=args.n_search,
                  target=args.target, tol=args.tol, max_iters=args.max_iters,
                  layout=args.layout, spmv_overlap=args.spmv_overlap,
                  spmv_comm=args.spmv_comm,
                  spmv_schedule=args.spmv_schedule,
                  spmv_balance=args.spmv_balance,
                  spmv_reorder=args.spmv_reorder,
                  spmv_kernel=args.spmv_kernel,
                  spmv_sstep=args.spmv_sstep,
                  plan_mode=args.plan_mode)
    res = solve(args.family, parse_params(args.params), fd,
                args.n_row, args.n_col, degraded_ok=args.degraded_ok,
                machine=machine, plan_cache=args.plan_cache)
    print(f"converged {res.n_converged} eigenpairs in {res.iterations} "
          f"iterations / {res.total_spmvs} SpMVs "
          f"({res.redistributions} redistributions)")
    print("eigenvalues:", np.array2string(res.eigenvalues, precision=10))


if __name__ == "__main__":
    main()
