"""Request queue + batcher: the vertical layer as a batching dimension.

Every SpMV/filter op of the engine grid acts column-wise independently —
``spmv(V)[:, j]`` depends only on ``V[:, j]``, the Chebyshev recurrence
is elementwise over columns, and the stack↔panel redistribution only
*moves* values. So vectors from different filter-diagonalization
requests can share one panel: the batcher concatenates the pending
filter blocks of compatible requests into one ``[D_pad, Σ n_b]`` panel,
runs ONE redistribute + Chebyshev sweep + redistribute, and demuxes
per-request column slices — bit-identically to serving each request
alone (tests/test_service.py asserts exact equality on the 8-device
mesh).

Compatibility = same ``pattern_hash`` (same operator), same engine plan
(the winning :class:`~repro.core.planner.Candidate` cell), same
``n_search``/dtype. Requests differ in target, tolerance, n_target and
seed: per-request **orthogonalization and Ritz extraction run on the
request's own slice** (the same ops a solo solve runs — batching never
mixes columns across requests through a Gram matrix), and per-request
filter polynomials ride the shared sweep as **per-column μ columns**,
zero-padded to the longest degree. Padding is exact: a zero coefficient
contributes ``Y + 0·T_k``, which is bitwise ``Y``, so a request batched
with a higher-degree neighbour computes exactly its solo filter.

The Lanczos inclusion interval is a property of the *operator*, not the
request, so the group computes it once from the service seed — which is
also what makes a request's result independent of its co-batched
neighbours. s-step plans (``spmv_sstep > 1``) fall back to per-request
filter application (the s-step applier's μ-regrouping is 1-D); analyze
steps still share the solver.

:class:`BatchedJob` wraps a group in the resumable-job protocol, so a
whole batch checkpoints/resumes through ``runtime/supervisor.py`` like a
solo job. :class:`EigenService` is the front end: submit requests,
``drain()`` plans each distinct pattern once (through the persistent
plan cache), groups compatible requests, and returns per-request
:class:`~repro.core.filter_diag.FDResult`.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any

import numpy as np
import jax
import jax.numpy as jnp

from ..core import make_solver_mesh
from ..core import perf_model as pm
from ..core.chebyshev import scale_params
from ..core.filter_diag import FDConfig, FDResult, FDState, FilterDiag
from ..core.lanczos import lanczos_interval
from ..core.spans import span
from ..core.planner import Candidate
from ..runtime import StragglerWatchdog, Supervisor, SupervisorConfig
from ..matrices import get_family
from .jobs import pack_state, state_template, unpack_state
from .plan_cache import PlanCache, cached_plan_layout, pattern_hash

__all__ = ["SolveRequest", "request_compat_key", "BatchedJob",
           "EigenService"]


@dataclasses.dataclass
class SolveRequest:
    """One tenant's eigenproblem: which operator, which eigenpairs.

    ``family``/``params`` name the matrix (``matrices.get_family``); a
    prebuilt matrix/CSR can be passed via ``matrix`` instead. Engine
    choice is NOT part of a request — the service plans it (or takes the
    cached plan) per pattern.
    """

    req_id: str
    family: str | None = None
    params: dict = dataclasses.field(default_factory=dict)
    n_target: int = 4
    n_search: int = 16
    target: float = 0.0
    tol: float = 1e-9
    max_iters: int = 40
    seed: int = 7
    matrix: Any = None

    def resolve_matrix(self):
        if self.matrix is not None:
            return self.matrix
        if self.family is None:
            raise ValueError(f"request {self.req_id}: neither family nor "
                             f"matrix given")
        return get_family(self.family, **self.params)


def request_compat_key(phash: str, best: Candidate, n_search: int,
                       dtype: str) -> tuple:
    """Requests sharing this key may share one panel: same operator
    pattern, same engine cell (every planned axis), same bundle width
    and dtype."""
    return (phash, best.layout, best.n_row, best.n_col, best.overlap,
            best.comm, best.schedule, best.balance, best.reorder,
            best.kernel, best.sstep, n_search, dtype)


@dataclasses.dataclass
class _Entry:
    """Per-request slot inside a batch group."""

    req: SolveRequest
    cfg: FDConfig
    state: FDState | None = None


class BatchedJob:
    """A compatible request group as one resumable job.

    State is the dict of per-request :class:`FDState`; one job ``step``
    advances every active request by one outer iteration — per-request
    analyze on its own slice, then a single shared filter sweep over the
    concatenated pending blocks. Implements the supervisor's job
    protocol (template/init/step/done/pack/unpack), so fault injection
    and resume work identically to solo jobs.
    """

    def __init__(self, fd: FilterDiag, requests: list[SolveRequest],
                 service_seed: int = 0, verbose: bool = False):
        self.fd = fd
        self.verbose = verbose
        self.service_seed = service_seed
        self.entries = [
            _Entry(req=r, cfg=dataclasses.replace(
                fd.cfg, n_target=r.n_target, target=r.target, tol=r.tol,
                max_iters=r.max_iters, seed=r.seed))
            for r in requests
        ]
        self.mesh = fd.mesh
        n = fd.cfg.n_search
        self.specs = {e.req.req_id: {"V": fd.stack_layout.vec_pspec()}
                      for e in self.entries}
        self._template = {e.req.req_id: state_template(fd, n)
                          for e in self.entries}

    # ---------------------------------------------------- job protocol --
    def template(self) -> dict:
        return self._template

    def init(self) -> dict:
        """Group Lanczos (an operator property, from the service seed —
        NOT the request seeds, so results are independent of batch
        composition) + per-request search draws from the request seeds,
        exactly the split a solo ``FilterDiag.init_state`` performs."""
        fd = self.fd
        k0 = jax.random.split(jax.random.PRNGKey(self.service_seed))[0]
        lam = lanczos_interval(
            fd.spmv_stack, fd.D, fd.D_pad, fd.dtype, k0,
            fd.cfg.lanczos_steps,
            mask=(None if fd.rowmap is None
                  else jnp.asarray(fd.rowmap.valid_mask())),
        )
        for e in self.entries:
            k1 = jax.random.split(jax.random.PRNGKey(e.cfg.seed))[1]
            e.state = FDState(V=fd.random_search_vectors(k1), lam=lam,
                              total_spmvs=fd.cfg.lanczos_steps)
        return {e.req.req_id: e.state for e in self.entries}

    def step(self, states: dict) -> dict:
        fd = self.fd
        for e in self.entries:
            e.state = states[e.req.req_id]
        active = [e for e in self.entries if not e.state.done]
        # per-request analyze on the request's own slice — the identical
        # ops (tsqr, ritz, host logic) a solo solve runs on that block
        for e in active:
            e.state = fd.step_analyze(e.state, cfg=e.cfg,
                                      verbose=self.verbose)
        pend = [e for e in active if not e.state.done]
        if pend:
            if fd.cheb_sstep is not None:
                # s-step applier regroups μ 1-D — apply per request
                for e in pend:
                    e.state = fd.step_filter(e.state, cfg=e.cfg)
            else:
                self._filter_batched(pend)
        return {e.req.req_id: e.state for e in self.entries}

    def _filter_batched(self, pend: list[_Entry]):
        """One shared Chebyshev sweep over the concatenated pending
        blocks, per-column μ zero-padded to the longest degree, in the
        span ``batch_filter`` (``FilterDiag.panel_filter``'s spans inside)."""
        fd = self.fd
        lam = pend[0].state.lam
        alpha, beta = scale_params(*lam)
        widths = [e.cfg.n_search for e in pend]
        degrees = [e.state.pending[1] for e in pend]
        n_max = max(degrees)
        Mu = np.zeros((n_max + 1, sum(widths)))
        col = 0
        for e, w in zip(pend, widths):
            mu_h, deg = e.state.pending
            Mu[: deg + 1, col: col + w] = np.asarray(mu_h)[:, None]
            col += w
        V = jnp.concatenate([e.state.V for e in pend], axis=1)
        with span("batch_filter", degree=n_max, columns=V.shape[1],
                  requests=len(pend)):
            V = fd.panel_filter(V, Mu, alpha, beta)
        redist = 2 if fd.N_col > 1 else 0
        col = 0
        for e, w, deg in zip(pend, widths, degrees):
            st = e.state
            st.V = V[:, col: col + w]
            col += w
            st.pending = None
            st.iteration += 1
            # solo accounting: the request's own degree x its own width
            st.total_spmvs += deg * w
            st.redistributions += redist

    def done(self, states: dict) -> bool:
        return all(s.done for s in states.values())

    def step_index(self, states: dict) -> int:
        return max(s.iteration for s in states.values())

    def pack(self, states: dict) -> tuple[dict, dict]:
        trees, extras = {}, {}
        for rid, s in states.items():
            trees[rid], extras[rid] = pack_state(s, self.fd)
        return trees, {"requests": extras}

    def unpack(self, trees: dict, extra: dict) -> dict:
        out = {}
        for e in self.entries:
            rid = e.req.req_id
            st = unpack_state(trees[rid], extra["requests"][rid], self.fd)
            st.V = jnp.asarray(st.V)
            e.state = st
            out[rid] = st
        return out

    def results(self, states: dict) -> dict[str, FDResult]:
        return {rid: s.result for rid, s in states.items()}


class EigenService:
    """Multi-tenant front end: submit requests, drain to results.

    ``drain()`` resolves each distinct sparsity pattern once, plans it
    through the persistent plan cache (repeat patterns skip the planner),
    groups requests by :func:`request_compat_key`, and runs each group as
    one :class:`BatchedJob` — supervised with checkpoint/resume when a
    checkpoint root is given, plain loop otherwise.
    """

    def __init__(self, *, plan_cache: PlanCache | None = None,
                 machine: pm.MachineModel | None = None,
                 ckpt_root: str | None = None,
                 service_seed: int = 0,
                 supervisor_cfg: SupervisorConfig | None = None,
                 verbose: bool = False):
        self.plan_cache = plan_cache
        self.machine = machine if machine is not None else pm.TPU_V5E
        self.ckpt_root = ckpt_root
        self.service_seed = service_seed
        self.supervisor_cfg = supervisor_cfg or SupervisorConfig(
            checkpoint_interval=1, keep_checkpoints=3)
        self.verbose = verbose
        self.queue: list[SolveRequest] = []
        self.plans: dict[tuple, Any] = {}   # (pattern hash, n_search) -> Plan
        self.cache_hits = 0

    def submit(self, req: SolveRequest) -> str:
        if any(r.req_id == req.req_id for r in self.queue):
            raise ValueError(f"duplicate request id {req.req_id!r}")
        self.queue.append(req)
        return req.req_id

    # ------------------------------------------------------------------
    def _plan(self, matrix, n_devices: int, n_search: int):
        phash = pattern_hash(matrix)
        pkey = (phash, n_search)  # the chosen n_col must divide n_search
        if pkey not in self.plans:
            D = matrix.shape[0] if hasattr(matrix, "shape") else matrix.D
            plan, hit = cached_plan_layout(
                matrix, n_devices, n_search=n_search, cache=self.plan_cache,
                machine=self.machine, d_pad=-(-D // n_devices) * n_devices)
            self.plans[pkey] = plan
            self.cache_hits += int(hit)
        return phash, self.plans[pkey]

    def drain(self, fault_hook=None) -> dict[str, FDResult]:
        """Solve every queued request; returns ``{req_id: FDResult}``."""
        n_devices = len(jax.devices())
        groups: dict[tuple, list] = {}
        mats: dict[tuple, Any] = {}
        plans: dict[tuple, Candidate] = {}
        for req in self.queue:
            mat = req.resolve_matrix()
            phash, plan = self._plan(mat, n_devices, req.n_search)
            best = plan.best
            ckey = request_compat_key(phash, best, req.n_search, "float64")
            groups.setdefault(ckey, []).append(req)
            mats.setdefault(ckey, mat)
            plans.setdefault(ckey, best)
        self.queue = []
        results: dict[str, FDResult] = {}
        for i, (ckey, reqs) in enumerate(groups.items()):
            results.update(self._run_group(
                mats[ckey], plans[ckey], reqs, group_idx=i,
                fault_hook=fault_hook))
        return results

    def _run_group(self, mat, best: Candidate, reqs: list[SolveRequest],
                   group_idx: int, fault_hook=None) -> dict[str, FDResult]:
        # the chosen (n_row x n_col) split realizes the planned layout —
        # same convention as launch/solve.py's auto path
        cfg = FDConfig(
            n_search=reqs[0].n_search, layout="panel",
            spmv_overlap=best.overlap, spmv_comm=best.comm,
            spmv_schedule=best.schedule, spmv_balance=best.balance,
            spmv_reorder=best.reorder, spmv_kernel=best.kernel,
            spmv_sstep=best.sstep, seed=self.service_seed)
        mesh = make_solver_mesh(best.n_row, best.n_col)
        with mesh:
            fd = FilterDiag(mat, mesh, cfg, rowmap=best.rowmap)
            job = BatchedJob(fd, reqs, service_seed=self.service_seed,
                             verbose=self.verbose)
            if self.ckpt_root is not None:
                sup = Supervisor(
                    os.path.join(self.ckpt_root, f"group_{group_idx:03d}"),
                    self.supervisor_cfg)
                states = sup.run_job(job, fault_hook=fault_hook,
                                     watchdog=StragglerWatchdog())
            else:
                states = job.init()
                while not job.done(states):
                    states = job.step(states)
            return job.results(states)
