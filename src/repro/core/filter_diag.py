"""Filter diagonalization driver — paper Algorithm 1.

Orchestrates the two orthogonal layers of parallelism:

  stack layout : orthogonalization (TSQR), Ritz extraction, convergence
  panel layout : Chebyshev polynomial filter (bulk of all SpMVs)
  steps 7 / 9  : explicit redistribution between the two layouts

The driver is layout-generic: with ``n_col = 1`` it degenerates to the
classic single-layer stack algorithm (the paper's baseline); with
``n_col = P`` the filter runs in the pillar layout (comm-free SpMV).

The filter layout is picked by ``FDConfig.layout``: an explicit name
("stack" / "panel" / "pillar") materialized on the given mesh, or
``"auto"``, which runs the χ-driven planner (``core/planner.py``) over
the layouts the mesh realizes and adopts the minimum-predicted-time
configuration — including whether to use the split-phase overlap SpMV
engine, which halo-exchange engine to run, and how the compressed
engine's permute rounds are scheduled (``FDConfig.spmv_overlap``,
``FDConfig.spmv_comm``, and ``FDConfig.spmv_schedule`` are then set from
the plan; ``spmv_comm="compressed"`` replaces the padded all_to_all with
per-pair-sized ppermute rounds, and ``spmv_schedule="matching"`` derives
those rounds from greedy max-weight matchings instead of cyclic shifts).
A ``panel_layout`` passed explicitly to ``FilterDiag`` overrides all of
them.

The row partition itself is part of the engine configuration
(``FDConfig.spmv_balance`` / ``FDConfig.spmv_reorder``,
``core/partition.py``): ``spmv_balance="commvol"`` re-balances the
shard boundaries so hot blocks shrink before scheduling, and
``spmv_reorder="rcm"`` applies a bandwidth-reducing row order first —
eigenvalues are unchanged and :meth:`FilterDiag.gather_global`
un-permutes vectors back to the original row order. Both are planned
once at the finest level (P_total) so the stack- and panel-level
operators share one map, and ``layout="auto"`` decides them together
with the other engine axes.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np
import jax
import jax.numpy as jnp

from jax.sharding import Mesh

from . import filters
from .chebyshev import chebyshev_filter, scale_params
from .lanczos import lanczos_interval
from .layouts import Layout
from .orthogonalize import make_gram, make_svqb, make_tsqr
from .redistribute import make_redistribute
from .spans import span
from .spmv import (build_dist_ell, build_sstep_ell, make_fused_cheb_step,
                   make_spmv, make_sstep_cheb)

__all__ = ["FDConfig", "FDResult", "FDState", "FilterDiag"]


@dataclasses.dataclass
class FDConfig:
    n_target: int = 10          # N_t requested eigenpairs
    n_search: int = 40          # N_s search vectors (N_s >> N_t)
    target: float = 0.0         # τ
    tol: float = 1e-10          # residual convergence threshold (paper)
    max_iters: int = 50
    lanczos_steps: int = 30
    search_expand: float = 1.5  # search-interval growth factor
    degree_cap: int = 200_000
    sharpness: float = 6.0
    ortho: str = "tsqr"         # or "svqb"
    redist_impl: str = "explicit"  # or "gspmd"
    layout: str = "panel"       # filter layout: stack | panel | pillar | auto
    spmv_overlap: bool = False  # split-phase SpMV: hide halo exchange
    spmv_comm: str = "a2a"      # halo exchange: a2a | compressed (ppermute)
    spmv_schedule: str = "cyclic"  # compressed rounds: cyclic | matching
    spmv_balance: str = "rows"  # row partition: rows | commvol (planned cuts)
    spmv_reorder: str = "none"  # row order: none | rcm (bandwidth-reducing)
    spmv_kernel: bool = False   # Pallas kernels for the local contraction
    spmv_sstep: int = 1         # s-step filter: depth-s ghosts, ceil(n/s) exchanges
    plan_mode: str = "auto"     # pattern passes: exact | sampled | auto (gate)
    dtype: str = "float64"
    seed: int = 7


@dataclasses.dataclass
class FDResult:
    eigenvalues: np.ndarray
    residuals: np.ndarray
    n_converged: int
    iterations: int
    total_spmvs: int
    redistributions: int
    history: list


@dataclasses.dataclass
class FDState:
    """Explicit iteration state of one FD solve (Algorithm 1 unrolled).

    Everything the outer loop carries between iterations lives here, so a
    solve can be driven step-by-step (``FilterDiag.step``), checkpointed
    at iteration boundaries (``service/jobs.py`` packs ``V`` as the pytree
    leaf and the host fields as the manifest extra), and resumed from the
    last committed step — the resumed trajectory is bit-identical to the
    uninterrupted one because every per-iteration quantity is recomputed
    from (V, lam) by the same deterministic ops.

    ``pending`` is transient within one iteration only: ``step_analyze``
    stashes the filter coefficients it chose and ``step_filter`` consumes
    them; at checkpoint boundaries it is always ``None``.
    """

    V: jax.Array | None            # search block [D_pad, N_s], stack layout
    lam: tuple                     # Lanczos inclusion interval (λ_l, λ_r)
    iteration: int = 0
    total_spmvs: int = 0
    redistributions: int = 0
    history: list = dataclasses.field(default_factory=list)
    pending: tuple | None = None   # (mu [deg+1], degree) awaiting step_filter
    done: bool = False
    result: FDResult | None = None


def _jit_full_precision(f, name: str):
    """jit ``f`` traced with full-precision matmuls, its ops in the named
    scope ``fd.<name>``. A TPU runs float32 dots in bfloat16 passes by
    default, which leaves Rayleigh-Ritz residuals near 1e-3; CPU dots are
    full precision either way."""
    def traced(*args):
        with jax.default_matmul_precision("highest"), \
                jax.named_scope("fd." + name):
            return f(*args)

    return jax.jit(traced)


class FilterDiag:
    """Filter diagonalization on a (row x col) solver mesh.

    ``matrix`` may be a MatrixFamily or a CSR — both expose the sparsity
    pattern, which ``build_dist_ell`` turns into per-shard ELL blocks plus
    the halo communication plan (and which the planner consumes when
    ``cfg.layout == "auto"``; the chosen plan is kept on ``self.plan``).
    """

    def __init__(self, matrix, mesh: Mesh, cfg: FDConfig,
                 panel_layout: Layout | None = None,
                 rowmap=None):
        dt = jnp.dtype(cfg.dtype)
        if getattr(matrix, "is_complex", False) and not jnp.issubdtype(dt, jnp.complexfloating):
            dt = jnp.dtype("complex128" if dt == jnp.float64 else "complex64")
        on_tpu = mesh.devices.flat[0].platform == "tpu"
        if dt == jnp.complex128 and on_tpu:
            # the TPU compiler aborts the process on a complex128 matmul
            # and refuses complex128 qr/eigh/vdot: refuse before any trace
            raise ValueError(
                "complex128 does not compile for TPU; solve complex "
                "families there with FDConfig(dtype='float32'), which runs "
                "them in complex64")
        self.dtype = dt
        if panel_layout is None and cfg.layout == "auto":
            # the planner decides spmv_overlap — work on a copy so the
            # caller's config object is not mutated
            cfg = dataclasses.replace(cfg)
        self.cfg = cfg
        self.mesh = mesh
        self.plan = None
        # an explicitly passed rowmap (e.g. the one the solve CLI's auto
        # plan already computed) is used verbatim — no re-planning
        self.rowmap = rowmap
        self.panel_layout = panel_layout or self._resolve_layout(matrix, mesh, cfg)
        # stack shards D over all axes, panel-row axes slowest ("matching")
        self.stack_layout = Layout(
            "stack", self.panel_layout.dist_axes + self.panel_layout.bundle_axes, ()
        )
        self.P_total = self.stack_layout.n_row(mesh)
        self.N_row = self.panel_layout.n_row(mesh)
        self.N_col = self.panel_layout.n_col(mesh)
        if cfg.n_search % max(self.N_col, 1):
            raise ValueError("n_search must be divisible by N_col")
        if on_tpu and cfg.ortho == "tsqr" and dt == jnp.float64 \
                and self.P_total > 1:
            # XLA:TPU rejects a float64 QR inside shard_map across chips
            raise ValueError(
                "float64 TSQR does not compile for a multi-chip TPU mesh "
                "(\"A tuple parameter that is being flattened shouldn't "
                "have frontend attributes\"); use FDConfig(ortho='svqb') "
                "there")
        if cfg.spmv_sstep < 1:
            raise ValueError(f"spmv_sstep must be >= 1 "
                             f"(got {cfg.spmv_sstep})")
        D = matrix.shape[0] if hasattr(matrix, "shape") else matrix.D
        self.D = D
        # planned row decomposition (core/partition.py): the auto planner
        # may have handed one over; an explicit spmv_balance/spmv_reorder
        # plans it here, at the finest level P_total, so the stack- and
        # panel-level operators below share one map
        if self.rowmap is None and (cfg.spmv_balance, cfg.spmv_reorder) \
                != ("rows", "none"):
            from .partition import plan_rowmap

            self.rowmap = plan_rowmap(matrix, self.P_total,
                                      balance=cfg.spmv_balance,
                                      reorder=cfg.spmv_reorder,
                                      sstep=cfg.spmv_sstep,
                                      plan_mode=cfg.plan_mode)
            if self.rowmap.identity:
                self.rowmap = None  # planned map degenerated to equal rows
        # one padded extent for both layouts (the planned map's when set)
        self.D_pad = (self.rowmap.D_pad if self.rowmap is not None
                      else -(-D // self.P_total) * self.P_total)
        self.ell_stack = build_dist_ell(matrix, self.P_total, dtype=dt,
                                        d_pad=self.D_pad,
                                        split_halo=cfg.spmv_overlap,
                                        rowmap=self.rowmap)
        if self.N_col > 1:
            self.ell_panel = build_dist_ell(matrix, self.N_row, dtype=dt,
                                            d_pad=self.D_pad,
                                            split_halo=cfg.spmv_overlap,
                                            rowmap=self.rowmap)
        else:
            self.ell_panel = self.ell_stack
        # s-step filter operator (seventh engine axis): depth-s ghost
        # zones at the panel level only — Lanczos and the Ritz residual
        # are single SpMVs, so the stack operator stays s=1
        self.sell_panel = (
            build_sstep_ell(matrix, self.N_row, cfg.spmv_sstep, dtype=dt,
                            d_pad=self.D_pad, rowmap=self.rowmap)
            if cfg.spmv_sstep > 1 else None
        )
        self._build_fns(matrix)

    # ------------------------------------------------------------------
    def _resolve_layout(self, matrix, mesh: Mesh, cfg: FDConfig) -> Layout:
        """Materialize ``cfg.layout`` on the mesh; ``"auto"`` runs the
        χ-driven planner over {stack, panel, pillar} × {a2a,
        compressed-cyclic, compressed-matching} × {overlap on/off} ×
        {equal-rows, commvol} partitions and also decides
        ``cfg.spmv_overlap``, ``cfg.spmv_comm``, ``cfg.spmv_schedule``,
        ``cfg.spmv_balance``/``cfg.spmv_reorder``, and
        ``cfg.spmv_kernel`` (an explicitly requested reorder or kernel
        widens the corresponding planner axis)."""
        from .planner import layout_on_mesh, plan_for_mesh

        if cfg.layout == "auto":
            # plan on the engine's padded partition (build_dist_ell below
            # uses d_pad = ceil(D/P)*P) so the scored χ/L are the ones the
            # built operator will actually realize
            P = 1
            for a in mesh.axis_names:
                P *= mesh.shape[a]
            D = matrix.shape[0] if hasattr(matrix, "shape") else matrix.D
            self.plan = plan_for_mesh(
                matrix, mesh, n_search=cfg.n_search,
                d_pad=-(-D // P) * P,
                reorder=tuple(dict.fromkeys(("none", cfg.spmv_reorder))),
                kernel=tuple(dict.fromkeys((False, cfg.spmv_kernel))),
                sstep=tuple(dict.fromkeys((1, cfg.spmv_sstep))),
                plan_mode=cfg.plan_mode)
            best = self.plan.best
            cfg.spmv_overlap = best.overlap
            cfg.spmv_comm = best.comm
            cfg.spmv_schedule = best.schedule
            cfg.spmv_balance = best.balance
            cfg.spmv_reorder = best.reorder
            cfg.spmv_kernel = best.kernel
            cfg.spmv_sstep = best.sstep
            # the operators below are built from exactly the map the
            # winning candidate was scored on
            if self.rowmap is None:
                self.rowmap = best.rowmap
            return layout_on_mesh(mesh, best.layout)
        if cfg.layout in ("stack", "panel", "pillar"):
            return layout_on_mesh(mesh, cfg.layout)
        raise ValueError(f"unknown FDConfig.layout {cfg.layout!r} "
                         "(expected stack | panel | pillar | auto)")

    def _build_fns(self, matrix):
        mesh, cfg = self.mesh, self.cfg
        self.spmv_stack = make_spmv(mesh, self.stack_layout, self.ell_stack,
                                    use_kernel=cfg.spmv_kernel,
                                    overlap=cfg.spmv_overlap,
                                    comm=cfg.spmv_comm,
                                    schedule=cfg.spmv_schedule)
        self.spmv_panel = (
            make_spmv(mesh, self.panel_layout, self.ell_panel,
                      use_kernel=cfg.spmv_kernel,
                      overlap=cfg.spmv_overlap, comm=cfg.spmv_comm,
                      schedule=cfg.spmv_schedule)
            if self.N_col > 1 else self.spmv_stack
        )
        # kernelized recurrence step: the fused 2a·A·w1 + 2b·w1 - w2 body
        # (single shard_map / cheb_dia dispatch) used by the filter loop
        self.fused_step_panel = (
            make_fused_cheb_step(mesh, self.panel_layout, self.ell_panel,
                                 use_kernel=True,
                                 overlap=cfg.spmv_overlap,
                                 comm=cfg.spmv_comm,
                                 schedule=cfg.spmv_schedule)
            if cfg.spmv_kernel else None
        )
        # s-step filter applier (spmv_sstep > 1): the whole degree-n
        # filter in ceil(n/s) depth-s ghost exchanges, bit-identical to
        # the per-step engines (core/spmv.py make_sstep_cheb)
        self.cheb_sstep = (
            make_sstep_cheb(mesh, self.panel_layout, self.sell_panel,
                            use_kernel=cfg.spmv_kernel,
                            overlap=cfg.spmv_overlap,
                            comm=cfg.spmv_comm,
                            schedule=cfg.spmv_schedule)
            if self.sell_panel is not None else None
        )
        if cfg.ortho == "tsqr":
            self._tsqr = make_tsqr(mesh, self.stack_layout)
            self.orthogonalize = _jit_full_precision(
                lambda V: self._tsqr(V)[0], "orthogonalize")
        else:
            self.orthogonalize = _jit_full_precision(
                make_svqb(mesh, self.stack_layout), "orthogonalize")
        self.gram = make_gram(mesh, self.stack_layout)
        self.to_panel, self.to_stack = make_redistribute(
            mesh, self.stack_layout, self.panel_layout, impl=cfg.redist_impl
        )
        self.to_panel = jax.jit(self.to_panel)
        self.to_stack = jax.jit(self.to_stack)

        def ritz(spmv, V):
            AV = spmv(V)
            with jax.named_scope("fd.gram"):
                H = self.gram(V, AV)  # [Ns, Ns] replicated
                H = 0.5 * (H + jnp.conj(H.T))
            with jax.named_scope("fd.eigh"):
                theta, Y = jnp.linalg.eigh(H)
            # residual norms: || AV y - θ V y ||
            with jax.named_scope("fd.residuals"):
                AVY = AV @ Y.astype(AV.dtype)
                VY = V @ Y.astype(V.dtype)
                Rm = AVY - VY * theta[None, :].astype(VY.dtype)
                res = jnp.sqrt(jnp.sum(jnp.abs(Rm) ** 2, axis=0))
            return theta, Y, res, VY

        # the engines are passed in, not captured, so their operator
        # arrays are program parameters rather than baked-in constants
        self.ritz = functools.partial(_jit_full_precision(ritz, "ritz"),
                                      self.spmv_stack)
        # filter(V, mu, alpha, beta): one compiled program per degree, the
        # static length of mu
        if self.cheb_sstep is not None:
            self.filter = jax.jit(self.cheb_sstep)
        else:
            def run(spmv, fused_step, V, mu, alpha, beta):
                with jax.named_scope("fd.filter"):
                    return chebyshev_filter(spmv, mu, alpha, beta, V,
                                            fused_step=fused_step)

            self.filter = functools.partial(
                jax.jit(run), self.spmv_panel, self.fused_step_panel)

    # ------------------------------------------------------------------
    def random_search_vectors(self, key) -> jax.Array:
        cfg = self.cfg
        if self.rowmap is None:
            V = jax.random.normal(key, (self.D_pad, cfg.n_search)).astype(self.dtype)
            V = V * (jnp.arange(self.D_pad)[:, None] < self.D)
        else:
            # planned partition: draw in row space and embed at the map's
            # positions (interior pads stay exactly zero)
            V0 = jax.random.normal(key, (self.D, cfg.n_search)).astype(self.dtype)
            V = jnp.zeros((self.D_pad, cfg.n_search), dtype=self.dtype)
            V = V.at[jnp.asarray(self.rowmap.pos)].set(V0)
        return jax.device_put(V, self.stack_layout.vec_sharding(self.mesh))

    def gather_global(self, V) -> np.ndarray:
        """Rows of a padded [D_pad, ...] vector block in the **original**
        row order [D, ...] — the eigenvector un-permutation of a planned
        partition (identity gather for the equal-rows layout). The
        embed→extract round trip is bit-exact."""
        Vh = np.asarray(V)
        if self.rowmap is None:
            return Vh[: self.D]
        return Vh[self.rowmap.pos]

    def _intervals(self, theta, res, lam, cfg: FDConfig | None = None):
        """Adaptive target & search intervals from the current Ritz data.

        Intervals are bounding boxes of the closest Ritz values rather than
        symmetric windows around τ: for extremal targets (τ outside the
        spectrum) a τ-centered window would keep covering ≫ N_s eigenvalues
        and FD would stall — the paper's Fig. 2 (right column) failure.
        """
        cfg = cfg if cfg is not None else self.cfg
        d = np.abs(theta - cfg.target)
        order = np.argsort(d)
        spec_w = lam[1] - lam[0]
        sel_t = theta[order[: min(cfg.n_target, len(order))]]
        # anchor on τ (clipped into the spectrum): with random start vectors
        # the Ritz values cluster in the spectral bulk, and a pure bounding
        # box would lock the filter onto the wrong region
        tau_c = float(np.clip(cfg.target, lam[0], lam[1]))
        lo = min(float(sel_t.min()), tau_c)
        hi = max(float(sel_t.max()), tau_c)
        pad_t = max(1e-8 * spec_w, 0.05 * (hi - lo))
        target = (lo - pad_t, hi + pad_t)
        n_s = min(int(0.75 * cfg.n_search), len(order))
        sel_s = theta[order[:n_s]]
        s_lo = min(float(sel_s.min()), target[0])
        s_hi = max(float(sel_s.max()), target[1])
        mid = 0.5 * (s_lo + s_hi)
        half = max(0.5 * (s_hi - s_lo),
                   cfg.search_expand * 0.5 * (target[1] - target[0]))
        # pad outward so wanted states sit on the filter plateau, not on the
        # Jackson transition slope (slope width ~ pi/n of the mapped axis)
        pad_s = 0.15 * half
        lo_s = max(mid - half - pad_s, lam[0])
        hi_s = min(mid + half + pad_s, lam[1])
        # extremal targets: widen the outward side by ~the transition width
        # (0.75 of the inner span) so edge states sit on the filter plateau
        # instead of the Jackson slope — without collapsing the degree the
        # way fully opening the window to the inclusion bound would
        if cfg.target <= float(theta.min()):
            lo_s = max(lam[0], target[0] - 0.75 * (hi_s - target[0]))
        if cfg.target >= float(theta.max()):
            hi_s = min(lam[1], target[1] + 0.75 * (target[1] - lo_s))
        search = (lo_s, hi_s)
        return target, search

    # ------------------------------------------------------------------
    # explicit-state iteration API (resumable jobs, service batching)
    # ------------------------------------------------------------------
    def init_state(self, key=None) -> FDState:
        """Fresh :class:`FDState`: Lanczos inclusion interval + random
        search block. ``solve`` is exactly ``init_state`` followed by
        ``step`` until ``done``."""
        cfg = self.cfg
        key = key if key is not None else jax.random.PRNGKey(cfg.seed)
        k0, k1 = jax.random.split(key)
        lam = lanczos_interval(
            self.spmv_stack, self.D, self.D_pad, self.dtype, k0,
            cfg.lanczos_steps,
            mask=(None if self.rowmap is None
                  else jnp.asarray(self.rowmap.valid_mask())),
        )
        V = self.random_search_vectors(k1)
        return FDState(V=V, lam=lam, total_spmvs=cfg.lanczos_steps)

    def step_analyze(self, state: FDState, cfg: FDConfig | None = None,
                     verbose: bool = False) -> FDState:
        """First half of one outer iteration: orthogonalize, Ritz extract,
        adapt the intervals, and either finish the solve (``state.done``)
        or stash the chosen filter in ``state.pending``.

        ``cfg`` overrides the convergence-relevant fields (target, tol,
        n_target, …) — the service batcher passes per-request configs
        while sharing this solver's operators.

        Spans (``core/spans.py``): ``step_analyze`` holding
        ``orthogonalize``, ``ritz`` (its program and the fetch of θ and
        the residuals) and ``choose_filter`` (host only).
        """
        cfg = cfg if cfg is not None else self.cfg
        it = state.iteration
        n_s = state.V.shape[1]
        with span("step_analyze"):
            with span("orthogonalize", columns=n_s) as s:
                V = s.end_on(self.orthogonalize(state.V))
            with span("ritz", columns=n_s, fetches=2):
                theta, Y, res, VY = self.ritz(V)
                theta_h = np.asarray(theta)
                res_h = np.asarray(res)
            if it >= cfg.max_iters:
                # not converged within max_iters — report best effort
                order = np.argsort(np.abs(theta_h - cfg.target))[: cfg.n_target]
                return self._finish(state, theta_h[order], res_h[order],
                                    int((res_h[order] <= cfg.tol).sum()),
                                    cfg.max_iters)
            state.total_spmvs += cfg.n_search
            with span("choose_filter") as s:
                target, search = self._intervals(theta_h, res_h, state.lam,
                                                 cfg=cfg)
                in_t = (theta_h >= target[0]) & (theta_h <= target[1])
                conv = in_t & (res_h <= cfg.tol)
                state.history.append(
                    dict(iter=it, n_conv=int(conv.sum()), search=search,
                         best_res=float(res_h[in_t].min()) if in_t.any() else float("nan"))
                )
                if verbose:
                    print(f"[fd] it={it:3d} conv={int(conv.sum()):4d}/{cfg.n_target} "
                          f"search=({search[0]:+.4e},{search[1]:+.4e}) "
                          f"best_res={state.history[-1]['best_res']:.2e}")
                if conv.sum() >= cfg.n_target:
                    order = np.argsort(np.abs(theta_h - cfg.target))
                    sel = order[conv[order]][: max(cfg.n_target, int(conv.sum()))]
                    return self._finish(state, theta_h[sel], res_h[sel],
                                        int(conv.sum()), it)
                poly = filters.build_filter(
                    search, state.lam, sharpness=cfg.sharpness,
                    n_max=cfg.degree_cap,
                )
                s.counts["degree"] = poly.degree
        # start the filter from the Ritz basis (better conditioning)
        state.V = VY
        state.pending = (np.asarray(poly.mu), poly.degree)
        return state

    @staticmethod
    def _finish(state: FDState, eigenvalues, residuals, n_converged: int,
                iterations: int) -> FDState:
        state.done = True
        state.result = FDResult(
            eigenvalues=eigenvalues, residuals=residuals,
            n_converged=n_converged, iterations=iterations,
            total_spmvs=state.total_spmvs,
            redistributions=state.redistributions, history=state.history,
        )
        return state

    def panel_filter(self, V, mu, alpha: float, beta: float) -> jax.Array:
        """p(A) V for a stack-layout block ``V`` and host coefficients
        ``mu`` ([deg+1], or [deg+1, N_s] per column): to the panel layout
        (N_col > 1), the filter, back to the stack layout, each in a span
        ``to_panel`` / ``filter`` / ``to_stack`` that ends on its output
        while a trace records."""
        if self.N_col > 1:
            with span("to_panel", columns=V.shape[1]) as s:
                V = s.end_on(self.to_panel(V))
        with span("filter", degree=len(mu) - 1, columns=V.shape[1]) as s:
            V = s.end_on(self.filter(V, jnp.asarray(mu), alpha, beta))
        if self.N_col > 1:
            with span("to_stack", columns=V.shape[1]) as s:
                V = s.end_on(self.to_stack(V))
        return V

    def step_filter(self, state: FDState,
                    cfg: FDConfig | None = None) -> FDState:
        """Second half of one outer iteration: apply the pending Chebyshev
        filter in the panel layout (redistributing if N_col > 1) and
        advance the iteration counter; the span ``step_filter`` holds
        :meth:`panel_filter`'s."""
        cfg = cfg if cfg is not None else self.cfg
        mu_h, degree = state.pending
        alpha, beta = scale_params(*state.lam)
        with span("step_filter", degree=degree, columns=state.V.shape[1]):
            state.V = self.panel_filter(state.V, mu_h, alpha, beta)
        state.total_spmvs += degree * cfg.n_search
        if self.N_col > 1:
            state.redistributions += 2
        state.pending = None
        state.iteration += 1
        return state

    def step(self, state: FDState, verbose: bool = False) -> FDState:
        """One full outer iteration (analyze + filter) — the unit the
        resumable-job driver checkpoints at."""
        state = self.step_analyze(state, verbose=verbose)
        if not state.done:
            state = self.step_filter(state)
        return state

    def solve(self, key=None, verbose: bool = False) -> FDResult:
        state = self.init_state(key)
        while not state.done:
            state = self.step(state, verbose=verbose)
        return state.result
