"""Distributed sparse matrix–(multiple)-vector multiplication engine.

Host side (`Partition`, `CommPlan`, `build_dist_ell`): given a matrix
family (or CSR) and the number of row shards P, build

  * row blocks of the partition — equal blocks R = ceil(D/P) by default
    (the paper's "nearly equidistant" row indices; the tail block is
    zero-padded), or a *planned* decomposition when a
    ``core/partition.py`` RowMap is passed (``balance="commvol"``
    non-uniform boundaries and/or the ``reorder="rcm"`` row order,
    realized as an embed into an equal-block padded position space),
  * per-shard ELL blocks with *remapped* columns: local columns map to
    [0, R), remote columns map into a halo region [R, R + P*L),
  * a communication plan: for every (sender q -> receiver p) pair the
    sorted list of local entries q must ship to p, padded to the max
    pair volume L.

Device side (`make_spmv`): a ``shard_map`` function executing the paper's
distributed SpMV: gather send slots -> single ``all_to_all`` over the
horizontal (``row``) mesh axes -> local ELL contraction against
``[x_local ‖ halo]``. The all_to_all moves exactly ``P * L * n_b * S_d``
bytes per device — L is the padded max per-pair volume derived from the
paper's n_vc counts, so the measured (HLO) collective volume equals the
pattern-only prediction of ``planner.comm_plan`` bit-for-bit
(tests/test_planner.py) and the χ-metric estimate up to the imbalance
factor χ₃/χ₂.

Overlap execution model (``make_spmv(..., overlap=True)``): each shard's
ELL block is split once, on the host, into a *local* part (columns in
``[0, R)`` — entries resolvable without communication) and a *halo* part
(columns in the remote region ``[R, R + P*L)``), in the spirit of
node-aware SpMV (Bienz, Gropp & Olson, arXiv:1612.08060). The device body
then

  1. launches the halo ``all_to_all`` (no dependence on the local part),
  2. contracts the local ELL while the bytes are in flight,
  3. contracts the halo ELL against the received buffer and accumulates.

On backends with async collectives XLA schedules (1) and (2) concurrently,
hiding the communication behind local work; the cost model becomes

  T = max(T_comm, T_local) + T_halo

instead of the additive ``T = T_comm + T_local+halo`` of Eq. 12 — see
``perf_model.cheb_iter_time_overlap``. Within every output row the split
engine accumulates local entries (ascending column) before halo entries,
which is exactly the unsplit ELL slot order, so baseline and overlapped
engines agree bit-for-bit up to associativity-free summation order.

Sparsity-compressed execution model (``make_spmv(..., comm="compressed")``):
the single padded ``all_to_all`` physically realizes the paper's χ₃ — every
(sender, receiver) pair moves L slots even when its true volume L_qp is
tiny or zero, so each device pays ``P * L`` entries per vector column
regardless of the imbalance factor χ₃/χ₂. The compressed engine instead
walks a *neighbor schedule* derived from the per-pair true volumes
(:meth:`DistEll.neighbor_plan`): a sequence of ``lax.ppermute`` rounds,
each round an arbitrary (partial) permutation of the shards padded only
to that round's max scheduled pair volume, with empty pairs never
scheduled at all. Total moved entries drop from ``P * L`` (χ₃-scaled) to
``H = Σ_r L_r`` — the node-aware idea of Bienz, Gropp & Olson
(arXiv:1612.08060): exchange only what the pattern requires, with actual
neighbors.

*How the rounds are derived is itself an axis* (:func:`neighbor_schedule`,
``schedule={"cyclic", "matching"}``):

  * ``"cyclic"`` — one round per cyclic shift k with a nonzero pair; the
    round's perm is the full shift permutation and its pad is that
    shift's max pair volume ``L_k = max_q L_{q -> q+k}``. Simple and
    contention-free, but one hot receiver at shift k taxes all P pairs
    of that round.
  * ``"matching"`` — greedy max-weight matchings extracted from the
    pair-volume matrix (in the spirit of Birkhoff decompositions): hot
    pairs from *different* shifts share one round's pad whenever their
    endpoints are disjoint, so ``H_matching <= H_cyclic`` always (the
    scheduler falls back to the cyclic rounds if greedy packing ever
    paid more — see :func:`neighbor_schedule`). On hub-and-spoke
    patterns (``matrices/hubnet.py``) the cyclic schedule pays one
    full-sized round per hub shift while a matching packs all hub
    corridors into O(1) rounds.

The halo columns are re-based into the compact round-concatenated
receive buffer **without re-sorting the ELL slots**, so the accumulation
order per output row is identical to the a2a engine and all six engine
combinations ({a2a, compressed-cyclic, compressed-matching} x
{plain, overlap}) agree bit-for-bit. ``comm="compressed"`` composes with
``overlap=True``: the permute rounds launch first, the local block
contracts while the bytes are in flight, and the halo block contracts
against the compact buffer last.

The vertical (``col``) mesh axes shard the vector bundle; no SpMV
communication crosses them (the paper's central point).
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..matrices.families import MatrixFamily
from ..matrices.sparse import CSR
from .layouts import Layout
from .partition import RowMap

__all__ = ["Partition", "DistEll", "NeighborPlan", "build_dist_ell",
           "make_spmv", "make_fused_cheb_step", "neighbor_schedule",
           "SstepEll", "SstepNeighbor", "build_sstep_ell", "sstep_ghosts",
           "make_sstep_cheb", "SPMV_COMM_ENGINES", "SPMV_SCHEDULES"]

#: Horizontal-layer communication engines of ``make_spmv``.
SPMV_COMM_ENGINES = ("a2a", "compressed")

#: Round schedulers of the compressed engine (``make_spmv(schedule=...)``).
SPMV_SCHEDULES = ("cyclic", "matching")


def neighbor_schedule(pair_counts: np.ndarray, schedule: str = "cyclic",
                      ) -> tuple[tuple[tuple[tuple[int, int], ...], ...],
                                 tuple[int, ...]]:
    """Decompose the pair-volume matrix into the compressed engine's
    permutation rounds.

    Returns ``(perms, round_L)`` for true per-pair volumes
    ``pair_counts[q, p]`` (sender q -> receiver p): ``perms[r]`` is round
    r's ``lax.ppermute`` permutation — a tuple of ``(src, dst)`` device
    pairs in which every device appears at most once as source and at
    most once as destination — and ``round_L[r]`` is the round's pad,
    the max volume among its scheduled nonzero pairs. Every round moves
    exactly ``round_L[r]`` slots per device, so per-device moved entries
    are ``H = sum(round_L)``; pairs with zero volume are never given a
    round of their own.

    ``schedule="cyclic"``: one round per cyclic shift k with at least one
    nonzero pair; the perm is the full shift permutation
    ``j -> (j + k) % P`` and the pad is that shift's max pair volume
    ``L_k = max_q L_{q -> (q+k) % P}`` — one hot receiver taxes all P
    pairs of its round.

    ``schedule="matching"``: greedy max-weight matching decomposition (in
    the spirit of Birkhoff decompositions / node-aware SpMV schedules):
    nonzero pairs are taken in descending volume and placed first-fit
    into the earliest round where both endpoints are still free, so hot
    pairs from *different* cyclic shifts share one round's pad instead
    of each taxing its own round. Should greedy packing ever pay more
    than the cyclic rounds, the cyclic decomposition is returned
    instead — ``H_matching <= H_cyclic`` holds by construction.

    Single source of truth for the round derivation — the engine
    (``DistEll.neighbor_plan``) and the planner's byte prediction
    (``planner.SpmvCommPlan.permute_schedule``) both call it, which is
    what keeps predicted == HLO-measured exact.
    """
    pc = np.asarray(pair_counts)
    P = pc.shape[0]
    q = np.arange(P)
    cyc_perms, cyc_L = [], []
    for k in range(1, P):
        Lk = int(pc[q, (q + k) % P].max())
        if Lk:
            cyc_perms.append(tuple((j, int((j + k) % P)) for j in range(P)))
            cyc_L.append(Lk)
    cyclic = (tuple(cyc_perms), tuple(cyc_L))
    if schedule == "cyclic":
        return cyclic
    if schedule != "matching":
        raise ValueError(f"unknown schedule {schedule!r} "
                         f"(expected one of {SPMV_SCHEDULES})")
    # first-fit-descending greedy matchings: the (volume desc, src, dst)
    # key makes the decomposition deterministic, and descending order
    # makes each round's pad the volume of the pair that opened it
    pairs = sorted(((int(pc[s, d]), s, d)
                    for s in range(P) for d in range(P)
                    if s != d and pc[s, d]),
                   key=lambda t: (-t[0], t[1], t[2]))
    rounds: list[dict] = []
    for w, s, d in pairs:
        for r in rounds:
            if s not in r["src"] and d not in r["dst"]:
                break
        else:
            r = dict(src=set(), dst=set(), pairs=[], L=w)
            rounds.append(r)
        r["src"].add(s)
        r["dst"].add(d)
        r["pairs"].append((s, d))
    perms = tuple(tuple(sorted(r["pairs"])) for r in rounds)
    round_L = tuple(r["L"] for r in rounds)
    if sum(round_L) > sum(cyc_L):
        return cyclic  # never schedule worse than the cyclic rounds
    return perms, round_L


# --------------------------------------------------------------------------
# host side
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Partition:
    """Equal-block row partition: block p owns rows [p*R, min((p+1)*R, D)).

    ``d_pad`` (a multiple of P, >= D) fixes the padded global extent so that
    stack- and panel-layout engines over the same vectors agree on shapes;
    defaults to ceil(D/P)*P.

    This is the ``balance="rows"``, ``reorder="none"`` fast path of the
    partition planner — non-uniform / reordered decompositions are
    expressed by ``core/partition.py``'s :class:`~repro.core.partition.
    RowMap` and consumed via ``build_dist_ell(..., rowmap=...)``.
    """

    D: int
    P: int
    d_pad: int | None = None

    @property
    def D_pad(self) -> int:
        if self.d_pad is not None:
            assert self.d_pad % self.P == 0 and self.d_pad >= self.D
            return self.d_pad
        return (-(-self.D // self.P)) * self.P

    @property
    def R(self) -> int:
        return self.D_pad // self.P

    def boundaries(self) -> np.ndarray:
        return np.minimum(np.arange(self.P + 1, dtype=np.int64) * self.R, self.D)

    def owner(self, cols: np.ndarray) -> np.ndarray:
        return np.minimum(cols // self.R, self.P - 1)


@dataclasses.dataclass
class NeighborPlan:
    """Static schedule of the compressed (neighbor-permute) halo exchange.

    One ``lax.ppermute`` round per entry of ``perms``: round ``r``
    applies the (partial) permutation ``perms[r]`` — a tuple of
    ``(src, dst)`` device pairs produced by :func:`neighbor_schedule`
    (the full shift permutation for the cyclic scheduler, only the
    matched pairs for the matching scheduler) — with every send segment
    padded to ``round_L[r]`` slots. A device absent from a round's
    permutation receives zeros there and references none of those slots.
    Pairs with zero volume are never scheduled, so they move no bytes at
    all. The receive buffers concatenate in round order into a compact
    halo region of ``H = sum(round_L)`` entries (vs ``P * L`` for the
    padded a2a). ``cols_halo_nbr`` is only needed by the overlap variant
    and is filled lazily (``DistEll.neighbor_plan(split_halo=True)``) so
    the plain compressed engine never materializes the local/halo split.

    ``halo_rounds`` is the round-pipelined form of the split halo block:
    one ELL sub-block ``(cols_r, vals_r)`` per permute round, holding —
    complete, in the original slot order — every halo row whose LAST
    needed sender lands in round r (each round is a partial permutation,
    so rounds partition the halo by sender and a row completes exactly
    when its highest-round sender arrives). ``cols_r`` keeps the compact
    [0, H) positions, all ``< sum(round_L[:r+1])``, so sub-block r
    gathers only from the concatenated prefix of rounds ``<= r`` — the
    pipelined engine can contract it while round r+1's ``ppermute`` is
    still in flight, and because each row's halo entries are contracted
    atomically in slot order the result is bit-identical to the
    unpipelined engines. Built lazily from concrete operands; stays
    ``None`` on surrogate (shape-only) operators, where the engine falls
    back to the all-rounds-then-contract body.
    """

    perms: tuple[tuple[tuple[int, int], ...], ...]  # per-round (src, dst)
    round_L: tuple[int, ...]  # per-round pad: max scheduled pair volume
    send_nbr: jax.Array       # [P, H] int32 local rows to ship, round-major
    cols_nbr: jax.Array       # [P, R, W] combined cols, halo re-based to [R, R+H)
    cols_halo_nbr: jax.Array | None = None  # [P, R, W_halo] split halo cols in [0, H)
    halo_rounds: tuple | None = None  # per-round ([P,R,W_r] cols, vals) sub-blocks

    @property
    def H(self) -> int:
        """Per-device moved entries per vector column (Σ_r L_r)."""
        return int(sum(self.round_L))

    def scheduled_pairs(self) -> tuple[tuple[int, int], ...]:
        """All (src, dst) device pairs across rounds, in round order —
        the introspection surface of the static plan linter
        (``repro.analysis.plan_lint``): a pair scheduled twice or a round
        that is not a partial permutation shows up directly here."""
        return tuple(p for perm in self.perms for p in perm)


@dataclasses.dataclass
class DistEll:
    """Pytree of device arrays for the distributed ELL SpMV.

    All arrays carry a leading P axis that is sharded over the horizontal
    mesh axes inside ``make_spmv``. The four ``*_loc`` / ``*_halo`` fields
    are the split-phase form consumed by the overlap engine; they are
    populated on demand by :meth:`split` (or eagerly with
    ``build_dist_ell(..., split_halo=True)``). ``pair_counts`` holds the
    true per-(sender, receiver) volumes L_qp behind the comm plan;
    :meth:`neighbor_plan` turns them into the compressed engine's
    ppermute schedule — cyclic-shift or matching rounds — lazily, cached
    per scheduler in ``nbr``.
    """

    cols: jax.Array  # [P, R, W] int32, remapped columns
    vals: jax.Array  # [P, R, W] matrix dtype
    send_idx: jax.Array  # [P, P, L] int32 local row indices to ship
    R: int = dataclasses.field(metadata=dict(static=True))
    L: int = dataclasses.field(metadata=dict(static=True))
    P: int = dataclasses.field(metadata=dict(static=True))
    D: int = dataclasses.field(metadata=dict(static=True))
    n_vc: np.ndarray | None = None  # exact per-shard remote counts (diagnostics)
    pair_counts: np.ndarray | None = None  # [P, P] true volumes L_qp (q -> p)
    cols_loc: jax.Array | None = None   # [P, R, W_loc] columns in [0, R)
    vals_loc: jax.Array | None = None   # [P, R, W_loc]
    cols_halo: jax.Array | None = None  # [P, R, W_halo] columns in [0, P*L)
    vals_halo: jax.Array | None = None  # [P, R, W_halo]
    nbr: dict | None = None  # schedule name -> NeighborPlan (cached)
    rowmap: RowMap | None = None  # planned row decomposition (None = equal rows)

    @property
    def comm_bytes_per_spmv(self) -> int:
        """all_to_all payload per vector column, summed over shards."""
        return self.P * self.P * self.L * self.vals.dtype.itemsize

    @property
    def halo_nnz_fraction(self) -> float:
        """Fraction of stored nonzeros in the halo part (perf-model input).

        Computed directly from masks on the combined ``cols``/``vals`` —
        no local/halo device arrays are materialized for a count.
        """
        cols = np.asarray(self.cols)
        vals = np.asarray(self.vals)
        stored = vals != 0
        n_halo = int(np.count_nonzero(stored & (cols >= self.R)))
        n_all = int(np.count_nonzero(stored))
        return n_halo / max(n_all, 1)

    def split(self):
        """Split the combined ELL into (cols_loc, vals_loc, cols_halo,
        vals_halo) for the overlap engine; cached after the first call.

        Local columns keep their [0, R) indices; halo columns are rebased
        into the received buffer, i.e. [0, P*L). Per row, the split parts
        preserve the combined slot order (local ascending, then halo
        ascending), so split + unsplit contractions sum in the same order.
        """
        if self.cols_loc is not None:
            return self.cols_loc, self.vals_loc, self.cols_halo, self.vals_halo
        cols = np.asarray(self.cols)
        vals = np.asarray(self.vals)
        P, R, W = cols.shape
        stored = vals != 0
        is_halo = stored & (cols >= self.R)
        is_loc = stored & ~is_halo
        W_loc = int(is_loc.sum(axis=2).max()) if W else 0
        W_halo = int(is_halo.sum(axis=2).max()) if W else 0
        W_loc = max(W_loc, 1)  # keep the local block non-degenerate
        cols_loc = np.zeros((P, R, W_loc), dtype=cols.dtype)
        vals_loc = np.zeros((P, R, W_loc), dtype=vals.dtype)
        cols_halo = np.zeros((P, R, W_halo), dtype=cols.dtype)
        vals_halo = np.zeros((P, R, W_halo), dtype=vals.dtype)
        for p in range(P):
            for part, mask, carr, varr, rebase in (
                ("loc", is_loc[p], cols_loc[p], vals_loc[p], 0),
                ("halo", is_halo[p], cols_halo[p], vals_halo[p], self.R),
            ):
                rows, slots = np.nonzero(mask)
                if not len(rows):
                    continue
                counts = np.bincount(rows, minlength=R)
                out_slot = np.arange(len(rows)) - np.repeat(
                    np.cumsum(counts) - counts, counts
                )
                carr[rows, out_slot] = cols[p][rows, slots] - rebase
                varr[rows, out_slot] = vals[p][rows, slots]
        self.cols_loc = jnp.asarray(cols_loc)
        self.vals_loc = jnp.asarray(vals_loc)
        self.cols_halo = jnp.asarray(cols_halo)
        self.vals_halo = jnp.asarray(vals_halo)
        return self.cols_loc, self.vals_loc, self.cols_halo, self.vals_halo

    # ------------------------------------------------- compressed engine --

    def _round_offsets(self, schedule: str):
        """(perms, round_L, off_by_pair): the scheduler's permutation
        rounds, the per-round pads, and each scheduled (sender, receiver)
        pair's offset into the concatenated receive buffer (-1 = the pair
        is in no round, i.e. moves nothing)."""
        if self.pair_counts is None:
            raise ValueError(
                "compressed engine needs per-pair volumes; rebuild the "
                "operator with build_dist_ell (pair_counts=None)")
        perms, round_L = neighbor_schedule(self.pair_counts, schedule)
        off_by_pair = np.full((self.P, self.P), -1, dtype=np.int64)
        H = 0
        for perm, Lk in zip(perms, round_L):
            for s, d in perm:
                off_by_pair[s, d] = H
            H += Lk
        return perms, round_L, off_by_pair

    def _rebase_halo(self, cols, vals, halo_mask_base, off_by_pair, base):
        """Re-base halo columns ``halo_mask_base + q*L + slot`` (a2a receive
        layout) into ``base + off(q, p) + slot`` (compact round buffer),
        touching only stored entries — the ELL slot layout is unchanged, so
        the compressed contraction accumulates in the baseline's order."""
        out = []
        for p in range(self.P):
            cp = cols[p].copy()
            halo = (vals[p] != 0) & (cp >= halo_mask_base)
            if halo.any():
                c = cp[halo] - halo_mask_base
                q, slot = c // self.L, c % self.L
                off = off_by_pair[q, p]
                assert (off >= 0).all(), "stored halo entry in no round"
                cp[halo] = (base + off + slot).astype(cp.dtype)
            out.append(cp)
        return np.stack(out)

    def neighbor_plan(self, split_halo: bool = False,
                      schedule: str = "cyclic") -> NeighborPlan:
        """Compressed-engine schedule + re-based device arrays; cached per
        scheduler (``schedule={"cyclic", "matching"}``).

        ``send_nbr[q]`` concatenates, round-major, the first L_r send
        slots of the pair q is the source of in round r (zeros where q is
        idle); ``cols_nbr`` is the combined ELL with halo columns re-based
        into ``[R, R + H)``. ``split_halo=True`` additionally fills
        ``cols_halo_nbr`` (the split-phase halo block re-based into
        ``[0, H)``) for the overlap variant — the plain compressed engine
        skips the split entirely.
        """
        if self.nbr is None:
            self.nbr = {}
        plan = self.nbr.get(schedule)
        if plan is None:
            perms, round_L, off_by_pair = self._round_offsets(schedule)
            P = self.P
            send_idx = np.asarray(self.send_idx)
            H = int(sum(round_L))
            send_nbr = np.zeros((P, max(H, 1)), dtype=np.int32)
            off = 0
            for perm, Lk in zip(perms, round_L):
                for s, d in perm:
                    send_nbr[s, off:off + Lk] = send_idx[s, d, :Lk]
                off += Lk
            cols_nbr = self._rebase_halo(np.asarray(self.cols),
                                         np.asarray(self.vals),
                                         self.R, off_by_pair, self.R)
            plan = NeighborPlan(
                perms=perms, round_L=round_L,
                send_nbr=jnp.asarray(send_nbr),
                cols_nbr=jnp.asarray(cols_nbr),
            )
            self.nbr[schedule] = plan
        if split_halo and plan.cols_halo_nbr is None:
            _, _, ch, vh = self.split()
            _, _, off_by_pair = self._round_offsets(schedule)
            # split halo cols already sit at base 0 (values q*L + slot)
            ch_nbr = (self._rebase_halo(np.asarray(ch), np.asarray(vh),
                                        0, off_by_pair, 0)
                      if ch.shape[2] else np.asarray(ch))
            plan.cols_halo_nbr = jnp.asarray(ch_nbr)
        if (split_halo and plan.halo_rounds is None
                and _host_concrete(plan.cols_halo_nbr)
                and _host_concrete(self.vals_halo)):
            plan.halo_rounds = _build_halo_rounds(
                np.asarray(plan.cols_halo_nbr), np.asarray(self.vals_halo),
                plan.round_L)
        return plan


def _host_concrete(a) -> bool:
    """True when ``a`` is a concrete host-readable array (not a tracer,
    not a ShapeDtypeStruct surrogate) — gate for lazy host-side planning
    such as the pipelined round sub-blocks and the kernel tile batches."""
    from ..kernels.ops import is_concrete

    return a is not None and is_concrete(a)


def _build_halo_rounds(ch_nbr: np.ndarray, vh: np.ndarray,
                       round_L: tuple[int, ...]) -> tuple:
    """Group the split halo block by completion round (host side).

    ``ch_nbr`` holds compact [0, H) halo positions (round-major), ``vh``
    the matching values; entry positions in ``[Σ round_L[:r], Σ
    round_L[:r+1])`` arrive in round r. A row is assigned to the round of
    its HIGHEST-round entry — the earliest point at which every one of
    its halo operands has been received — and its entries are packed into
    that round's ELL sub-block in the original slot order. Positions are
    NOT re-based: sub-block r gathers from the concatenated prefix buffer
    of rounds <= r, whose length ``Σ round_L[:r+1]`` bounds every packed
    position by construction.
    """
    P, R, Wh = ch_nbr.shape
    stored = vh != 0
    ends = np.cumsum(np.asarray(round_L, dtype=np.int64))
    rounds = []
    if Wh:
        rnd = np.searchsorted(ends, ch_nbr, side="right")
        row_last = np.where(stored, rnd, -1).max(axis=2)  # [P, R]
    else:
        row_last = np.full((P, R), -1, dtype=np.int64)
    for r in range(len(round_L)):
        m = stored & (row_last == r)[:, :, None] if Wh else np.zeros(
            (P, R, 0), dtype=bool)
        Wr = int(m.sum(axis=2).max()) if Wh else 0
        cr = np.zeros((P, R, Wr), dtype=np.int32)
        vr = np.zeros((P, R, Wr), dtype=vh.dtype)
        for p in range(P):
            rows, slots = np.nonzero(m[p])
            if not len(rows):
                continue
            counts = np.bincount(rows, minlength=R)
            out_slot = np.arange(len(rows)) - np.repeat(
                np.cumsum(counts) - counts, counts)
            cr[p, rows, out_slot] = ch_nbr[p, rows, slots]
            vr[p, rows, out_slot] = vh[p, rows, slots]
        rounds.append((jnp.asarray(cr), jnp.asarray(vr)))
    return tuple(rounds)


def _pattern_chunks(matrix, rows):
    r, c, v = matrix.row_entries(rows)
    return r, c, v


def build_dist_ell(
    matrix: MatrixFamily | CSR,
    P_row: int,
    dtype=None,
    d_pad: int | None = None,
    split_halo: bool = False,
    rowmap: RowMap | None = None,
) -> DistEll:
    """Build per-shard ELL blocks + comm plan for P_row horizontal shards.

    With ``split_halo=True`` the local/halo split consumed by the overlap
    engine is built eagerly (otherwise ``make_spmv(..., overlap=True)``
    materializes it lazily on first use).

    ``rowmap`` makes the row decomposition a planned quantity
    (``core/partition.py``): shard p owns the rows the map places at
    positions ``[p·R, (p+1)·R)`` of the padded position space — possibly
    non-uniform (``balance="commvol"``) and/or RCM-reordered
    (``reorder="rcm"``). The map may be planned at any level whose
    ``D_pad`` is divisible by ``P_row``, so the stack- and panel-level
    operators of one solve share a single map. Without a map (or with
    the identity map) the equal-rows :class:`Partition` fast path is
    taken, and ``d_pad`` keeps its historical meaning.

    ``L`` is the *true* max per-pair volume: **zero** when no shard
    needs any remote column, in which case the engines skip the halo
    exchange entirely and the pattern-only byte prediction (0) stays
    exact — empty pairs are never charged a phantom 1-entry pad.
    """
    if rowmap is not None:
        if rowmap.D != (matrix.shape[0] if isinstance(matrix, CSR)
                        else matrix.D):
            raise ValueError("rowmap.D does not match the matrix")
        if d_pad is not None and d_pad != rowmap.D_pad:
            raise ValueError(f"d_pad={d_pad} conflicts with the rowmap's "
                             f"D_pad={rowmap.D_pad}")
        if not rowmap.identity:
            ell = _build_dist_ell_mapped(matrix, P_row, rowmap, dtype)
            if split_halo:
                ell.split()
            return ell
        d_pad = rowmap.D_pad
    if isinstance(matrix, CSR):
        D = matrix.shape[0]
        get_rows = lambda a, b: _csr_rows(matrix, a, b)
    else:
        from ..matrices.matfree import collect_row_entries

        # windowed generator protocol: shard blocks of a streaming-scale
        # family never materialize one whole-shard COO temporary
        D = matrix.D
        get_rows = lambda a, b: collect_row_entries(
            matrix, np.arange(a, b, dtype=np.int64))
    part = Partition(D, P_row, d_pad)
    R = part.R
    per_shard = []
    for p in range(P_row):
        a, b = int(p * R), int(min(max((p + 1) * R, 0), D))
        a = min(a, D)
        rows, cols, vals = get_rows(a, b)
        per_shard.append((a, b, rows, cols, vals))

    # remote needs per (receiver p, owner q)
    need: list[dict[int, np.ndarray]] = []
    for p, (a, b, rows, cols, vals) in enumerate(per_shard):
        remote = np.unique(cols[(cols < a) | (cols >= b)])
        owners = part.owner(remote)
        need.append({int(q): remote[owners == q] for q in np.unique(owners)})
    L = max((len(v) for d in need for v in d.values()), default=0)

    # true per-pair volumes L_qp (sender q -> receiver p) — the compressed
    # engine's neighbor schedule and the planner's χ₂-scaled byte
    # prediction both derive from these
    pair_counts = np.zeros((P_row, P_row), dtype=np.int64)
    send_idx = np.zeros((P_row, P_row, L), dtype=np.int32)
    for p, d in enumerate(need):
        for q, glob in d.items():
            pair_counts[q, p] = len(glob)
            send_idx[q, p, : len(glob)] = (glob - q * R).astype(np.int32)

    # local ELL with remapped columns
    W = 0
    shard_ell = []
    for p, (a, b, rows, cols, vals) in enumerate(per_shard):
        local = (cols >= a) & (cols < b)
        newcols = np.empty_like(cols)
        newcols[local] = cols[local] - a
        rem = ~local
        if rem.any():
            rc = cols[rem]
            q = part.owner(rc)
            # slot of each remote col within need[p][q] (sorted): searchsorted
            slot = np.empty(len(rc), dtype=np.int64)
            for qq in np.unique(q):
                m = q == qq
                slot[m] = np.searchsorted(need[p][int(qq)], rc[m])
            newcols[rem] = R + q * L + slot
        # rows relative to block start, build padded ELL
        rel = rows - a
        order = np.lexsort((newcols, rel))
        rel, newcols, vals = rel[order], newcols[order], vals[order]
        counts = np.bincount(rel, minlength=R)
        W = max(W, int(counts.max()) if len(counts) else 0)
        shard_ell.append((rel, newcols, vals, counts))

    vdtype = (np.dtype(dtype) if dtype is not None
              else shard_ell[0][2].dtype if shard_ell else np.float64)
    cols_arr = np.zeros((P_row, R, W), dtype=np.int32)
    vals_arr = np.zeros((P_row, R, W), dtype=vdtype)
    for p, (rel, newcols, vals, counts) in enumerate(shard_ell):
        slot = np.arange(len(rel)) - np.repeat(np.cumsum(counts) - counts, counts)
        cols_arr[p, rel, slot] = newcols
        vals_arr[p, rel, slot] = vals.astype(vdtype)

    n_vc = np.array([sum(len(v) for v in d.values()) for d in need], dtype=np.int64)
    ell = DistEll(
        cols=jnp.asarray(cols_arr),
        vals=jnp.asarray(vals_arr),
        send_idx=jnp.asarray(send_idx),
        R=R,
        L=L,
        P=P_row,
        D=D,
        n_vc=n_vc,
        pair_counts=pair_counts,
        rowmap=rowmap,
    )
    if split_halo:
        ell.split()
    return ell


def _build_dist_ell_mapped(matrix, P_row: int, rowmap: RowMap,
                           dtype=None) -> DistEll:
    """``build_dist_ell`` body for a non-identity :class:`RowMap`.

    Identical output semantics as the fast path, expressed in *position*
    space: shard p's ELL row i holds the matrix row the map places at
    position ``p·R + i`` (pad positions stay all-zero rows), local
    columns are position offsets, remote columns index the halo region
    ``R + q·L + slot`` with slots assigned in ascending *position* order
    per pair — so the per-row slot order (and hence the accumulation
    order of every engine) follows the mapped layout exactly the way the
    fast path follows the natural one.
    """
    D = rowmap.D
    R = rowmap.level_R(P_row)
    pos = rowmap.pos
    if isinstance(matrix, CSR):
        get_rows = lambda rows_g: _csr_rows_at(matrix, rows_g)
    else:
        from ..matrices.matfree import collect_row_entries

        get_rows = lambda rows_g: collect_row_entries(matrix, rows_g)
    per_shard = []
    for p in range(P_row):
        rows_g, _ = rowmap.shard_rows(p, P_row)
        rows, cols, vals = get_rows(rows_g)
        per_shard.append((rows, cols, vals))

    # remote needs per (receiver p, owner q), as sorted sender positions
    need: list[dict[int, np.ndarray]] = []
    for p, (rows, cols, vals) in enumerate(per_shard):
        cpos = pos[cols]
        remote = np.unique(cpos[(cpos // R) != p])
        owners = remote // R
        need.append({int(q): remote[owners == q] for q in np.unique(owners)})
    L = max((len(v) for d in need for v in d.values()), default=0)

    pair_counts = np.zeros((P_row, P_row), dtype=np.int64)
    send_idx = np.zeros((P_row, P_row, L), dtype=np.int32)
    for p, d in enumerate(need):
        for q, spos in d.items():
            pair_counts[q, p] = len(spos)
            send_idx[q, p, : len(spos)] = (spos - q * R).astype(np.int32)

    W = 0
    shard_ell = []
    for p, (rows, cols, vals) in enumerate(per_shard):
        cpos = pos[cols]
        local = (cpos // R) == p
        newcols = np.empty(len(cols), dtype=np.int64)
        newcols[local] = cpos[local] - p * R
        rem = ~local
        if rem.any():
            rc = cpos[rem]
            q = rc // R
            slot = np.empty(len(rc), dtype=np.int64)
            for qq in np.unique(q):
                m = q == qq
                slot[m] = np.searchsorted(need[p][int(qq)], rc[m])
            newcols[rem] = R + q * L + slot
        rel = pos[rows] - p * R
        order = np.lexsort((newcols, rel))
        rel, newcols, vals = rel[order], newcols[order], vals[order]
        counts = np.bincount(rel, minlength=R)
        W = max(W, int(counts.max()) if len(counts) else 0)
        shard_ell.append((rel, newcols, vals, counts))

    vdtype = (np.dtype(dtype) if dtype is not None
              else shard_ell[0][2].dtype if shard_ell else np.float64)
    cols_arr = np.zeros((P_row, R, W), dtype=np.int32)
    vals_arr = np.zeros((P_row, R, W), dtype=vdtype)
    for p, (rel, newcols, vals, counts) in enumerate(shard_ell):
        slot = np.arange(len(rel)) - np.repeat(np.cumsum(counts) - counts, counts)
        cols_arr[p, rel, slot] = newcols
        vals_arr[p, rel, slot] = vals.astype(vdtype)

    n_vc = np.array([sum(len(v) for v in d.values()) for d in need],
                    dtype=np.int64)
    return DistEll(
        cols=jnp.asarray(cols_arr),
        vals=jnp.asarray(vals_arr),
        send_idx=jnp.asarray(send_idx),
        R=R,
        L=L,
        P=P_row,
        D=D,
        n_vc=n_vc,
        pair_counts=pair_counts,
        rowmap=rowmap,
    )


def _csr_rows(csr: CSR, a: int, b: int):
    lo, hi = int(csr.indptr[a]), int(csr.indptr[b])
    counts = np.diff(csr.indptr[a : b + 1])
    rows = np.repeat(np.arange(a, b, dtype=np.int64), counts)
    return rows, csr.indices[lo:hi].astype(np.int64), csr.data[lo:hi]


def _csr_rows_at(csr: CSR, rows_g: np.ndarray):
    """(rows, cols, vals) of an arbitrary (not necessarily contiguous)
    row set — the mapped partition's accessor."""
    from ..matrices.sparse import gather_row_entry_idx

    rows_g = np.asarray(rows_g, dtype=np.int64)
    gather, counts = gather_row_entry_idx(csr.indptr, rows_g)
    rows = np.repeat(rows_g, counts)
    return rows, csr.indices[gather].astype(np.int64), csr.data[gather]


# --------------------------------------------------------------------------
# device side
# --------------------------------------------------------------------------


def _ell_contract(acc, cols, vals, xsrc):
    """W-step scan accumulation of an ELL block into acc — shared by the
    baseline and overlap engines so they stay bit-for-bit equivalent (no
    [R, W, nb] temporary materialized after fusion)."""
    def body(acc, cw):
        c, v = cw
        return acc + v[:, None] * jnp.take(xsrc, c, axis=0), None

    with jax.named_scope("fd.spmv"):
        acc, _ = lax.scan(body, acc, (cols.T, vals.T))
    return acc


def _contract_block(acc, cols, vals, xsrc, tiles):
    """Contract one ELL block into ``acc`` — Pallas tile kernel when a
    per-device tile batch ``(tile_cb, tcols, tvals, br, bc)`` is given,
    the jnp scan otherwise. Both paths thread ``acc`` and visit stored
    entries in ascending-column order, so the choice never changes a
    bit of the result."""
    if tiles is None:
        return _ell_contract(acc, cols, vals, xsrc)
    from ..kernels import ops as kops

    tile_cb, tcols, tvals, br, bc = tiles
    with jax.named_scope("fd.spmv"):
        return kops.ell_spmv_tiled(tile_cb, tcols, tvals, xsrc, y0=acc,
                                   br=br, bc=bc)


def _halo_a2a(x, send_idx, dist_axes, P_row, L):
    """The padded halo exchange: each shard's send slots to every peer in
    one ``all_to_all``; returns the received [P_row * L, nb] halo."""
    with jax.named_scope("fd.halo"):
        send = jnp.take(x, send_idx, axis=0)  # [P, L, nb]
        halo = lax.all_to_all(send, dist_axes, split_axis=0, concat_axis=0,
                              tiled=False)
        return halo.reshape(P_row * L, x.shape[1])


def _local_spmv(cols, vals, send_idx, x, dist_axes, P_row, L, tiles=None):
    """Per-device body: halo exchange + ELL contraction. x: [R, nb] local.

    ``L == 0`` means no shard needs any remote column (a zero-halo
    partition) — the exchange is skipped entirely, so the engine moves
    exactly the zero bytes the pattern-only prediction charges."""
    R, W = cols.shape
    nb = x.shape[1]
    if P_row > 1 and L:
        halo = _halo_a2a(x, send_idx, dist_axes, P_row, L)
        xfull = jnp.concatenate([x, halo], axis=0)
    else:
        xfull = x
    acc0 = jnp.zeros((R, nb), dtype=jnp.result_type(vals.dtype, x.dtype))
    return _contract_block(acc0, cols, vals, xfull, tiles)


def _local_spmv_overlap(cols_loc, vals_loc, cols_halo, vals_halo, send_idx, x,
                        dist_axes, P_row, L, tiles=None):
    """Split-phase per-device body: launch the halo exchange, contract the
    local ELL while bytes are in flight, then contract the halo ELL.

    The all_to_all has no data dependence on the local contraction, so on
    backends with async collectives XLA hides it behind step 2 — the
    ``T = max(T_comm, T_local) + T_halo`` execution model. The halo
    contraction THREADS the local accumulator (whether the local block
    ran in the tile kernel or the jnp scan), preserving the unsplit slot
    order."""
    R = cols_loc.shape[0]
    nb = x.shape[1]
    if P_row > 1 and L:
        halo = _halo_a2a(x, send_idx, dist_axes, P_row, L)
    else:
        halo = jnp.zeros((0, nb), dtype=x.dtype)
    acc0 = jnp.zeros((R, nb), dtype=jnp.result_type(vals_loc.dtype, x.dtype))
    acc = _contract_block(acc0, cols_loc, vals_loc, x, tiles)  # overlaps comm
    if cols_halo.shape[1]:
        acc = _ell_contract(acc, cols_halo, vals_halo, halo)
    return acc


def _halo_parts_nbr(x, send_nbr, dist_axes, perms, round_L):
    """Launch all compressed ``ppermute`` rounds; return the per-round
    received segments (round r's segment is [round_L[r], nb]). Every
    round depends only on ``x``/``send_nbr`` — never on another round or
    on any contraction — so async-collective backends pipeline them
    freely and the round-pipelined engine can consume segment r while
    round r+1 is still in flight."""
    parts = []
    off = 0
    for perm, Lk in zip(perms, round_L):
        with jax.named_scope("fd.halo"):
            seg = jnp.take(x, send_nbr[off:off + Lk], axis=0)  # [Lk, nb]
            parts.append(lax.ppermute(seg, dist_axes, perm=list(perm)))
        off += Lk
    return parts


def _halo_exchange_nbr(x, send_nbr, dist_axes, perms, round_L):
    """Compressed halo exchange: one ``ppermute`` round per scheduled
    permutation, each padded to that round's max scheduled pair volume
    only; the received segments concatenate into the compact [H, nb] halo
    buffer (devices outside a round's perm receive zeros there)."""
    parts = _halo_parts_nbr(x, send_nbr, dist_axes, perms, round_L)
    if not parts:
        return jnp.zeros((0, x.shape[1]), dtype=x.dtype)
    return jnp.concatenate(parts, axis=0)


def _local_spmv_nbr(cols_nbr, vals, send_nbr, x, dist_axes, P_row, nbr: NeighborPlan,
                    tiles=None):
    """Compressed per-device body: neighbor-permute rounds + combined ELL
    contraction against ``[x_local ‖ compact halo]``. The ELL slot layout
    equals the baseline's, so the accumulation order (and hence the result,
    bit-for-bit) matches the a2a engine."""
    R, W = cols_nbr.shape
    nb = x.shape[1]
    if P_row > 1 and nbr.H:
        halo = _halo_exchange_nbr(x, send_nbr, dist_axes,
                                  nbr.perms, nbr.round_L)
        xfull = jnp.concatenate([x, halo], axis=0)
    else:
        xfull = x
    acc0 = jnp.zeros((R, nb), dtype=jnp.result_type(vals.dtype, x.dtype))
    return _contract_block(acc0, cols_nbr, vals, xfull, tiles)


def _local_spmv_nbr_overlap(cols_loc, vals_loc, cols_halo_nbr, vals_halo,
                            send_nbr, x, dist_axes, P_row, nbr: NeighborPlan,
                            tiles=None):
    """Compressed split-phase body WITHOUT round pipelining: launch the
    permute rounds, contract the local ELL while the (χ₂-proportional)
    bytes are in flight, contract the whole halo ELL against the compact
    receive buffer last. Kept as the fallback for surrogate operators
    (no concrete values to derive round sub-blocks from) and as the
    negative control of the round-pipeline split-phase proof
    (``make_spmv(..., pipeline=False)``)."""
    R = cols_loc.shape[0]
    nb = x.shape[1]
    if P_row > 1 and nbr.H:
        halo = _halo_exchange_nbr(x, send_nbr, dist_axes,
                                  nbr.perms, nbr.round_L)
    else:
        halo = jnp.zeros((0, nb), dtype=x.dtype)
    acc0 = jnp.zeros((R, nb), dtype=jnp.result_type(vals_loc.dtype, x.dtype))
    acc = _contract_block(acc0, cols_loc, vals_loc, x, tiles)  # overlaps comm
    if cols_halo_nbr.shape[1]:
        acc = _ell_contract(acc, cols_halo_nbr, vals_halo, halo)
    return acc


def _local_spmv_nbr_pipelined(cols_loc, vals_loc, halo_rounds, send_nbr, x,
                              dist_axes, P_row, nbr: NeighborPlan,
                              tiles=None):
    """Round-pipelined compressed split-phase body.

    All permute rounds launch up front (mutually independent), the local
    block contracts while they fly, and then round r's ELL sub-block —
    the halo rows COMPLETED by round r, i.e. whose last needed sender
    lands in round r — contracts against the concatenated prefix of
    received segments ``parts[:r+1]``. Contraction r therefore depends
    on collectives 1..r and on no later round: on async-collective
    backends round r+1's ppermute is in flight while round r's rows
    contract (the split-phase proof in ``analysis/overlap_check.py``
    checks exactly this prefix-chain dependence structure).

    Bit-identity with the unpipelined engines is by construction: each
    halo row appears in exactly one sub-block with ALL its halo entries
    in the original slot order, gathered from prefix-buffer positions
    identical to the full compact buffer's, so the per-element addition
    chain (local slots, then halo slots ascending) is unchanged — the
    sub-blocks only reorder which ROWS contract early, never the order
    of any row's summands."""
    R = cols_loc.shape[0]
    nb = x.shape[1]
    parts = (_halo_parts_nbr(x, send_nbr, dist_axes, nbr.perms, nbr.round_L)
             if P_row > 1 and nbr.H else [])
    acc0 = jnp.zeros((R, nb), dtype=jnp.result_type(vals_loc.dtype, x.dtype))
    acc = _contract_block(acc0, cols_loc, vals_loc, x, tiles)  # overlaps comm
    buf = jnp.zeros((0, nb), dtype=x.dtype)
    for part, (cols_r, vals_r) in zip(parts, halo_rounds):
        buf = jnp.concatenate([buf, part], axis=0)  # prefix of rounds <= r
        if cols_r.shape[1]:
            acc = _ell_contract(acc, cols_r, vals_r, buf)
    return acc


def _dev_tiles(plan, arrays):
    """Per-device tile tuple for :func:`_contract_block` from an
    :class:`~repro.kernels.ops.EllTilePlan` and the shard_map-delivered
    (already shard-indexed) device arrays; None when no plan exists."""
    if plan is None:
        return None
    tile_cb, tcols, tvals = arrays
    return (tile_cb, tcols, tvals, plan.br, plan.bc)


def _validate_engine(comm: str, schedule: str) -> None:
    if comm not in SPMV_COMM_ENGINES:
        raise ValueError(f"unknown comm engine {comm!r} "
                         f"(expected one of {SPMV_COMM_ENGINES})")
    if schedule not in SPMV_SCHEDULES:
        raise ValueError(f"unknown schedule {schedule!r} "
                         f"(expected one of {SPMV_SCHEDULES})")
    if comm != "compressed" and schedule != "cyclic":
        raise ValueError(f"schedule={schedule!r} only applies to "
                         f"comm='compressed' (got comm={comm!r})")


def _build_engine(mesh: Mesh, layout: Layout, ell: DistEll, *, use_kernel: bool,
                  overlap: bool, comm: str, schedule: str, pipeline: bool,
                  fused: bool):
    """Shared builder behind :func:`make_spmv` and
    :func:`make_fused_cheb_step`: assembles the per-engine plan arrays,
    the per-device contraction body and the ``shard_map`` wrapper. With
    ``fused=True`` the returned closure computes the fused Chebyshev
    step ``2a (A w1) + 2b w1 - w2`` in the same body (and, for
    kernel-enabled comm-free diagonal-structured operators, dispatches
    the whole step to the ``cheb_dia`` Pallas kernel)."""
    _validate_engine(comm, schedule)
    dist = layout.dist_axes
    vec_spec = layout.vec_pspec()

    def pspec(a):
        return P(dist if dist else None, *((None,) * (a.ndim - 1)))

    kops = None
    if use_kernel:
        from ..kernels import ops as kops_mod

        kops = kops_mod

    if fused and use_kernel and (ell.P == 1 or ell.L == 0):
        # comm-free operator: try the fused DIA Chebyshev kernel for the
        # whole step (bit-identical: ascending offsets == ascending
        # columns == the ELL slot order, same fused epilogue expression)
        dia = kops.plan_dia(ell.cols, ell.vals, ell.R)
        if dia is not None:
            offsets = dia.offsets

            def local_fn_dia(dv, w1, w2, a, b):
                return kops.cheb_dia(offsets, dv[0], w1, w1, w2, a, b)

            fn = shard_map(
                local_fn_dia,
                mesh=mesh,
                in_specs=(pspec(dia.dvals), vec_spec, vec_spec, P(), P()),
                out_specs=vec_spec,
                check_vma=False,
            )

            return _bind(_fused_caller(fn), [dia.dvals], "cheb_dia")

    if comm == "compressed":
        nbr = ell.neighbor_plan(split_halo=overlap, schedule=schedule)
        if overlap:
            cols_loc, vals_loc, _, vals_halo = ell.split()
            tiles_plan = (kops.plan_ell_tiles(cols_loc, vals_loc, ell.R)
                          if use_kernel else None)
            tile_args = list(tiles_plan.arrays()) if tiles_plan else []
            rounds = nbr.halo_rounds if pipeline else None
            if rounds is not None:
                n_r = len(rounds)
                args = ([cols_loc, vals_loc, nbr.send_nbr]
                        + [a for cv in rounds for a in cv] + tile_args)

                def body(x, cl, vl, send_nbr, *rest):
                    rnds = tuple((rest[2 * i], rest[2 * i + 1])
                                 for i in range(n_r))
                    return _local_spmv_nbr_pipelined(
                        cl, vl, rnds, send_nbr, x, dist, ell.P, nbr,
                        _dev_tiles(tiles_plan, rest[2 * n_r:]))
            else:
                args = [cols_loc, vals_loc, nbr.cols_halo_nbr, vals_halo,
                        nbr.send_nbr] + tile_args

                def body(x, cl, vl, ch, vh, send_nbr, *rest):
                    return _local_spmv_nbr_overlap(
                        cl, vl, ch, vh, send_nbr, x, dist, ell.P, nbr,
                        _dev_tiles(tiles_plan, rest))
        else:
            tiles_plan = (kops.plan_ell_tiles(nbr.cols_nbr, ell.vals,
                                              ell.R + nbr.H)
                          if use_kernel else None)
            args = ([nbr.cols_nbr, ell.vals, nbr.send_nbr]
                    + (list(tiles_plan.arrays()) if tiles_plan else []))

            def body(x, cols_nbr, vals, send_nbr, *rest):
                return _local_spmv_nbr(cols_nbr, vals, send_nbr, x, dist,
                                       ell.P, nbr,
                                       _dev_tiles(tiles_plan, rest))
    elif overlap:
        cols_loc, vals_loc, cols_halo, vals_halo = ell.split()
        tiles_plan = (kops.plan_ell_tiles(cols_loc, vals_loc, ell.R)
                      if use_kernel else None)
        args = ([cols_loc, vals_loc, cols_halo, vals_halo, ell.send_idx]
                + (list(tiles_plan.arrays()) if tiles_plan else []))

        def body(x, cl, vl, ch, vh, send_idx, *rest):
            return _local_spmv_overlap(cl, vl, ch, vh, send_idx, x, dist,
                                       ell.P, ell.L,
                                       _dev_tiles(tiles_plan, rest))
    else:
        tiles_plan = (kops.plan_ell_tiles(ell.cols, ell.vals,
                                          ell.R + ell.P * ell.L)
                      if use_kernel else None)
        args = ([ell.cols, ell.vals, ell.send_idx]
                + (list(tiles_plan.arrays()) if tiles_plan else []))

        def body(x, cols, vals, send_idx, *rest):
            return _local_spmv(cols, vals, send_idx, x, dist, ell.P, ell.L,
                               _dev_tiles(tiles_plan, rest))

    n_args = len(args)
    plan_specs = tuple(pspec(a) for a in args)
    body_name = "ell_gather" if tiles_plan is not None else "jnp"

    if fused:
        def local_fn_fused(*ins):
            dev = [a[0] for a in ins[:n_args]]
            w1, w2, a, b = ins[n_args:]
            y = body(w1, *dev)
            with jax.named_scope("fd.cheb_update"):
                return 2.0 * a * y + 2.0 * b * w1 - w2

        fn = shard_map(
            local_fn_fused,
            mesh=mesh,
            in_specs=plan_specs + (vec_spec, vec_spec, P(), P()),
            out_specs=vec_spec,
            check_vma=False,
        )

        return _bind(_fused_caller(fn), args, body_name)

    def local_fn(*ins):
        dev = [a[0] for a in ins[:n_args]]
        (x,) = ins[n_args:]
        return body(x, *dev)

    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=plan_specs + (vec_spec,),
        out_specs=vec_spec,
        check_vma=False,
    )

    return _bind(fn, args, body_name)


def _fused_caller(fn):
    """``fn(*plan, w1, w2, a, b)`` with alpha/beta cast to the vectors'
    real dtype (complex-safe)."""
    def step(*ins):
        *plan, w1, w2, alpha, beta = ins
        rdt = jnp.zeros((), dtype=w1.dtype).real.dtype
        return fn(*plan, w1, w2, jnp.asarray(alpha, dtype=rdt),
                  jnp.asarray(beta, dtype=rdt))

    return step


def _bind(fn, args, body: str):
    """The engine as a pytree: ``fn`` (jitted) bound to its operator
    arrays, which are the leaves. A jitted caller that takes the engine
    as an argument receives those arrays as parameters; captured in a
    closure they would be baked into every program as constants (hundreds
    of MB at deployment size). Called eagerly, the engine runs one cached
    program instead of re-tracing its shard_map body's scans every call.
    ``body`` names the local contraction dispatched."""
    engine = jax.tree_util.Partial(jax.jit(fn), *args)
    engine.body = body
    return engine


def make_spmv(mesh: Mesh, layout: Layout, ell: DistEll, *,
              use_kernel: bool = False, overlap: bool = False,
              comm: str = "a2a", schedule: str = "cyclic",
              pipeline: bool = True):
    """Return spmv(x) on the global padded array X [D_pad, N_s'] where the
    layout's dist axes shard D and bundle axes shard N_s.

    ``overlap=True`` selects the split-phase engine that issues the halo
    exchange before the local contraction so communication can hide
    behind local work (identical results; summation order preserved).
    ``comm`` picks the horizontal-layer exchange: ``"a2a"`` (one
    all_to_all padded to the global max pair volume L — moved bytes scale
    with χ₃) or ``"compressed"`` (neighbor ppermute rounds padded per
    round — moved bytes ≈ χ₂-scaled, empty pairs skipped). ``schedule``
    picks how the compressed engine's rounds are derived from the
    pair-volume matrix: ``"cyclic"`` (one round per nonzero cyclic
    shift) or ``"matching"`` (greedy max-weight matchings — hot pairs of
    different shifts share one round's pad; see
    :func:`neighbor_schedule`).

    ``use_kernel=True`` dispatches the local block to the Pallas
    ``ell_gather`` tile kernel (interpret mode off-TPU); the kernel
    threads the same accumulator chain as the jnp scan, so kernel-on and
    kernel-off engines agree bit-for-bit. ``pipeline`` (compressed +
    overlap only) selects the round-pipelined halo contraction — round
    r's completed rows contract while round r+1's ppermute is in
    flight; ``pipeline=False`` keeps the all-rounds-then-contract body
    (the negative control of the split-phase round proof). All twelve
    engine combinations ({a2a, cmp-cyclic, cmp-matching} x {plain,
    overlap} x {kernel off, on}) agree bit-for-bit.

    The returned function's ``body`` attribute names the local
    contraction it dispatches (``"ell_gather"``, ``"cheb_dia"`` for the
    fused step, or ``"jnp"``). On TPU a requested kernel that cannot take
    the operator raises :class:`~repro.kernels.ops.KernelRefused` here,
    at build time; it is never replaced by the jnp body."""
    return _build_engine(mesh, layout, ell, use_kernel=use_kernel,
                         overlap=overlap, comm=comm, schedule=schedule,
                         pipeline=pipeline, fused=False)


def make_fused_cheb_step(mesh: Mesh, layout: Layout, ell: DistEll, *,
                         use_kernel: bool = False, overlap: bool = False,
                         comm: str = "a2a", schedule: str = "cyclic",
                         pipeline: bool = True):
    """w2' = 2a (A w1) + 2b w1 - w2 — the paper's fused SpMV+axpy kernel
    (Alg. 2 step 7), computed in one shard_map body so XLA (or the Pallas
    kernel) fuses the axpy with the contraction (κ = 5, not 6). With
    ``overlap=True`` the SpMV inside uses the split-phase engine (round-
    pipelined halo contraction when ``comm="compressed"`` and
    ``pipeline=True``); with ``comm="compressed"`` it uses the
    neighbor-permute halo exchange, whose rounds come from the
    ``schedule`` scheduler (same options as :func:`make_spmv`). With
    ``use_kernel=True`` a comm-free diagonal-structured operator runs the
    whole step in the fused ``cheb_dia`` Pallas kernel; otherwise the
    local block uses the ``ell_gather`` tile kernel and the epilogue
    fuses in XLA."""
    return _build_engine(mesh, layout, ell, use_kernel=use_kernel,
                         overlap=overlap, comm=comm, schedule=schedule,
                         pipeline=pipeline, fused=True)


# --------------------------------------------------------------------------
# s-step (communication-avoiding) engine axis
# --------------------------------------------------------------------------


def sstep_ghosts(indptr: np.ndarray, cols: np.ndarray, P_row: int, R: int,
                 s: int) -> list[tuple[np.ndarray, np.ndarray]]:
    """Per-shard depth-``s`` ghost zones of a position-space pattern.

    ``(indptr, cols)`` is a CSR pattern over the padded position space
    ``[0, P_row * R)`` (pad positions have empty rows). For each shard p
    a breadth-first search from its owned positions ``[p*R, (p+1)*R)``
    collects every position first reached at depth d ∈ [1, s] — exactly
    the reachability frontier of the pattern powers A^1 .. A^s, so the
    depth-d ghost set is a statistic of A^d alone. Returns, per shard,
    ``(gpos, gdep)``: ghost positions sorted ascending (≡ sorted by
    (owner, position) since owner = pos // R is monotone) and each
    ghost's BFS depth. Single source of truth for the builder
    (:func:`build_sstep_ell`) and the planner's χ(A^s) statistics
    (``planner.comm_plan(sstep=...)``) — which is what keeps the s-step
    byte prediction exact.
    """
    from ..matrices.sparse import gather_row_entry_idx

    indptr = np.asarray(indptr, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    D_pos = P_row * R
    assert len(indptr) == D_pos + 1, "pattern must cover the padded space"
    out = []
    for p in range(P_row):
        seen = np.zeros(D_pos, dtype=bool)
        seen[p * R:(p + 1) * R] = True
        frontier = np.arange(p * R, (p + 1) * R, dtype=np.int64)
        gpos_parts: list[np.ndarray] = []
        gdep_parts: list[np.ndarray] = []
        for d in range(1, s + 1):
            if not frontier.size:
                break
            gather, _ = gather_row_entry_idx(indptr, frontier)
            nxt = np.unique(cols[gather])
            new = nxt[~seen[nxt]]
            if not new.size:
                break
            seen[new] = True
            gpos_parts.append(new)
            gdep_parts.append(np.full(new.size, d, dtype=np.int64))
            frontier = new
        if gpos_parts:
            gpos = np.concatenate(gpos_parts)
            gdep = np.concatenate(gdep_parts)
            order = np.argsort(gpos, kind="stable")
            gpos, gdep = gpos[order], gdep[order]
        else:
            gpos = np.zeros(0, dtype=np.int64)
            gdep = np.zeros(0, dtype=np.int64)
        out.append((gpos, gdep))
    return out


@dataclasses.dataclass
class SstepNeighbor:
    """Compressed-engine schedule of the depth-s ghost exchange.

    Same permutation rounds as :class:`NeighborPlan` (both come from
    :func:`neighbor_schedule`, here applied to the depth-s pair-volume
    matrix); ``gather`` maps ghost slot j of each shard into the compact
    round-concatenated receive buffer (``off_by_pair[owner_j] +
    rank_within_pair_j``), so the gathered ghost block is identical to
    the a2a engine's — the per-step ELLs are comm-engine independent.
    """

    perms: tuple[tuple[tuple[int, int], ...], ...]
    round_L: tuple[int, ...]
    send_nbr: np.ndarray  # [P, max(H, 1)] int32, round-major send slots
    gather: np.ndarray    # [P, G] int32 into the compact [H] buffer

    @property
    def H(self) -> int:
        return int(sum(self.round_L))


@dataclasses.dataclass
class SstepEll:
    """Depth-s ghost-zone operator: per-step ELL blocks + one exchange plan.

    Shard p's extended address space is ``[0, R + G)``: owned rows at
    their local offsets, ghost j (of the ascending-position ghost list)
    at address ``R + j`` (``G`` is the max ghost count over shards; pad
    slots beyond a shard's own ghost count are never referenced). Step i
    of a group (0-indexed) holds the ELL rows whose outputs are still
    needed — owned rows plus ghosts at BFS depth ≤ s-1-i; deeper ghost
    rows are all-zero rows. Every row's entries are sorted by
    ``(owner(col) != owner(row), owner(col), position(col))`` — for
    owned rows that reproduces :class:`DistEll`'s slot order exactly,
    and for ghost rows it reproduces the order of the row's HOME shard,
    so each recurrence step accumulates in the same order everywhere and
    the s-step engines agree bit-for-bit with the s=1 engines.

    ``steps[i] = (cols, vals)`` with shapes [P, R+G, W_i]; the exchange
    plan (``send_idx``/``pair_counts``/``gather_a2a``) covers the full
    depth-s ghost set, so one exchange feeds all s recurrence steps of a
    group. ``ghost_cum[d]`` is the max-over-shards count of ghosts at
    depth ≤ d (the planner's redundant-work statistic).
    """

    steps: tuple  # s x (cols [P, R+G, W_i] int32, vals [P, R+G, W_i])
    send_idx: jax.Array    # [P, P, L] int32 local rows to ship (depth-s)
    gather_a2a: jax.Array  # [P, G] int32 into the padded [P*L] a2a buffer
    R: int
    G: int
    L: int
    P: int
    D: int
    s: int
    n_vc: np.ndarray | None = None          # per-shard ghost counts
    pair_counts: np.ndarray | None = None   # [P, P] depth-s volumes L_qp
    ghost_cum: tuple | None = None          # [s+1] max ghosts at depth <= d
    ghost_owner: np.ndarray | None = None   # [P, G] host: owner of ghost j
    ghost_rank: np.ndarray | None = None    # [P, G] host: rank within pair
    cols_loc: np.ndarray | None = None  # [P, R, W_loc] step-0 local prefix
    vals_loc: np.ndarray | None = None
    cols_post: np.ndarray | None = None  # [P, R+G, W_post] step-0 remainder
    vals_post: np.ndarray | None = None
    nbr: dict | None = None  # schedule name -> SstepNeighbor (cached)
    rowmap: RowMap | None = None

    def n_groups(self, degree: int) -> int:
        """ceil(degree / s) exchanges for a degree-term filter."""
        return -(-int(degree) // self.s)

    def split(self):
        """Step-0 split for the overlap engine: ``(cols_loc, vals_loc)``
        is the owned rows' local-address prefix (contracted while the
        exchange is in flight), ``(cols_post, vals_post)`` the owned
        rows' ghost-address suffix plus the full ghost rows — contracted
        against the extended vector afterwards, threading the
        accumulator, so the per-row summand order is unchanged."""
        if self.cols_loc is not None:
            return self.cols_loc, self.vals_loc, self.cols_post, self.vals_post
        cols = np.asarray(self.steps[0][0])
        vals = np.asarray(self.steps[0][1])
        Pn, RG, W = cols.shape
        R = self.R
        stored = vals != 0
        own_row = np.zeros((Pn, RG, 1), dtype=bool)
        own_row[:, :R, :] = True
        pre = stored & own_row & (cols < R)
        post = stored & ~pre
        W_loc = max(int(pre.sum(axis=2).max()) if W else 0, 1)
        W_post = int(post.sum(axis=2).max()) if W else 0
        cols_loc = np.zeros((Pn, R, W_loc), dtype=np.int32)
        vals_loc = np.zeros((Pn, R, W_loc), dtype=vals.dtype)
        cols_post = np.zeros((Pn, RG, W_post), dtype=np.int32)
        vals_post = np.zeros((Pn, RG, W_post), dtype=vals.dtype)
        for p in range(Pn):
            for mask, carr, varr, nrows in (
                (pre[p, :R], cols_loc[p], vals_loc[p], R),
                (post[p], cols_post[p], vals_post[p], RG),
            ):
                rows_, slots = np.nonzero(mask)
                if not len(rows_):
                    continue
                counts = np.bincount(rows_, minlength=nrows)
                out_slot = np.arange(len(rows_)) - np.repeat(
                    np.cumsum(counts) - counts, counts)
                carr[rows_, out_slot] = cols[p, :nrows][rows_, slots]
                varr[rows_, out_slot] = vals[p, :nrows][rows_, slots]
        # cached as HOST arrays: split() may first run inside a jit trace
        # (the group builders are lazy), and caching device arrays made
        # under a trace would leak tracers into later traces.
        self.cols_loc = cols_loc
        self.vals_loc = vals_loc
        self.cols_post = cols_post
        self.vals_post = vals_post
        return self.cols_loc, self.vals_loc, self.cols_post, self.vals_post

    def neighbor_plan(self, schedule: str = "cyclic") -> SstepNeighbor:
        """Compressed-engine rounds over the depth-s pair volumes; cached
        per scheduler. The ghost gather indexes the compact buffer at
        each scheduled pair's round offset, so the gathered block equals
        the a2a engine's bit-for-bit."""
        if self.nbr is None:
            self.nbr = {}
        plan = self.nbr.get(schedule)
        if plan is not None:
            return plan
        if self.pair_counts is None:
            raise ValueError("compressed s-step engine needs per-pair "
                             "volumes (pair_counts=None)")
        perms, round_L = neighbor_schedule(self.pair_counts, schedule)
        off_by_pair = np.full((self.P, self.P), -1, dtype=np.int64)
        H = 0
        for perm, Lk in zip(perms, round_L):
            for src, dst in perm:
                off_by_pair[src, dst] = H
            H += Lk
        send_idx = np.asarray(self.send_idx)
        send_nbr = np.zeros((self.P, max(H, 1)), dtype=np.int32)
        off = 0
        for perm, Lk in zip(perms, round_L):
            for src, dst in perm:
                send_nbr[src, off:off + Lk] = send_idx[src, dst, :Lk]
            off += Lk
        gather = np.zeros((self.P, self.G), dtype=np.int32)
        for p in range(self.P):
            ng = int(self.n_vc[p])
            if ng:
                own = self.ghost_owner[p, :ng]
                offg = off_by_pair[own, p]
                assert (offg >= 0).all(), "ghost with unscheduled sender"
                gather[p, :ng] = (offg + self.ghost_rank[p, :ng]
                                  ).astype(np.int32)
        plan = SstepNeighbor(perms=perms, round_L=round_L,
                             send_nbr=send_nbr, gather=gather)
        self.nbr[schedule] = plan
        return plan

    def as_dist_ell(self) -> DistEll:
        """s=1 round trip: the depth-1 ghost operator re-expressed in
        :class:`DistEll`'s halo addressing (``R + owner*L + rank``) —
        bit-identical to ``build_dist_ell`` by construction (same per-row
        slot order, same send plan, same widths)."""
        if self.s != 1:
            raise ValueError("as_dist_ell requires s == 1")
        cols = np.array(np.asarray(self.steps[0][0])[:, :self.R, :],
                        dtype=np.int32)
        vals = np.asarray(self.steps[0][1])[:, :self.R, :]
        for p in range(self.P):
            m = cols[p] >= self.R
            if m.any():
                j = cols[p][m] - self.R
                cols[p][m] = (self.R + self.ghost_owner[p, j] * self.L
                              + self.ghost_rank[p, j]).astype(np.int32)
        return DistEll(cols=jnp.asarray(cols), vals=jnp.asarray(vals),
                       send_idx=self.send_idx, R=self.R, L=self.L,
                       P=self.P, D=self.D, n_vc=self.n_vc,
                       pair_counts=self.pair_counts, rowmap=self.rowmap)


def build_sstep_ell(
    matrix: MatrixFamily | CSR,
    P_row: int,
    sstep: int,
    dtype=None,
    d_pad: int | None = None,
    split_halo: bool = False,
    rowmap: RowMap | None = None,
) -> SstepEll:
    """Build the depth-``sstep`` ghost-zone operator for P_row shards.

    BFS over the pattern from each shard's rows collects the depth-s
    ghost set (:func:`sstep_ghosts`); the exchange plan ships it in ONE
    collective per group of s recurrence steps, and per-step ELL blocks
    over the extended address space ``[0, R + G)`` apply the operator to
    owned + still-needed ghost rows. ``sstep=1`` reproduces today's
    :class:`DistEll` bit-exactly (see :meth:`SstepEll.as_dist_ell`).
    Accepts the same ``rowmap`` planned decompositions as
    ``build_dist_ell`` — the BFS runs in position space.
    """
    s = int(sstep)
    if s < 1:
        raise ValueError(f"sstep must be >= 1 (got {sstep})")
    D = matrix.shape[0] if isinstance(matrix, CSR) else matrix.D
    pos = None
    if rowmap is not None:
        if rowmap.D != D:
            raise ValueError("rowmap.D does not match the matrix")
        if d_pad is not None and d_pad != rowmap.D_pad:
            raise ValueError(f"d_pad={d_pad} conflicts with the rowmap's "
                             f"D_pad={rowmap.D_pad}")
        if rowmap.identity:
            R = Partition(D, P_row, rowmap.D_pad).R
        else:
            R = rowmap.level_R(P_row)
            pos = rowmap.pos
    else:
        R = Partition(D, P_row, d_pad).R
    D_pos = P_row * R

    if isinstance(matrix, CSR):
        rows, cols, vals = _csr_rows(matrix, 0, D)
    else:
        rows, cols, vals = matrix.row_entries(np.arange(D, dtype=np.int64))
        rows = np.asarray(rows, dtype=np.int64)
        cols = np.asarray(cols, dtype=np.int64)
        vals = np.asarray(vals)
    if pos is not None:
        rows = pos[rows]
        cols = pos[cols]
    # stable (position-row, position-col) sort: duplicate entries keep
    # their fetch order, exactly like build_dist_ell's per-shard lexsort
    order = np.lexsort((cols, rows))
    rows, cols, vals = rows[order], cols[order], vals[order]
    indptr = np.zeros(D_pos + 1, dtype=np.int64)
    indptr[1:] = np.cumsum(np.bincount(rows, minlength=D_pos))

    ghosts = sstep_ghosts(indptr, cols, P_row, R, s)
    n_vc = np.array([g.size for g, _ in ghosts], dtype=np.int64)
    G = int(n_vc.max()) if len(n_vc) else 0

    # depth-s exchange plan: true per-pair volumes, within-pair slots in
    # ascending position order (= DistEll's need-set order at s=1)
    pair_counts = np.zeros((P_row, P_row), dtype=np.int64)
    for p, (gpos, _) in enumerate(ghosts):
        if gpos.size:
            pair_counts[:, p] = np.bincount(gpos // R, minlength=P_row)
    L = int(pair_counts.max()) if pair_counts.size else 0
    send_idx = np.zeros((P_row, P_row, L), dtype=np.int32)
    ghost_owner = np.zeros((P_row, G), dtype=np.int64)
    ghost_rank = np.zeros((P_row, G), dtype=np.int64)
    for p, (gpos, _) in enumerate(ghosts):
        if not gpos.size:
            continue
        own = gpos // R
        starts = np.searchsorted(own, np.arange(P_row))
        rank = np.arange(gpos.size) - starts[own]
        for q in np.unique(own):
            m = own == q
            send_idx[int(q), p, :int(m.sum())] = (gpos[m] - int(q) * R
                                                  ).astype(np.int32)
        ghost_owner[p, :gpos.size] = own
        ghost_rank[p, :gpos.size] = rank
    gather_a2a = (ghost_owner * L + ghost_rank).astype(np.int32)

    cum = np.zeros((max(P_row, 1), s + 1), dtype=np.int64)
    for p, (_, gdep) in enumerate(ghosts):
        for d in range(1, s + 1):
            cum[p, d] = int((gdep <= d).sum())
    ghost_cum = tuple(int(v) for v in cum.max(axis=0))

    # per-shard entry lists for every row that is an OUTPUT of some step
    # (owned rows + ghosts at depth <= s-1), sorted per row by the
    # universal (owner != row_owner, owner, position) key
    from ..matrices.sparse import gather_row_entry_idx

    shard_data = []
    for p, (gpos, gdep) in enumerate(ghosts):
        inc = gdep <= s - 1
        inc_pos = np.concatenate([np.arange(p * R, (p + 1) * R,
                                            dtype=np.int64), gpos[inc]])
        inc_ext = np.concatenate([np.arange(R, dtype=np.int64),
                                  R + np.nonzero(inc)[0]])
        inc_owner = np.concatenate([np.full(R, p, dtype=np.int64),
                                    gpos[inc] // R])
        inc_depth = np.concatenate([np.zeros(R, dtype=np.int64), gdep[inc]])
        gather, counts = gather_row_entry_idx(indptr, inc_pos)
        e_cols = cols[gather]
        e_vals = vals[gather]
        e_row = np.repeat(inc_ext, counts)
        e_rowner = np.repeat(inc_owner, counts)
        e_depth = np.repeat(inc_depth, counts)
        e_own = e_cols // R
        local_m = e_own == p
        e_addr = np.empty(e_cols.size, dtype=np.int64)
        e_addr[local_m] = e_cols[local_m] - p * R
        if (~local_m).any():
            rc = e_cols[~local_m]
            idx = np.searchsorted(gpos, rc)
            ok = (idx < gpos.size) & (gpos[np.minimum(idx, max(gpos.size - 1,
                                                               0))] == rc)
            if not ok.all():
                raise AssertionError("s-step BFS closure violated: an "
                                     "output row references a position "
                                     "outside the depth-s ghost zone")
            e_addr[~local_m] = R + idx
        remote_flag = (e_own != e_rowner).astype(np.int64)
        e_order = np.lexsort((e_cols, e_own, remote_flag, e_row))
        e_row = e_row[e_order]
        e_addr = e_addr[e_order]
        e_vals = e_vals[e_order]
        e_depth = e_depth[e_order]
        rcounts = np.bincount(e_row, minlength=R + G)
        slot = np.arange(e_row.size) - np.repeat(
            np.cumsum(rcounts) - rcounts, rcounts)
        shard_data.append((e_row, e_addr, e_vals, e_depth, slot))

    vdtype = np.dtype(dtype) if dtype is not None else vals.dtype
    steps = []
    for i in range(s):
        lim = s - 1 - i
        W_i = 0
        for e_row, e_addr, e_vals, e_depth, slot in shard_data:
            m = e_depth <= lim
            if m.any():
                W_i = max(W_i, int(slot[m].max()) + 1)
        ci = np.zeros((P_row, R + G, W_i), dtype=np.int32)
        vi = np.zeros((P_row, R + G, W_i), dtype=vdtype)
        for p, (e_row, e_addr, e_vals, e_depth, slot) in enumerate(
                shard_data):
            m = e_depth <= lim
            ci[p, e_row[m], slot[m]] = e_addr[m]
            vi[p, e_row[m], slot[m]] = e_vals[m].astype(vdtype)
        steps.append((jnp.asarray(ci), jnp.asarray(vi)))

    sell = SstepEll(
        steps=tuple(steps),
        send_idx=jnp.asarray(send_idx),
        gather_a2a=jnp.asarray(gather_a2a),
        R=R, G=G, L=L, P=P_row, D=D, s=s,
        n_vc=n_vc,
        pair_counts=pair_counts,
        ghost_cum=ghost_cum,
        ghost_owner=ghost_owner,
        ghost_rank=ghost_rank,
        rowmap=rowmap,
    )
    if split_halo:
        sell.split()
    return sell


def _build_sstep_group(mesh: Mesh, layout: Layout, sell: SstepEll, *,
                       n_steps: int, first: bool, use_kernel: bool,
                       overlap: bool, comm: str, schedule: str):
    """One fused s-step GROUP: a single depth-s ghost exchange followed by
    ``n_steps`` three-term recurrence steps applied on the extended block.

    The first group ships only ``V`` (the recurrence seeds off one
    vector); later groups ship ``[w1 | w2]`` width-doubled in the same
    collective, so a degree-n filter runs ⌈n/s⌉ exchanges total. Step i
    contracts the step-i ELL (outputs valid at depth ≤ s-1-i), applies
    the same fused epilogue expression as the s=1 engines, and shifts
    the recurrence carries — all inside one shard_map body. The owned
    slices of the step outputs come back STACKED (``[n_steps, R, nb]``
    per shard) so the μ-accumulation runs in the caller's main graph
    with exactly the same op tree as :func:`chebyshev_filter` — keeping
    XLA's fused-multiply-add formation, and therefore the bits,
    identical to the s=1 engines. With ``overlap=True`` the
    exchange is launched first and step 0's local prefix contracts while
    the ghost bytes fly (steps >= 1 have a data dependence on the ghosts
    and cannot overlap anything). With ``use_kernel=True`` step 0's
    block dispatches to the Pallas ``ell_gather`` tile kernel.
    """
    _validate_engine(comm, schedule)
    dist = layout.dist_axes
    vec_spec = layout.vec_pspec()

    def pspec(a):
        return P(dist if dist else None, *((None,) * (a.ndim - 1)))

    kops = None
    if use_kernel:
        from ..kernels import ops as kops_mod

        kops = kops_mod

    R, G = sell.R, sell.G
    has_halo = sell.P > 1 and G > 0
    nbrp = sell.neighbor_plan(schedule) if comm == "compressed" else None
    if comm == "compressed":
        ex_args = [nbrp.send_nbr, nbrp.gather]
    else:
        ex_args = [sell.send_idx, sell.gather_a2a]

    later = [a for cv in sell.steps[1:n_steps] for a in cv]
    if overlap:
        cl, vl, cpost, vpost = sell.split()
        tiles_plan = kops.plan_ell_tiles(cl, vl, R) if use_kernel else None
        step0 = [cl, vl, cpost, vpost]
    else:
        c0, v0 = sell.steps[0]
        tiles_plan = (kops.plan_ell_tiles(c0, v0, R + G)
                      if use_kernel else None)
        step0 = [c0, v0]
    tile_args = list(tiles_plan.arrays()) if tiles_plan else []
    args = ex_args + step0 + later + tile_args
    n_ex = len(ex_args)
    n0 = n_ex + len(step0)
    n_later = 2 * (n_steps - 1)
    n_args = len(args)

    def group_dev(w1, w2, a, b, dev):
        ex = dev[:n_ex]
        sarrs = dev[n_ex:n0]
        later_arrs = dev[n0:n0 + n_later]
        tiles = _dev_tiles(tiles_plan, dev[n0 + n_later:])
        nb = w1.shape[1]
        adt = jnp.result_type(sarrs[1].dtype, w1.dtype)
        payload = w1 if first else jnp.concatenate([w1, w2], axis=1)
        if has_halo:
            if comm == "compressed":
                send_nbr, gather = ex
                buf = _halo_exchange_nbr(payload, send_nbr, dist,
                                         nbrp.perms, nbrp.round_L)
            else:
                send_idx, gather = ex
                buf = _halo_a2a(payload, send_idx, dist, sell.P, sell.L)

        def take_ghosts():
            if has_halo:
                return jnp.take(buf, gather, axis=0)  # [G, payload width]
            return jnp.zeros((G, payload.shape[1]), dtype=payload.dtype)

        if overlap:
            cl_, vl_, cpost_, vpost_ = sarrs
            # local prefix contracts while the ghost exchange is in flight
            y_pre = _contract_block(jnp.zeros((R, nb), dtype=adt),
                                    cl_, vl_, w1, tiles)
            ghosts = take_ghosts()
            w1e = jnp.concatenate([w1, ghosts[:, :nb]], axis=0)
            w2e = (None if first
                   else jnp.concatenate([w2, ghosts[:, nb:]], axis=0))
            y = jnp.concatenate([y_pre, jnp.zeros((G, nb), dtype=adt)],
                                axis=0)
            if cpost_.shape[1]:
                y = _ell_contract(y, cpost_, vpost_, w1e)
        else:
            c0_, v0_ = sarrs
            ghosts = take_ghosts()
            w1e = jnp.concatenate([w1, ghosts[:, :nb]], axis=0)
            w2e = (None if first
                   else jnp.concatenate([w2, ghosts[:, nb:]], axis=0))
            y = _contract_block(jnp.zeros((R + G, nb), dtype=adt),
                                c0_, v0_, w1e, tiles)

        ts = []
        for i in range(n_steps):
            if i:
                ci, vi = later_arrs[2 * (i - 1)], later_arrs[2 * i - 1]
                y = _ell_contract(jnp.zeros((R + G, nb), dtype=adt),
                                  ci, vi, w1e)
            with jax.named_scope("fd.cheb_update"):
                if first and i == 0:
                    t = a * y + b * w1e
                else:
                    t = 2.0 * a * y + 2.0 * b * w1e - w2e
            ts.append(t[:R])
            w2e, w1e = w1e, t
        return jnp.stack(ts), w1e[:R], w2e[:R]

    plan_specs = tuple(pspec(a) for a in args)
    vec_in = (vec_spec,) if first else (vec_spec, vec_spec)
    stk_spec = P(None, *tuple(vec_spec))

    def local_fn(*ins):
        dev = [a[0] for a in ins[:n_args]]
        if first:
            w1, a, b = ins[n_args:]
            return group_dev(w1, None, a, b, dev)
        w1, w2, a, b = ins[n_args:]
        return group_dev(w1, w2, a, b, dev)

    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=plan_specs + vec_in + (P(), P()),
        out_specs=(stk_spec, vec_spec, vec_spec),
        check_vma=False,
    )

    if first:
        def group1(V, alpha, beta):
            rdt = jnp.zeros((), dtype=V.dtype).real.dtype
            a = jnp.asarray(alpha, dtype=rdt)
            b = jnp.asarray(beta, dtype=rdt)
            return fn(*args, V, a, b)

        return group1

    def group(w1, w2, alpha, beta):
        rdt = jnp.zeros((), dtype=w1.dtype).real.dtype
        a = jnp.asarray(alpha, dtype=rdt)
        b = jnp.asarray(beta, dtype=rdt)
        return fn(*args, w1, w2, a, b)

    return group


def make_sstep_cheb(mesh: Mesh, layout: Layout, sell: SstepEll, *,
                    use_kernel: bool = False, overlap: bool = False,
                    comm: str = "a2a", schedule: str = "cyclic"):
    """Communication-avoiding Chebyshev filter application (seventh engine
    axis, ``spmv_sstep = sell.s``): ``apply(V, mu, alpha, beta)`` runs
    the whole degree-n filter in ⌈n/s⌉ depth-s ghost exchanges — s
    three-term recurrence steps per exchange — instead of n per-SpMV
    halo exchanges. Composes with the comm engine (``a2a`` /
    ``compressed`` + scheduler), the overlap split of step 0, and the
    Pallas tile kernel, and agrees bit-for-bit with every s=1 engine.
    ``s == 1`` callers should use :func:`make_fused_cheb_step` /
    :func:`make_spmv` (one exchange per step IS the s=1 engine)."""
    from .chebyshev import chebyshev_filter_sstep

    if sell.s < 2:
        raise ValueError("make_sstep_cheb requires s >= 2; the s=1 axis "
                         "point is the existing make_spmv engine grid")
    cache: dict = {}

    def factory(n_steps: int, first: bool):
        key = (int(n_steps), bool(first))
        if key not in cache:
            cache[key] = _build_sstep_group(
                mesh, layout, sell, n_steps=key[0], first=key[1],
                use_kernel=use_kernel, overlap=overlap, comm=comm,
                schedule=schedule)
        return cache[key]

    def apply(V, mu, alpha, beta):
        return chebyshev_filter_sstep(factory, mu, alpha, beta, V, sell.s)

    return apply
