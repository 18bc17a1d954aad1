"""Dispatch layer for the Pallas kernels.

``prefer_pallas()`` is True only on TPU backends. Off TPU the kernels
run in ``interpret=True`` mode, and the jnp reference stands in where a
block decomposition does not exist. On the hardware path
(``interpret=False``) a requested kernel runs or raises
:class:`KernelRefused`: 64-bit operands (``float64``, ``complex128``) have
no TPU vector type and are refused before Mosaic sees them, and so are
shapes no kernel block divides. Complex DIA matrices are decomposed into
real/imaginary planes (4 real kernel calls) since TPU VREGs have no
complex type; the ref-vs-kernel decision is made ONCE, before the
decomposition, so a CPU fallback runs one complex reference call instead
of four real ones.

Two host-side planners feed the distributed engine (``core/spmv.py``):

* :func:`plan_ell_tiles` re-buckets a stacked ELL block into the
  (row-block x col-block) tile format of ``ell_gather.py`` at operator
  build time — tiles can only be built from *concrete* host arrays, so
  the planner returns ``None`` on traced/abstract operands (e.g. the
  dryrun surrogate operator) and the engine keeps the jnp scan path.
  The tile kernel does not compile for TPU (its in-kernel row gather has
  no Mosaic lowering), so on TPU the planner raises
  :class:`KernelRefused` instead.
* :func:`plan_dia` extracts a DIA (offset, diagonal-values) form of a
  zero-halo local block for the fused ``cheb_dia`` Chebyshev kernel,
  with offsets sorted ascending so the per-row accumulation order equals
  the ELL slot order (ascending column) bit-for-bit.

All kernel entry points thread an explicit accumulator (``y0``) so the
per-output-element floating-point addition chain is identical to the
``lax.scan`` reference — the engines' twelve-way bit-identity grid
depends on it.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np
import jax
import jax.numpy as jnp

from . import ref
from .cheb_dia import cheb_dia as _cheb_dia_kernel
from .ell_gather import build_tiles, ell_gather_spmv

#: Lane-block candidates for the vector count: the hardware path needs a
#: multiple of 128 lanes; interpret mode accepts any divisor.
HW_BN = (256, 128)
INTERPRET_BN = (256, 128, 64, 32, 16, 8, 4, 2, 1)

#: Row-block candidates for the tile kernel (first divisor of R wins).
ELL_BR_CANDIDATES = (256, 128, 64, 32, 16, 8)

#: x rows resident per VMEM column block of the tile kernel.
ELL_BC = 512

#: Max distinct diagonal offsets before plan_dia refuses (the DIA form
#: stores n_diag * R values; past a few dozen diagonals the gather-free
#: format stops paying for itself).
DIA_MAX_DIAGS = 64


#: Operand dtypes the hardware path refuses: Mosaic has no 64-bit float
#: vector type (complex64 runs as float32 planes).
HW_REFUSED_DTYPES = (np.dtype("float64"), np.dtype("complex128"))


class KernelRefused(ValueError):
    """An explicitly requested Pallas kernel cannot run these operands on
    the TPU. Raised instead of substituting the jnp reference."""


def prefer_pallas() -> bool:
    return jax.default_backend() == "tpu"


#: The TPU compiler's words on the ell_gather tile kernel (v5e, JAX
#: 0.9.0): the per-slot column slice and the sublane gather it feeds
#: have no Mosaic lowering (docs/kernels.md).
ELL_GATHER_TPU_REFUSAL = ("Unimplemented primitive in Pallas TPU lowering "
                          "for KernelType.TC: dynamic_slice")


def require_tile_kernel():
    """Raise on TPU, where the ell_gather tile kernel does not compile."""
    if prefer_pallas():
        raise KernelRefused(
            "the ell_gather tile kernel does not compile for TPU ("
            f"{ELL_GATHER_TPU_REFUSAL}); run with spmv_kernel=False there")


def _check_hw_dtypes(*arrays):
    for a in arrays:
        if np.dtype(a.dtype) in HW_REFUSED_DTYPES:
            raise KernelRefused(
                f"{np.dtype(a.dtype)} operand: the TPU kernels take 32-bit "
                "operands only (use FDConfig(dtype='float32'))")


def is_concrete(a) -> bool:
    """True when ``a`` is a host-readable array (numpy, or a committed
    jax array) — i.e. NOT a tracer and NOT a ShapeDtypeStruct surrogate.
    The host-side planners require concrete operands; the engine falls
    back to the jnp path otherwise."""
    if isinstance(a, np.ndarray):
        return True
    return isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer)


# ------------------------------------------------------------------ ELL --


@dataclasses.dataclass(frozen=True)
class EllTilePlan:
    """Host-built tile batch of a stacked [P, R, W] ELL block.

    Arrays keep the leading shard axis so the engine can pass them
    through ``shard_map`` next to the block they were built from; the
    static block sizes travel with the plan (they parameterize the
    kernel grid)."""

    tile_cb: jax.Array  # [P, RB, T]
    tcols: jax.Array    # [P, RB, T, br, Wt]
    tvals: jax.Array    # [P, RB, T, br, Wt]
    br: int
    bc: int

    def arrays(self) -> tuple[jax.Array, jax.Array, jax.Array]:
        return (self.tile_cb, self.tcols, self.tvals)


def plan_ell_tiles(cols, vals, Rx: int, *, bc: int = ELL_BC,
                   br_candidates=ELL_BR_CANDIDATES) -> EllTilePlan | None:
    """Build the ell_gather tile batch for a stacked [P, R, W] ELL block.

    Raises :class:`KernelRefused` on TPU (:func:`require_tile_kernel`).
    Returns ``None`` (caller keeps the jnp scan path) when

    * the operands are not concrete host arrays (dryrun surrogates),
    * the value dtype is not real floating (the tile kernel is real-only),
    * no row-block candidate divides R, or the block is empty (W == 0).

    Per shard the tiles are built order-preserving (each entry goes to
    the earliest tile at-or-after its row's last-used tile with a
    matching column block), so the kernel's tile-major accumulation
    visits every stored entry in exactly the scan order — for ANY slot
    order, including the non-monotone re-based halo addresses of the
    compressed engines — and kernel-on == kernel-off bit-for-bit
    (padded slots add a bit-neutral ``+ 0.0``).
    """
    require_tile_kernel()
    if not (is_concrete(cols) and is_concrete(vals)):
        return None
    cols = np.asarray(cols)
    vals = np.asarray(vals)
    if not np.issubdtype(vals.dtype, np.floating):
        return None
    P, R, W = cols.shape
    if W == 0 or R == 0:
        return None
    br = _pick_block(R, br_candidates)
    if br is None:
        return None
    per_shard = [build_tiles(cols[p], vals[p], Rx, br, bc) for p in range(P)]
    T = max(tb.shape[1] for tb, _, _ in per_shard)
    Wt = max(tc.shape[3] for _, tc, _ in per_shard)
    RB = R // br
    tile_cb = np.zeros((P, RB, T), dtype=np.int32)
    tcols = np.zeros((P, RB, T, br, Wt), dtype=np.int32)
    tvals = np.zeros((P, RB, T, br, Wt), dtype=vals.dtype)
    for p, (tb, tc, tv) in enumerate(per_shard):
        tile_cb[p, :, : tb.shape[1]] = tb
        tcols[p, :, : tc.shape[1], :, : tc.shape[3]] = tc
        tvals[p, :, : tv.shape[1], :, : tv.shape[3]] = tv
    return EllTilePlan(tile_cb=jnp.asarray(tile_cb), tcols=jnp.asarray(tcols),
                       tvals=jnp.asarray(tvals), br=br, bc=bc)


def ell_spmv_tiled(tile_cb, tcols, tvals, x, y0=None, *, br: int, bc: int,
                   interpret=None):
    """Contract a per-device tile batch against ``x``, threading ``y0``.

    ``tile_cb [RB, T]`` / ``tcols``/``tvals [RB, T, br, Wt]`` are one
    shard's slice of an :class:`EllTilePlan`. The vector-block size bn is
    chosen at trace time from ``x.shape[1]``. Interpret mode always has
    bn=1 available; on the hardware path a vector count that no lane
    block (256, 128) divides, or a 64-bit operand, raises
    :class:`KernelRefused`.
    """
    interpret = (not prefer_pallas()) if interpret is None else interpret
    nb = x.shape[1]
    bn = _pick_block(nb, HW_BN if not interpret else INTERPRET_BN)
    if not interpret:
        _check_hw_dtypes(tvals, x)
        if bn is None:
            raise KernelRefused(f"no lane block of {HW_BN} divides the "
                                f"vector count nb={nb}")
    pad = (-x.shape[0]) % bc
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return ell_gather_spmv(tile_cb, tcols, tvals, x, y0,
                           br=br, bc=bc, bn=bn, interpret=interpret)


def ell_spmv(cols, vals, x):
    """Local ELL contraction (scan-of-gathers jnp reference). The Pallas
    tile kernel is opted in by the operator builder via
    :func:`plan_ell_tiles` + :func:`ell_spmv_tiled`; this entry point is
    the shared fallback body. Both comm engines dispatch here — the
    compressed (neighbor-permute) engine only re-bases column values into
    its compact halo buffer, so the same contraction body serves
    ``comm="a2a"`` and ``comm="compressed"``."""
    return ref.ell_spmv_ref(cols, vals, x)


def ell_spmv_split(cols_loc, vals_loc, cols_halo, vals_halo, x, halo):
    """Split-phase ELL contraction for the overlap engines.

    The local block never reads the halo buffer, so the caller can launch
    the halo exchange first and XLA overlaps it with the local contraction.
    The halo block gathers only from the (small) received buffer — on TPU
    it stays VMEM-resident, which is exactly the regime the ell_gather tile
    kernel wants (one column block, no re-bucketing). With the compressed
    engine the received buffer shrinks further (``Σ_k L_k`` instead of
    ``P·L`` rows); ``cols_halo`` then indexes that compact buffer."""
    return ref.ell_spmv_split_ref(cols_loc, vals_loc, cols_halo, vals_halo,
                                  x, halo)


# ------------------------------------------------------------------ DIA --


@dataclasses.dataclass(frozen=True)
class DiaPlan:
    """Host-extracted DIA form of a stacked zero-halo [P, R, W] local
    block: ``offsets`` sorted ascending (so the per-row accumulation
    order equals the ELL slot order), ``dvals[p, d, r]`` the value at
    (r, r + offsets[d]) of shard p (0 where the diagonal has no entry)."""

    offsets: tuple[int, ...]
    dvals: jax.Array  # [P, n_diag, R]


def plan_dia(cols, vals, R: int, *, max_diags: int = DIA_MAX_DIAGS
             ) -> DiaPlan | None:
    """Extract the DIA form of a stacked local ELL block, or ``None``.

    Refuses (caller keeps the ELL path) when the operands are not
    concrete, not real floating, reference columns outside ``[0, R)``
    (i.e. the block has halo entries), or need more than ``max_diags``
    distinct diagonals — the fused ``cheb_dia`` kernel is only dispatched
    for comm-free diagonal-structured operators.
    """
    if not (is_concrete(cols) and is_concrete(vals)):
        return None
    cols = np.asarray(cols)
    vals = np.asarray(vals)
    if not np.issubdtype(vals.dtype, np.floating):
        return None
    P, Rb, W = cols.shape
    if W == 0 or Rb != R:
        return None
    stored = vals != 0
    if not stored.any():
        return None
    if cols[stored].max() >= R:
        return None  # halo entries: not a comm-free local block
    rows = np.broadcast_to(np.arange(R)[None, :, None], cols.shape)
    offs = cols.astype(np.int64) - rows
    uniq = np.unique(offs[stored])
    if len(uniq) > max_diags:
        return None
    dvals = np.zeros((P, len(uniq), R), dtype=vals.dtype)
    dpos = {int(o): d for d, o in enumerate(uniq)}
    for p in range(P):
        rr, ww = np.nonzero(stored[p])
        for r, w in zip(rr, ww):
            dvals[p, dpos[int(offs[p, r, w])], r] = vals[p, r, w]
    return DiaPlan(offsets=tuple(int(o) for o in uniq),
                   dvals=jnp.asarray(dvals))


def cheb_dia(offsets, dvals, x, w1, w2, alpha, beta, *, interpret=None,
             force_ref=False):
    """Fused Chebyshev DIA step with real/complex dispatch.

    In interpret mode the kernel-vs-reference decision (``_too_small``,
    ragged R via ``_pick_block``, ``x.shape[0] % br``) is made ONCE up
    front; a complex operand that falls back therefore runs a single
    complex reference call, not four real-plane reference calls. On the
    hardware path those shapes, and 64-bit operands, raise
    :class:`KernelRefused`; only ``force_ref`` selects the reference.
    """
    interpret = (not prefer_pallas()) if interpret is None else interpret
    R, nb = w1.shape
    br = _pick_block(R, (512, 256, 128, 64, 32, 16, 8))
    bn = _pick_block(nb, HW_BN if not interpret else INTERPRET_BN)
    ragged = br is None or bn is None or x.shape[0] % br
    if force_ref or (interpret and (_too_small(dvals, w1) or ragged)):
        return ref.cheb_dia_ref(offsets, dvals, x, w1, w2, alpha, beta)
    if not interpret:
        _check_hw_dtypes(dvals, x, w1, w2)
        if ragged:
            raise KernelRefused(
                f"no (row, lane) block of the cheb_dia kernel fits R={R}, "
                f"nb={nb}, x rows={x.shape[0]}")
    call = functools.partial(_cheb_dia_kernel, tuple(int(o) for o in offsets),
                             br=br, bn=bn, interpret=interpret)
    if jnp.iscomplexobj(dvals) or jnp.iscomplexobj(x):
        dr, di = jnp.real(dvals), jnp.imag(dvals)
        xr, xi = jnp.real(x), jnp.imag(x)
        w1r, w1i = jnp.real(w1), jnp.imag(w1)
        w2r, w2i = jnp.real(w2), jnp.imag(w2)
        zeros = jnp.zeros_like(w1r)
        # (Ar + iAi)(xr + ixi): real = Ar xr - Ai xi ; imag = Ar xi + Ai xr
        yr = call(dr, xr, w1r, w2r, alpha, beta) - (
            call(di, xi, zeros, zeros, alpha, 0.0)
        )
        yi = call(dr, xi, w1i, w2i, alpha, beta) + (
            call(di, xr, zeros, zeros, alpha, 0.0)
        )
        return yr + 1j * yi
    return call(dvals, x, w1, w2, alpha, beta)


def _pick_block(n, candidates):
    for c in candidates:
        if n % c == 0:
            return c
    return None


def _too_small(dvals, w1) -> bool:
    return w1.shape[0] < 8 or w1.shape[1] < 1
