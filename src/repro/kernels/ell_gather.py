"""Pallas TPU kernel: blocked ELL SpMMV with VMEM gather (irregular matrices).

For matrices that are not diagonal-structured (the Hubbard dn-sector hop
graph, SpinChainXXZ), the local contraction y[r] = Σ_w vals[r,w] x[cols[r,w]]
needs a gather. TPU adaptation: the gather must be VMEM-resident, so the
host pre-buckets each row block's entries by *column block* (tile format:
row-block x col-block ELL with tile-local columns). The kernel grid is
(row blocks, n_b blocks, tiles); each step loads one x column-block into
VMEM and gathers rows from it with `jnp.take` along the sublane axis.

The kernel runs in interpret mode only. It does not compile for TPU:
Mosaic lowers neither the per-slot ``dynamic_slice`` of the tile's
columns nor the sublane gather they feed (docs/kernels.md, "What the TPU
compiler accepts"), so on TPU ``ops.plan_ell_tiles`` refuses it with an
error instead of substituting the jnp scan.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BR = 256
DEFAULT_BC = 2048  # x rows per column block resident in VMEM
DEFAULT_BN = 128


def build_tiles(cols: np.ndarray, vals: np.ndarray, Rx: int, br: int, bc: int):
    """Re-bucket an ELL block [R, W] into (row-block x col-block) tiles.

    Returns (tile_cb [RB, T], tcols [RB, T, br, Wt], tvals [...]) where T is
    the padded tile count and Wt the padded per-tile width. Padded entries
    point at tile-local column 0 with value 0.

    The tiling is *order-preserving for arbitrary slot orders*: each entry
    is placed in the earliest tile at-or-after its row's previously used
    tile whose column block matches (a new tile is opened otherwise), so
    every row visits its tiles — and its slots inside each tile — in the
    original slot order. The kernel's tile-major accumulation therefore
    reproduces the jnp scan's per-element addition chain bit-for-bit even
    when a row's columns are not monotone in column block (e.g. the
    re-based halo addresses of the compressed engines).
    """
    R, W = cols.shape
    RB = R // br
    tiles: list[list[tuple[int, np.ndarray, np.ndarray]]] = []
    T = 1
    Wt = 1
    for rb in range(RB):
        c = cols[rb * br : (rb + 1) * br]
        v = vals[rb * br : (rb + 1) * br]
        nz = v != 0
        cb_of = c // bc
        tile_cbs: list[int] = []           # column block of each tile, in order
        entries: list[list[tuple[int, int]]] = []  # per tile: (row, slot)
        last_t = np.full(br, -1, dtype=np.int64)
        for w in range(W):
            for r in np.nonzero(nz[:, w])[0]:
                cb = int(cb_of[r, w])
                lo = max(int(last_t[r]), 0)
                for t in range(lo, len(tile_cbs)):
                    if tile_cbs[t] == cb:
                        break
                else:
                    t = len(tile_cbs)
                    tile_cbs.append(cb)
                    entries.append([])
                entries[t].append((int(r), int(w)))
                last_t[r] = t
        row_tiles = []
        for cb, ent in zip(tile_cbs, entries):
            counts = np.zeros(br, dtype=np.int64)
            for r, _ in ent:
                counts[r] += 1
            w_t = int(counts.max())
            tc = np.zeros((br, w_t), dtype=np.int32)
            tv = np.zeros((br, w_t), dtype=vals.dtype)
            fill = np.zeros(br, dtype=np.int64)
            for r, w in ent:
                tc[r, fill[r]] = c[r, w] - cb * bc
                tv[r, fill[r]] = v[r, w]
                fill[r] += 1
            row_tiles.append((cb, tc, tv))
            Wt = max(Wt, w_t)
        T = max(T, len(row_tiles))
        tiles.append(row_tiles)
    tile_cb = np.zeros((RB, T), dtype=np.int32)
    tcols = np.zeros((RB, T, br, Wt), dtype=np.int32)
    tvals = np.zeros((RB, T, br, Wt), dtype=vals.dtype)
    for rb, row_tiles in enumerate(tiles):
        for t, (cb, tc, tv) in enumerate(row_tiles):
            tile_cb[rb, t] = cb
            tcols[rb, t, :, : tc.shape[1]] = tc
            tvals[rb, t, :, : tv.shape[1]] = tv
    return tile_cb, tcols, tvals


def _kernel(tile_cb, tcols, tvals, xblk, y0blk, out, *, n_tiles):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        out[...] = y0blk[...]

    c = tcols[0, 0]  # [br, Wt] tile-local columns
    v = tvals[0, 0]
    xb = xblk[...]  # [bc, bn]

    # rolled slot loop, NOT an unrolled python loop: XLA compiles an
    # unrolled mul-add chain with FMA contraction (differently rounded),
    # while the rolled loop emits the same one-mul-one-add iteration body
    # as the engines' lax.scan — bit-identical accumulation
    def slot(w, acc):
        cw = jax.lax.dynamic_slice_in_dim(c, w, 1, axis=1)[:, 0]
        vw = jax.lax.dynamic_slice_in_dim(v, w, 1, axis=1)
        return acc + vw * jnp.take(xb, cw, axis=0)

    out[...] = jax.lax.fori_loop(0, c.shape[1], slot, out[...])


@functools.partial(jax.jit, static_argnames=("br", "bc", "bn", "interpret"))
def ell_gather_spmv(
    tile_cb: jax.Array,  # [RB, T] col-block index per tile (scalar prefetch)
    tcols: jax.Array,    # [RB, T, br, Wt]
    tvals: jax.Array,    # [RB, T, br, Wt]
    x: jax.Array,        # [Rx_pad, nb] (padded to multiple of bc)
    y0: jax.Array | None = None,  # [R, nb] accumulator threaded into the tiles
    br: int = DEFAULT_BR,
    bc: int = DEFAULT_BC,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
):
    """Tiled ELL contraction ``y0 + A @ x`` (``y0 = 0`` when omitted).

    The optional ``y0`` operand initializes each output block at tile 0,
    so a caller that has already accumulated (e.g. the split-phase local
    block before the halo block) THREADS its accumulator through the
    kernel: per output element the addition chain is y0, then the
    entries in the order-preserving tile sequence of :func:`build_tiles`
    — the same slot order as the jnp scan, which is what keeps kernel-on
    and kernel-off engines bit-identical.
    """
    RB, T, _, Wt = tcols.shape
    R = RB * br
    Rx, nb = x.shape
    assert Rx % bc == 0 and nb % bn == 0
    if y0 is None:
        y0 = jnp.zeros((R, nb), dtype=x.dtype)
    grid = (RB, nb // bn, T)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, br, Wt), lambda rb, cb, t, cbref: (rb, t, 0, 0)),
            pl.BlockSpec((1, 1, br, Wt), lambda rb, cb, t, cbref: (rb, t, 0, 0)),
            pl.BlockSpec((bc, bn), lambda rb, cb, t, cbref: (cbref[rb, t], cb)),
            pl.BlockSpec((br, bn), lambda rb, cb, t, cbref: (rb, cb)),
        ],
        out_specs=pl.BlockSpec((br, bn), lambda rb, cb, t, cbref: (rb, cb)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, n_tiles=T),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, nb), y0.dtype),
        interpret=interpret,
    )(tile_cb, tcols, tvals, x, y0)
