"""Pallas TPU kernel: fused Chebyshev step for DIA (diagonal-offset) SpMMV.

TPU adaptation of the paper's fused SpMV+axpy kernel (Alg. 2 step 7,
Kreutzer et al. [19]). On CPU the fused kernel exists to keep the vector
traffic factor at κ=5; on TPU we additionally re-think the *format*:

  * The flagship matrices (Exciton/TopIns stencils, and the paper's class
    of lattice Hamiltonians generally) are unions of a few dozen shifted
    diagonals. SELL-C-σ's row sorting serves CPU SIMD lanes; on TPU the
    lane dimension is the *vector block* (n_b >= 128 after padding), so
    gather-free shifted-diagonal FMAs on (8,128) VREG tiles are the
    natural format: every op is a static-stride VMEM load + FMA, the MXU
    is bypassed (SpMMV is bandwidth-bound) and the VPU streams at b_m.

  * Grid = (row blocks, n_b blocks, diagonals), accumulating over the
    innermost diagonal axis into the output block (whose index map is
    constant along that axis, so the block is revisited consecutively).
    The x operand is passed twice with diagonal-dependent index maps
    (aligned blocks k and k+1) so an unaligned offset is assembled from
    two aligned VMEM tiles with one dynamic sublane rotate
    (``pltpu.roll``: Mosaic lowers no ``dynamic_slice`` of a value) — no
    HBM gather exists on the critical path.

  * The diagonal values arrive transposed, as one [BR, n_diag] block per
    row block: the full diagonal axis is the lane dim, so the block
    meets Mosaic's (8, 128)-or-full-dim rule at any diagonal count. The
    grid step's diagonal is picked with a one-hot lane select and a lane
    sum, which is exact (every other lane adds 0).

  * The fused epilogue 2a*(A x) + 2b*w1 - w2 runs on the last diagonal,
    so W2 is read exactly once from HBM (κ = 5, not 6 — paper §3.2).

Block sizes: BR rows (multiple of 8 sublanes) x BN vector columns
(multiple of 128 lanes). VMEM footprint/step ≈ (2 x-tiles + w1 + w2 + out
+ rotate temp) * BR * BN * 4B + BR * 128 * 4B ≈ 3.3 MiB « 16 MiB at
512 x 256.

Complex matrices are handled in ops.py by splitting into real/imag DIA
planes (TPU has no native complex VREG type); this kernel is real-only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BR = 512
DEFAULT_BN = 256


def _kernel(off_blk, rot, ab, dvals, x0, x1, w1, w2, out):
    d = pl.program_id(2)
    br = out.shape[0]

    @pl.when(d == 0)
    def _init():
        out[...] = jnp.zeros_like(out)

    # xs = xx[shift : shift + br]: rotate left by shift, keep the head
    xx = jnp.concatenate([x0[...], x1[...]], axis=0)
    xs = pltpu.roll(xx, rot[d], 0)[:br]
    dvb = dvals[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, dvb.shape, 1)
    dv = jnp.sum(jnp.where(lane == d, dvb, 0), axis=1, keepdims=True)
    out[...] += dv * xs

    @pl.when(d == pl.num_programs(2) - 1)
    def _epilogue():
        a2 = 2.0 * ab[0]
        b2 = 2.0 * ab[1]
        out[...] = a2 * out[...] + b2 * w1[...] - w2[...]


@functools.partial(jax.jit, static_argnames=("offsets", "br", "bn", "interpret"))
def cheb_dia(
    offsets: tuple[int, ...],
    dvals: jax.Array,  # [n_diag, R] per-diagonal values (0 where invalid)
    x: jax.Array,      # [Rx, nb], Rx >= R (halo may be appended)
    w1: jax.Array,     # [R, nb]
    w2: jax.Array,     # [R, nb]
    alpha,
    beta,
    br: int = DEFAULT_BR,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
):
    """y = 2a*(A@x) + 2b*w1 - w2 for the DIA matrix given by (offsets, dvals).

    Rows where i + offset falls outside [0, Rx) must carry dvals == 0 (the
    host builder guarantees this); their x tiles are clamped loads whose
    contribution is multiplied by zero.
    """
    n_diag, R = dvals.shape
    Rx, nb = x.shape
    assert R % br == 0 and nb % bn == 0, (R, nb, br, bn)
    assert Rx % br == 0
    nxb = Rx // br
    off_blk = jnp.asarray([o // br for o in offsets], jnp.int32)
    # left rotation by the in-block shift o % br, as jnp.roll's amount
    rot = jnp.asarray([(2 * br - o % br) % (2 * br) for o in offsets],
                      jnp.int32)
    ab = jnp.stack([jnp.asarray(alpha, dvals.dtype),
                    jnp.asarray(beta, dvals.dtype)])

    grid = (R // br, nb // bn, n_diag)

    def x_map(k):  # k = 0 or 1: aligned block at floor(offset/br) + k, clamped
        def im(rb, cb, d, off_blk_ref, rot_ref, ab_ref):
            blk = rb + off_blk_ref[d] + k
            # explicit int32 bounds: under x64 jnp.clip's weak int64
            # bounds send Mosaic's convert lowering into a recursion
            blk = jax.lax.clamp(jnp.int32(0), blk, jnp.int32(nxb - 1))
            return blk, cb

        return im

    def vec_map(rb, cb, d, *_):
        return rb, cb

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            # dvals.T; int32 zero: under x64 a Python 0 lowers as i64
            pl.BlockSpec((br, n_diag),
                         lambda rb, cb, d, *_: (rb, jnp.int32(0))),
            pl.BlockSpec((br, bn), x_map(0)),
            pl.BlockSpec((br, bn), x_map(1)),
            pl.BlockSpec((br, bn), vec_map),  # w1
            pl.BlockSpec((br, bn), vec_map),  # w2
        ],
        out_specs=pl.BlockSpec((br, bn), vec_map),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, nb), w1.dtype),
        interpret=interpret,
    )(off_blk, rot, ab, dvals.T, x, x, w1, w2)
