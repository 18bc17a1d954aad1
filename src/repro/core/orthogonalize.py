"""Communication-avoiding orthogonalization in the stack layout.

TSQR (Demmel et al. [11]): local QR per row shard, then a butterfly tree
over the horizontal axis — log2(P) ppermute rounds exchanging only the
small N_s x N_s R factors. Aggregate communication O(P log P * N_s^2),
independent of D (the paper's requirement for the stack layout).

SVQB (Stathopoulos & Wu [41]): Gram matrix via one all-reduce (the
MPI_Allreduce of the paper, volume P * N_s^2), then a replicated eigen-
decomposition. Cheaper but numerically weaker — the paper uses TSQR for
large N_s; we provide both.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .layouts import Layout

__all__ = ["make_tsqr", "make_svqb", "make_gram"]


def _flat_axis_index(mesh: Mesh, axes: tuple[str, ...]):
    """Linearized device index over the given mesh axes (row-major)."""
    idx = jnp.zeros((), jnp.int32)
    for a in axes:
        idx = idx * mesh.shape[a] + lax.axis_index(a)
    return idx


def _qr_fixed(M):
    Q, R = jnp.linalg.qr(M)
    d = jnp.diagonal(R)
    s = jnp.where(jnp.abs(d) > 0, d / jnp.abs(d), jnp.ones_like(d))
    return Q * jnp.conj(s)[None, :], R / s[:, None]


def make_tsqr(mesh: Mesh, layout: Layout):
    """tsqr(V) -> (Q, R) with V [D_pad, N_s] in the stack layout.

    Requires the horizontal process count to be a power of two (true for
    all production meshes); the butterfly leaves every shard with the same
    global R and its local Q block.
    """
    dist = layout.dist_axes
    P_row = layout.n_row(mesh)
    levels = int(math.log2(P_row)) if P_row > 1 else 0
    if 2**levels != P_row:
        raise ValueError(f"TSQR butterfly needs power-of-two shards, got {P_row}")
    vec_spec = layout.vec_pspec()

    def local_fn(Vb):
        with jax.named_scope("fd.local_qr"):
            Q0, R = _qr_fixed(Vb)  # local [R_loc, Ns] -> Q0 [R_loc, Ns], R [Ns, Ns]
        if not levels:
            return Q0, R
        with jax.named_scope("fd.butterfly"):
            idx = _flat_axis_index(mesh, dist)
            acc = None
            for lvl in range(levels):
                bit = 1 << lvl
                perm = [(i, i ^ bit) for i in range(P_row)]
                R_peer = lax.ppermute(R, dist, perm)
                am_lo = (idx & bit) == 0
                # stack in consistent (lo above hi) order on both partners
                A = jnp.where(am_lo,
                              jnp.concatenate([R, R_peer], axis=0),
                              jnp.concatenate([R_peer, R], axis=0))
                Qf, R = _qr_fixed(A)
                Ns = R.shape[0]
                mine = jnp.where(am_lo, 0, 1)
                Qblk = lax.dynamic_slice_in_dim(Qf, mine * Ns, Ns, axis=0)  # [Ns, Ns]
                acc = Qblk if acc is None else acc @ Qblk
            return Q0 @ acc, R

    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(vec_spec,),
        out_specs=(vec_spec, P()),
        check_vma=False,
    )
    return fn


def make_gram(mesh: Mesh, layout: Layout):
    """gram(V, W) = V^H W with one all-reduce over the horizontal axes."""
    dist = layout.dist_axes
    vec_spec = layout.vec_pspec()

    def local_fn(Vb, Wb):
        g = jnp.conj(Vb).T @ Wb
        return lax.psum(g, dist) if dist else g

    return shard_map(local_fn, mesh=mesh, in_specs=(vec_spec, vec_spec),
                     out_specs=P(), check_vma=False)


def make_svqb(mesh: Mesh, layout: Layout, eps: float = 1e-14):
    """svqb(V) -> orthonormal basis of span(V) (Gram + eigh, one allreduce)."""
    gram = make_gram(mesh, layout)

    def svqb(V):
        G = gram(V, V)
        d = jnp.real(jnp.diagonal(G))
        s = 1.0 / jnp.sqrt(jnp.maximum(d, eps))
        Gs = G * s[:, None] * s[None, :]
        w, U = jnp.linalg.eigh(Gs)
        w = jnp.maximum(jnp.real(w), eps * jnp.max(jnp.real(w)))
        T = (s[:, None] * U) / jnp.sqrt(w)[None, :]
        return V @ T.astype(V.dtype)

    return svqb
