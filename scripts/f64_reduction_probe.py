#!/usr/bin/env python
"""Accuracy of float64 reductions on the default JAX backend.

TPUs have no float64 unit; XLA emulates it. This probe measures how far
the emulated float64 reductions the solver leans on (dot, sum, norm, the
tall-skinny Gram V^T W of Rayleigh-Ritz and SVQB, a small ``eigh``) land
from an extended-precision host reference, on one device and, when the
backend has more than one device, as per-shard partial results combined
by a ``psum`` across all of them (the row-sharded layout's reduction).

    python scripts/f64_reduction_probe.py [--n 705432] [--cols 16]

Each line gives the error in units of the reduction's own scale: for a
dot sum|x_i y_i|, for a Gram entry the same over its two columns, for
``eigh`` the residual ||A U - U diag(w)|| over ||A||. IEEE float64
rounding keeps these near log2(n) * 1.1e-16 or below.
"""
from __future__ import annotations

import argparse

import numpy as np


def _rel(got, ref, scale):
    return float(np.max(np.abs(np.asarray(got, np.longdouble) - ref)
                        / scale))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=705_432)
    ap.add_argument("--cols", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    hi = jax.lax.Precision.HIGHEST
    print(f"[probe] platform={devs[0].platform} kind={devs[0].device_kind} "
          f"devices={len(devs)} n={args.n} cols={args.cols}", flush=True)

    rng = np.random.default_rng(args.seed)
    x, y = rng.standard_normal((2, args.n))
    X = rng.standard_normal((args.n, args.cols))
    xl, yl, Xl = (a.astype(np.longdouble) for a in (x, y, X))
    dot_ref = np.sum(xl * yl)
    dot_scale = np.sum(np.abs(xl * yl))
    nrm_ref = np.sqrt(np.sum(xl * xl))
    gram_ref = Xl.T @ Xl
    gram_scale = np.abs(Xl).T @ np.abs(Xl)

    def report(where, name, err):
        print(f"[probe] {where:>9} {name:<22} rel error {err:.3e}",
              flush=True)

    d0 = devs[0]
    xd, yd, Xd = (jax.device_put(a, d0) for a in (x, y, X))

    def highest(f):  # traced as the solver traces its dense algebra
        def g(*a):
            with jax.default_matmul_precision("highest"):
                return f(*a)
        return jax.jit(g)

    one = {
        "vdot": jax.jit(jnp.vdot)(xd, yd),
        "vdot highest": highest(jnp.vdot)(xd, yd),
        "sum(x*y)": jax.jit(lambda a, b: jnp.sum(a * b))(xd, yd),
        "sum(x*y) highest": highest(lambda a, b: jnp.sum(a * b))(xd, yd),
        "norm": jax.jit(jnp.linalg.norm)(xd),
        "gram highest": jax.jit(
            lambda A: jnp.matmul(A.T, A, precision=hi))(Xd),
        "gram default": jax.jit(lambda A: A.T @ A)(Xd),
    }
    for k in ("vdot", "vdot highest", "sum(x*y)", "sum(x*y) highest"):
        report("1 device", k, _rel(one[k], dot_ref, dot_scale))
    report("1 device", "norm", _rel(one["norm"], nrm_ref, nrm_ref))
    for k in ("gram highest", "gram default"):
        report("1 device", k, _rel(one[k], gram_ref, gram_scale))
    z = rng.standard_normal(args.n)
    fma = np.asarray(jax.jit(lambda a, b, c: a * b + c)(xd, yd,
                                                        jax.device_put(z, d0)))
    fma_ref = xl * yl + z.astype(np.longdouble)
    report("1 device", "x*y+z (elementwise)",
           _rel(fma, fma_ref, np.abs(xl * yl) + np.abs(z)))

    G = np.asarray(gram_ref, np.float64) / args.n
    w, U = jax.jit(jnp.linalg.eigh)(jax.device_put(G, d0))
    w, U = np.asarray(w), np.asarray(U)
    report("1 device", f"eigh {args.cols}x{args.cols}",
           float(np.linalg.norm(G @ U - U * w) / np.linalg.norm(G)))

    if len(devs) > 1:
        p = len(devs)
        n_even = args.n - args.n % p
        mesh = Mesh(np.array(devs), ("row",))
        sh = NamedSharding(mesh, P("row"))

        def shard(a):
            return jax.device_put(a[:n_even], sh)

        xs, ys, Xs = shard(x), shard(y), shard(X)
        dot = jax.jit(jax.shard_map(
            lambda a, b: jax.lax.psum(jnp.vdot(a, b), "row"), mesh=mesh,
            in_specs=(P("row"), P("row")), out_specs=P()))
        gram = jax.jit(jax.shard_map(
            lambda A: jax.lax.psum(jnp.matmul(A.T, A, precision=hi), "row"),
            mesh=mesh, in_specs=P("row"), out_specs=P()))
        xe, ye, Xe = xl[:n_even], yl[:n_even], Xl[:n_even]
        report(f"{p} shards", "vdot + psum",
               _rel(dot(xs, ys), np.sum(xe * ye), np.sum(np.abs(xe * ye))))
        report(f"{p} shards", "gram highest + psum",
               _rel(gram(Xs), Xe.T @ Xe, np.abs(Xe).T @ np.abs(Xe)))


if __name__ == "__main__":
    main()
