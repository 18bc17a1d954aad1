"""Smoke run of the filter-diagonalization eigensolver on TPU chips.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the horizontal layer on a 2x2 host

One chip runs, in order: the device check; a float64 solve through
``repro.launch.solve.solve`` checked against a dense host ``eigh``; a
complex64 solve of the exciton family checked the same way; the solver at
deployment size (D = 2,704,156, float64, N_s = 16) for two outer
iterations, with its SpMV checked against a host CSR matvec; and the
Pallas DIA kernel against the jnp engine. ``--chips 4`` runs only the
row-sharded (horizontal) layer on a 2x2 panel and a 4x1 stack, with both
halo-exchange engines, against one chip: the SpMV, the Lanczos interval
and a Chebyshev filter at D = 705,432, and converged FD solves at
D = 924 checked against a dense host ``eigh``.

Every phase raises on failure. The script exits non-zero, and prints no
result line, when JAX finds no TPU. The last line of stdout is one JSON
object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))


def device_phase(n_chips: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    print(f"[device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (backend "
                         f"{d.platform!r}); it does not run elsewhere")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: --chips {n_chips} needs {n_chips} "
                         f"devices, JAX sees {len(devs)}")
    return devs


def jit_engine():
    """jit(engine, *args): the engine is an argument, so its operator
    arrays are parameters of the program, not constants baked into it."""
    import jax

    return jax.jit(lambda engine, *args: engine(*args))


def nearest_eigs(ev, target, k):
    """The k values of ``ev`` closest to ``target``, ascending."""
    ev = np.asarray(ev)
    return np.sort(ev[np.argsort(np.abs(ev - target))[:k]])


def dense_eigs(mat):
    """Host float64/complex128 spectrum of the family's own CSR build."""
    return np.linalg.eigvalsh(mat.build_csr().to_dense())


def host_csr(mat):
    """The family's own CSR build as a scipy matrix (host float64)."""
    import scipy.sparse as sp

    c = mat.build_csr()
    return sp.csr_matrix((c.data, c.indices, c.indptr), shape=c.shape)


def eig_phase(label, family, params, fd, bound, why, target):
    """Solve through the CLI's ``solve()`` on a 1x1 mesh and compare the
    n_target eigenvalues nearest the target with the dense host spectrum."""
    from repro.launch.solve import solve
    from repro.matrices import get_family

    t0 = time.perf_counter()
    res = solve(family, params, fd, 1, 1, verbose=False)
    t_solve = time.perf_counter() - t0
    got = nearest_eigs(res.eigenvalues, target, fd.n_target)
    ref = nearest_eigs(dense_eigs(get_family(family, **params)), target,
                       fd.n_target)
    if len(got) < fd.n_target or res.n_converged < fd.n_target:
        raise RuntimeError(f"[{label}] {res.n_converged} of {fd.n_target} "
                           f"pairs converged in {res.iterations} iterations")
    err = float(np.abs(got - ref).max())
    print(f"[{label}] {family} {params} dtype={fd.dtype} D="
          f"{get_family(family, **params).D}: {res.n_converged} converged "
          f"in {res.iterations} iterations / {res.total_spmvs} SpMVs, "
          f"{t_solve:.3f} s with compiles", flush=True)
    print(f"[{label}] eigenvalues {np.array2string(got, precision=10)}")
    print(f"[{label}] max |lambda - lambda_ref| = {err:.3e} "
          f"(bound {bound:.1e}: {why})", flush=True)
    if not err <= bound:
        raise RuntimeError(f"[{label}] eigenvalue error {err:.3e} exceeds "
                           f"{bound:.1e}")


def correctness_phases():
    from repro.core import FDConfig

    tol = 1e-8
    eig_phase(
        "f64", "SpinChainXXZ", dict(n_sites=12, n_up=6),
        FDConfig(n_target=4, n_search=16, target=0.05, tol=tol,
                 max_iters=25),
        bound=tol, target=0.05,
        why="a Hermitian Ritz value lies within its residual norm "
            "(<= tol) of an eigenvalue")
    # complex64: the filtered basis floors at residuals near 1.2e-4 on
    # this operator's ground state (CPU rehearsal), so tol sits 4x above
    # that; a Ritz value's own rounding adds ~100*eps32*||A||, ||A|| ~ 12
    tol32 = 5e-4
    eps32 = float(np.finfo(np.float32).eps)
    eig_phase(
        "c64", "Exciton", dict(L=4),
        FDConfig(n_target=4, n_search=32, target=-2.0, tol=tol32,
                 max_iters=40, dtype="float32"),
        bound=tol32 + 100 * eps32 * 12.0, target=-2.0,
        why="the residual bound tol plus 100*eps32*||A|| of complex64 "
            "rounding in the Rayleigh quotient")


# Emulated float64 on one v5e, as scripts/f64_reduction_probe.py measured
# it (chip run; error relative to each operation's own scale): a*b+c
# elementwise 1.543e-14; a length-D dot or sum 1.4e-13 at most, a norm
# 5.4e-15; a tall-skinny matmul (the Gram V^T W) 5.605e-07 at D =
# 2,704,156 and 1.389e-07 at 705,432: XLA:TPU splits a float64 matmul
# into float32 passes, while a dot of two vectors stays near float64.
EPS_ELEM = 1.543e-14
EPS_MATMUL = 5.605e-07


def deployment_phase(clock, n_sites=24, n_search=16, target=-10.3,
                     outer=2):
    """SpinChainXXZ at D = C(24, 12) on one chip, float64, two outer
    iterations through the FilterDiag step API (the loop ``solve`` runs),
    timed per iteration with the compile seconds inside each.

    N_s = 16 because emulated float64 is what bounds one chip. At this D
    the chip's compiler refuses TSQR and Rayleigh-Ritz at N_s = 32 and 64
    ("Ran out of memory in memory space hbm. Used 41.91G of 15.75G hbm"
    for TSQR at 32): their float64 matmuls and QR keep float32[8, D, N_s]
    temporaries, padded to 128 lanes. At N_s = 16 TSQR compiles to 8.3 GB
    of temporaries. The target sits just above the ground state (about
    -10.45), where the spectrum is thin and the filter degree stays low.
    The HBM the programs need is printed from ``memory_analysis()``:
    ``peak_bytes_in_use`` counts the buffers the runtime allocates, not a
    program's temporaries."""
    import jax
    import jax.numpy as jnp

    from repro.core import FDConfig, FilterDiag, make_solver_mesh
    from repro.core.chebyshev import scale_params
    from repro.matrices import get_family

    mat = get_family("SpinChainXXZ", n_sites=n_sites, n_up=n_sites // 2)
    fd = FDConfig(n_target=8, n_search=n_search, target=target, tol=1e-8,
                  max_iters=outer)
    mesh = make_solver_mesh(1, 1)
    t0 = time.perf_counter()
    fdd = FilterDiag(mat, mesh, fd)
    t_build = time.perf_counter() - t0
    ell = fdd.ell_stack
    print(f"[deploy] SpinChainXXZ n_sites={n_sites} D={mat.D} "
          f"ELL {ell.R}x{ell.cols.shape[-1]} n_search={n_search} "
          f"dtype={fdd.dtype} target={target}: host build {t_build:.3f} s",
          flush=True)

    c0, t0 = clock.secs, time.perf_counter()
    state = fdd.init_state()
    jax.block_until_ready(state.V)
    print(f"[deploy] lanczos interval ({state.lam[0]:+.6f}, "
          f"{state.lam[1]:+.6f}): {time.perf_counter() - t0:.3f} s, "
          f"compile {clock.secs - c0:.3f} s", flush=True)
    while True:
        c0, t0 = clock.secs, time.perf_counter()
        it, spmvs = state.iteration, state.total_spmvs
        state = fdd.step_analyze(state)
        if state.done:
            jax.block_until_ready(state.V)
            break
        mu, degree = state.pending
        state = fdd.step_filter(state)
        jax.block_until_ready(state.V)
        print(f"[deploy] outer iteration {it}: degree {degree}, "
              f"{state.total_spmvs - spmvs} SpMVs (vector), "
              f"{time.perf_counter() - t0:.3f} s, of which compile "
              f"{clock.secs - c0:.3f} s; best residual "
              f"{state.history[-1]['best_res']:.3e}", flush=True)
    print(f"[deploy] {state.iteration} outer iterations, "
          f"{state.total_spmvs} SpMVs in all", flush=True)
    V = state.V
    programs = {
        "tsqr": fdd.orthogonalize.lower(V),
        "ritz": fdd.ritz.func.lower(*fdd.ritz.args, V),
        f"filter deg {degree}": fdd.filter.func.lower(
            *fdd.filter.args, V, jnp.asarray(mu), *scale_params(*state.lam)),
    }
    need = {}
    for name, lowered in programs.items():
        m = lowered.compile().memory_analysis()
        need[name] = (m.temp_size_in_bytes + m.argument_size_in_bytes
                      + m.output_size_in_bytes - m.alias_size_in_bytes)
        print(f"[deploy] {name} program HBM: temp "
              f"{m.temp_size_in_bytes / 1e9:.3f} GB, arguments "
              f"{m.argument_size_in_bytes / 1e9:.3f} GB, output "
              f"{m.output_size_in_bytes / 1e9:.3f} GB", flush=True)
    name = max(need, key=need.get)
    print(f"[deploy] largest program HBM {need[name]} bytes "
          f"({need[name] / 2**30:.3f} GiB, {name})", flush=True)

    # the hot path itself: the built stack engine against the host CSR
    rng = np.random.default_rng(0)
    X = np.zeros((fdd.D_pad, 8))
    X[: mat.D] = rng.standard_normal((mat.D, 8))
    Xd = jax.device_put(jnp.asarray(X), fdd.stack_layout.vec_sharding(mesh))
    Y = np.asarray(jit_engine()(fdd.spmv_stack, Xd))[: mat.D]
    Y_ref = host_csr(mat) @ X[: mat.D]
    rel = float(np.abs(Y - Y_ref).max() / np.abs(Y_ref).max())
    bound = 1e-10
    print(f"[deploy] SpMV vs host CSR matvec: max rel error {rel:.3e} "
          f"(bound {bound:.0e}: rows hold <= {ell.cols.shape[-1]} terms, "
          f"so emulated float64 rounding stays near {ell.cols.shape[-1]} * "
          f"{EPS_ELEM:.2e} while a wrong entry or a float32 downcast shows "
          "at >= 1e-8)", flush=True)
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", -1)
    print(f"[deploy] peak_bytes_in_use {peak} ({peak / 2**30:.3f} GiB; "
          "runtime buffers only)", flush=True)
    if not rel <= bound:
        raise RuntimeError(f"deployment SpMV error {rel:.3e} > {bound:.0e}")


def kernel_phase(n=65536, nb=256):
    """The Pallas DIA engine in float32 on a P = 1 RoadNet operator (a
    ring with a commuter corridor: a few dozen diagonals) against the jnp
    engine: the fused Chebyshev step dispatches to ``cheb_dia``. The ELL
    tile kernel does not compile for TPU, so a kernel SpMV must be
    refused."""
    import jax
    import jax.numpy as jnp

    from repro.core import (build_dist_ell, make_fused_cheb_step,
                            make_solver_mesh, make_spmv, panel)
    from repro.kernels.ops import KernelRefused
    from repro.matrices import get_family

    mat = get_family("RoadNet", n=n, w=2, m=64, k=4)
    mesh = make_solver_mesh(1, 1)
    lay = panel(mesh)
    ell = build_dist_ell(mat, 1, dtype=np.float32)
    rng = np.random.default_rng(1)
    sh = lay.vec_sharding(mesh)
    w1, w2 = (jax.device_put(jnp.asarray(rng.standard_normal((ell.R, nb)),
                                         jnp.float32), sh) for _ in range(2))
    args = (w1, w2, jnp.float32(0.3), jnp.float32(-0.2))
    fk = make_fused_cheb_step(mesh, lay, ell, use_kernel=True)
    fj = make_fused_cheb_step(mesh, lay, ell)
    if (fk.body, fj.body) != ("cheb_dia", "jnp"):
        raise RuntimeError(f"[kernel] dispatched {fk.body!r}/{fj.body!r}, "
                           "expected 'cheb_dia'/'jnp'")
    apply_engine = jit_engine()
    ck = apply_engine.lower(fk, *args).compile()
    n_calls = ck.as_text().count("tpu_custom_call")
    if not n_calls:
        raise RuntimeError("[kernel] no tpu_custom_call in the compiled "
                           "fused step")
    yk = np.asarray(ck(fk, *args))
    t0 = time.perf_counter()
    jax.block_until_ready(ck(fk, *args))
    t_k = time.perf_counter() - t0
    yj = np.asarray(apply_engine(fj, *args))
    rel = float(np.abs(yk - yj).max() / np.abs(yj).max())
    # rows hold <= 9 terms plus the 3-term epilogue: float32 rounding
    # stays within ~1e-6 of max |y| in any summation order
    bound = 1e-5
    print(f"[kernel] RoadNet n={n} float32 nb={nb} fused step: body "
          f"cheb_dia, {n_calls} tpu_custom_call in the compiled HLO, one "
          f"call {t_k:.6f} s (warm), max rel diff vs jnp {rel:.3e} "
          f"(bound {bound:.0e})", flush=True)
    if not rel <= bound:
        raise RuntimeError(f"[kernel] fused step: {rel:.3e} > {bound:.0e}")
    try:
        make_spmv(mesh, lay, ell, use_kernel=True)
    except KernelRefused as e:
        print(f"[kernel] spmv with use_kernel=True refused: {e}", flush=True)
    else:
        raise RuntimeError("[kernel] the ell_gather tile kernel was not "
                           "refused on TPU")


def four_chip_phase(n_sites=22, n_search=16, degree=32, small_sites=12):
    """The horizontal layer across chips, against one chip in the same
    process.

    At D = C(22, 11) = 705,432, float64, each mesh (a 2x2 panel and a 4x1
    stack, with the a2a and the compressed halo exchange) applies its
    SpMV engine to one seeded block, runs FD's Lanczos interval, and
    applies one degree-32 Chebyshev filter to the block; each output is
    compared with the 1x1 mesh's and the SpMV also with the host CSR.
    Then FD solves SpinChainXXZ n_sites=12 to convergence on the 2x2
    panel and the 4x1 stack; its eigenvalues are compared with a dense
    host ``eigh`` and with the 1x1 solve, and the residual of every
    returned pair is recomputed on the host. FD does not converge the
    D = 705,432 chain within a chip run (its outer loop needs tens of
    iterations there), so converged eigenvalues are compared at D = 924.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import FDConfig, FilterDiag, make_solver_mesh
    from repro.core.filters import build_filter
    from repro.core.chebyshev import scale_params
    from repro.matrices import get_family

    devs = jax.devices()
    apply_engine = jit_engine()
    meshes = [(1, 1, "panel", "a2a"), (2, 2, "panel", "a2a"),
              (2, 2, "panel", "compressed"), (4, 1, "stack", "a2a"),
              (4, 1, "stack", "compressed")]

    def build(mat, n_row, n_col, layout, comm, **fd_kw):
        mesh = make_solver_mesh(n_row, n_col, devices=devs[: n_row * n_col])
        # SVQB: a float64 QR inside shard_map does not compile across
        # TPU chips, so TSQR is refused there (FilterDiag says so)
        fd = FDConfig(n_search=n_search, layout=layout, spmv_comm=comm,
                      ortho="svqb", **fd_kw)
        return mesh, FilterDiag(mat, mesh, fd)

    def placed(V):
        return sorted((s.device.id, s.index[0].start or 0)
                      for s in V.addressable_shards)

    # -- the layer at D = 705,432 ----------------------------------------
    mat = get_family("SpinChainXXZ", n_sites=n_sites, n_up=n_sites // 2)
    A = host_csr(mat)
    terms = int(np.diff(A.indptr).max())
    X = np.random.default_rng(2).standard_normal((mat.D, n_search))
    Y_host = A @ X
    print(f"[4chip] SpinChainXXZ n_sites={n_sites} D={mat.D} float64 "
          f"n_search={n_search}, <= {terms} terms per row", flush=True)
    # SpMV: each row's terms in float64, summed in an order the halo
    # split may change: |error| <= terms * EPS_ELEM of the row's scale
    b_spmv = 4 * terms * EPS_ELEM
    # the Lanczos coefficients are a dot and a norm of length D, summed
    # across the chips: measure that reduction's error on this mesh
    mesh4 = make_solver_mesh(4, 1, devices=devs[:4])
    P4 = jax.sharding.PartitionSpec(mesh4.axis_names[0])
    sh4 = jax.sharding.NamedSharding(mesh4, P4)
    n4 = mat.D - mat.D % 4
    x4, z4 = X[:n4, 0], X[:n4, 1]
    xl, zl = x4.astype(np.longdouble), z4.astype(np.longdouble)
    xd, zd = jax.device_put(x4, sh4), jax.device_put(z4, sh4)
    e_dot = float(abs(np.longdouble(float(jax.jit(jnp.vdot)(xd, zd)))
                      - np.sum(xl * zl)) / np.sum(np.abs(xl * zl)))
    nrm = np.sqrt(np.sum(xl * xl))
    e_nrm = float(abs(np.longdouble(float(jax.jit(jnp.linalg.norm)(xd)))
                      - nrm)
                  / nrm)
    e_red = max(e_dot, e_nrm, EPS_ELEM)
    print(f"[4chip] float64 reductions over 4 chips, length {n4}: dot "
          f"rel error {e_dot:.3e}, norm {e_nrm:.3e}", flush=True)
    # Lanczos interval: 30 steps, each coefficient off by <= e_red *
    # ||A||; Weyl on the tridiagonal moves its extremal Ritz values, and
    # their residual bounds, by at most steps * e_red * ||A||
    b_lam = 4 * 30 * e_red
    # filter: degree SpMVs and axpys, no reductions; a Chebyshev
    # recurrence on [-1, 1] grows a perturbation at most degree^2-fold
    b_filt = 4 * degree ** 2 * terms * EPS_ELEM
    ref = None
    failed = []
    for n_row, n_col, layout, comm in meshes:
        t0 = time.perf_counter()
        mesh, fdd = build(mat, n_row, n_col, layout, comm, n_target=4)
        Xp = np.zeros((fdd.D_pad, n_search))
        Xp[: mat.D] = X
        Xd = jax.device_put(jnp.asarray(Xp),
                            fdd.stack_layout.vec_sharding(mesh))
        y = np.asarray(apply_engine(fdd.spmv_stack, Xd))[: mat.D]
        lam = np.array(fdd.init_state().lam)
        if ref is None:
            poly = build_filter((lam[0], lam[0] + 0.1 * (lam[1] - lam[0])),
                                tuple(lam), degree=degree)
            coeffs = (jnp.asarray(poly.mu), *scale_params(*lam))
        V = fdd.to_panel(Xd) if fdd.N_col > 1 else Xd
        V = fdd.filter(V, *coeffs)
        Vs = fdd.to_stack(V) if fdd.N_col > 1 else V
        f = np.asarray(Vs)[: mat.D]
        tag = f"{layout} {n_row}x{n_col} {comm}"
        print(f"[4chip] {tag}: mesh {dict(mesh.shape)} devices "
              f"{[d.id for d in mesh.devices.flat]}, lanczos "
              f"({lam[0]:+.12f}, {lam[1]:+.12f}), "
              f"{time.perf_counter() - t0:.3f} s with compiles", flush=True)
        print(f"[4chip]   shards (device id, first row): stack "
              f"{placed(Xd)} filter layout {placed(V)}", flush=True)
        e_h = float(np.abs(y - Y_host).max() / np.abs(Y_host).max())
        if ref is None:
            ref = (y, lam, f)
            print(f"[4chip]   SpMV vs host CSR: max rel error {e_h:.3e} "
                  f"(bound {b_spmv:.1e})", flush=True)
            if not e_h <= b_spmv:
                failed.append(f"{tag} spmv vs host")
            continue
        e_y = float(np.abs(y - ref[0]).max() / np.abs(ref[0]).max())
        e_l = float(np.abs(lam - ref[1]).max() / np.abs(ref[1]).max())
        e_f = float(np.abs(f - ref[2]).max() / np.abs(ref[2]).max())
        print(f"[4chip]   vs 1x1: SpMV max rel diff {e_y:.3e} (bound "
              f"{b_spmv:.1e}; vs host {e_h:.3e}), lanczos interval rel "
              f"diff {e_l:.3e} (bound {b_lam:.1e}), degree-{degree} filter "
              f"max rel diff {e_f:.3e} (bound {b_filt:.1e})", flush=True)
        if not (e_y <= b_spmv and e_h <= b_spmv and e_l <= b_lam
                and e_f <= b_filt):
            failed.append(tag)

    # -- converged eigenpairs at D = 924 ---------------------------------
    small = get_family("SpinChainXXZ", n_sites=small_sites,
                       n_up=small_sites // 2)
    A = host_csr(small)
    target, tol, n_target = 0.05, 1e-8, 4
    lam_ref = nearest_eigs(dense_eigs(small), target, n_target)
    print(f"[4chip] SpinChainXXZ n_sites={small_sites} D={small.D} float64 "
          f"target={target} tol={tol}: reference "
          f"{np.array2string(lam_ref, precision=12)}", flush=True)
    # a Hermitian Ritz value lies within its residual norm of an
    # eigenvalue; the host recomputes that norm in IEEE float64, off from
    # the device's by its Rayleigh-Ritz matmuls: <= N_s * EPS_MATMUL * ||A||
    norm_a = float(abs(A).sum(axis=1).max())
    b_res = tol + 4 * n_search * EPS_MATMUL * norm_a
    th1 = None
    for n_row, n_col, layout, comm in (meshes[0], meshes[1], meshes[4]):
        t0 = time.perf_counter()
        mesh, fdd = build(small, n_row, n_col, layout, comm,
                          n_target=n_target, target=target, tol=tol,
                          max_iters=25)
        state = fdd.init_state()
        while not state.done:
            state = fdd.step(state)
        res = state.result
        theta, _, _, VY = fdd.ritz(fdd.orthogonalize(state.V))
        theta = np.asarray(theta)
        pick = [int(np.argmin(np.abs(theta - e))) for e in res.eigenvalues]
        Vh = fdd.gather_global(VY)[:, pick]
        r_host = np.linalg.norm(A @ Vh - Vh * theta[pick], axis=0) \
            / np.linalg.norm(Vh, axis=0)
        th = nearest_eigs(res.eigenvalues, target, n_target)
        err = float(np.abs(th - lam_ref).max()) if len(th) == n_target \
            else float("inf")
        tag = f"{layout} {n_row}x{n_col} {comm}"
        line = (f"[4chip] {tag} n_sites={small_sites}: {res.n_converged} "
                f"converged in {res.iterations} iterations, "
                f"{time.perf_counter() - t0:.3f} s with compiles; max "
                f"|lambda - lambda_ref| {err:.3e} (bound {tol:.0e}); host "
                f"residuals max {r_host.max():.3e} (bound {b_res:.2e})")
        if th1 is None:
            th1 = th
        elif len(th) == n_target:
            line += f"; vs 1x1 max |diff| {np.abs(th - th1).max():.3e}"
        print(line, flush=True)
        if not (res.n_converged >= n_target and err <= tol
                and r_host.max() <= b_res):
            failed.append(f"{tag} n_sites={small_sites}")
    if failed:
        raise RuntimeError(f"[4chip] out of bounds: {failed}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devs = device_phase(args.chips)

    import jax

    from repro.core.spans import COMPILES as clock
    from repro.launch.solve import enable_compile_cache

    jax.config.update("jax_enable_x64", True)
    print(f"[cache] compilation cache at {enable_compile_cache()}",
          flush=True)
    c0, t0 = clock.secs, time.perf_counter()
    if args.chips == 4:
        four_chip_phase()
    else:
        correctness_phases()
        deployment_phase(clock)
        kernel_phase()
    print(f"[cache] {time.perf_counter() - t0:.3f} s in all, compile "
          f"{clock.secs - c0:.3f} s; persistent cache hits {clock.hits}, "
          f"misses {clock.misses}", flush=True)
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
