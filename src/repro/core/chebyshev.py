"""Chebyshev filter evaluation — paper Algorithm 2.

Evaluates V <- p[A]V for p(x) = sum_k mu_k T_k(x) using the three-term
recurrence, with the fused SpMV+axpy step (kernel fusion keeps the vector
traffic factor at κ=5 instead of 6 — paper §3.2).

The recurrence runs entirely in the chosen vector layout; the only
communication is the halo all_to_all inside each SpMV (horizontal layer).
Also provides KPM moment accumulation (used for the DOS panels, Figs 7/8).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["scale_params", "chebyshev_filter", "chebyshev_filter_sstep",
           "kpm_moments"]


def scale_params(lambda_l: float, lambda_r: float) -> tuple[float, float]:
    """alpha, beta mapping spec(A) in [λl, λr] onto [-1, 1] (Alg. 2 step 1)."""
    alpha = 2.0 / (lambda_r - lambda_l)
    beta = (lambda_l + lambda_r) / (lambda_l - lambda_r)
    return alpha, beta


def chebyshev_filter(spmv, mu, alpha: float, beta: float, V, fused_step=None):
    """Return p[A]V given the distributed ``spmv`` closure.

    ``mu`` is a length-(n+1) coefficient array (n >= 2). Uses two workspace
    matrices W1, W2 (three live vectors total, as in the paper's memory
    accounting). The k-loop is a ``lax.scan`` so the compiled HLO contains
    a single fused iteration body regardless of the degree.

    ``fused_step(w1, w2, alpha, beta)``, when given (built with
    :func:`~repro.core.spmv.make_fused_cheb_step`), replaces the inline
    ``2a·spmv(w1) + 2b·w1 - w2`` recurrence step — same expression
    evaluated inside one shard_map body (or a single fused Pallas kernel
    for comm-free DIA operators), so the result is bit-identical while
    the vector traffic stays at the paper's κ = 5.
    """
    mu = jnp.asarray(mu, dtype=V.real.dtype if jnp.iscomplexobj(V) else V.dtype)
    n = mu.shape[0] - 1
    assert n >= 2, "filter degree must be >= 2"
    a = jnp.asarray(alpha, mu.dtype)
    b = jnp.asarray(beta, mu.dtype)

    if fused_step is None:
        def fused_step(w1, w2, alpha_, beta_):
            y = spmv(w1)
            with jax.named_scope("fd.cheb_update"):
                return 2 * a * y + 2 * b * w1 - w2  # fused SpMV+axpy

    y = spmv(V)
    with jax.named_scope("fd.cheb_update"):
        W1 = a * y + b * V                       # T1
    W2 = fused_step(W1, V, alpha, beta)          # T2
    with jax.named_scope("fd.cheb_update"):
        Y = mu[0] * V + mu[1] * W1 + mu[2] * W2

    def body(carry, mu_k):
        Y, Tkm1, Tkm2 = carry
        Tk = fused_step(Tkm1, Tkm2, alpha, beta)
        with jax.named_scope("fd.cheb_update"):
            Y = Y + mu_k * Tk
        return (Y, Tk, Tkm1), None

    if n >= 3:
        (Y, _, _), _ = lax.scan(body, (Y, W2, W1), mu[3:])
    return Y


def chebyshev_filter_sstep(group, mu, alpha: float, beta: float, V, s: int):
    """Communication-avoiding filter evaluation: ⌈n/s⌉ ghost exchanges.

    ``group(n_steps, first)`` (built by
    :func:`~repro.core.spmv.make_sstep_cheb`) returns a fused closure
    running ONE depth-s ghost exchange followed by ``n_steps`` recurrence
    steps on the extended block, returning the owned step outputs
    stacked (``[n_steps, D, nb]``) plus the shifted carries. The
    degree-n loop is split into a first group (seeds off V alone, so its
    exchange ships single width), a ``lax.scan`` over the uniform middle
    groups (one fused HLO body — the s-step analogue of the base
    filter's scanned step), and an explicit tail group of the n mod s
    leftover steps. The μ-accumulation happens HERE, in the main graph,
    with the identical op tree to :func:`chebyshev_filter` — the init
    ``mu0·V + mu1·T1 + mu2·T2`` followed by scanned ``Y + mu_k·T_k``
    updates — so XLA's fused-multiply-add choices match and the result
    is bit-identical to the s=1 engines for every s.
    """
    mu = jnp.asarray(mu, dtype=V.real.dtype if jnp.iscomplexobj(V) else V.dtype)
    n = int(mu.shape[0]) - 1
    s = int(s)
    assert n >= 2, "filter degree must be >= 2"
    assert s >= 2, "s=1 is the per-step engine grid (chebyshev_filter)"
    n_groups = -(-n // s)
    s1 = min(s, n)

    def acc(Yk, mu_T):
        mu_k, Tk = mu_T
        with jax.named_scope("fd.cheb_update"):
            return Yk + mu_k * Tk, None

    Ts, w1, w2 = group(s1, True)(V, alpha, beta)
    Y = mu[0] * V + mu[1] * Ts[0] + mu[2] * Ts[1]
    if s1 > 2:
        Y, _ = lax.scan(acc, Y, (mu[3:1 + s1], Ts[2:]))
    if s1 == n:
        return Y
    r_tail = n - (n_groups - 1) * s
    n_mid = (n_groups - 1) - (0 if r_tail == s else 1)
    if n_mid:
        g = group(s, False)
        mus_mid = mu[1 + s:1 + s + n_mid * s].reshape(n_mid, s)

        def body(carry, mus_k):
            Yk, a1, a2 = carry
            Ts_k, a1, a2 = g(a1, a2, alpha, beta)
            Yk, _ = lax.scan(acc, Yk, (mus_k, Ts_k))
            return (Yk, a1, a2), None

        (Y, w1, w2), _ = lax.scan(body, (Y, w1, w2), mus_mid)
    if r_tail != s:
        Ts_t, w1, w2 = group(r_tail, False)(w1, w2, alpha, beta)
        Y, _ = lax.scan(acc, Y, (mu[1 + n - r_tail:], Ts_t))
    return Y


def kpm_moments(spmv, alpha: float, beta: float, V, n_moments: int):
    """KPM moments mu_m = tr[T_m(A~)] estimated with the stochastic trace
    over the columns of V (used for the density-of-states panels)."""
    a = jnp.asarray(alpha, V.real.dtype if jnp.iscomplexobj(V) else V.dtype)
    b = jnp.asarray(beta, a.dtype)

    def dot(x, y):
        return jnp.real(jnp.sum(jnp.conj(x) * y))

    T0 = V
    T1 = a * spmv(V) + b * V
    m0 = dot(V, T0)
    m1 = dot(V, T1)

    def body(carry, _):
        Tkm1, Tkm2 = carry
        Tk = 2 * a * spmv(Tkm1) + 2 * b * Tkm1 - Tkm2
        return (Tk, Tkm1), dot(V, Tk)

    (_, _), ms = lax.scan(body, (T1, T0), None, length=n_moments - 2)
    return jnp.concatenate([jnp.stack([m0, m1]), ms])


def kpm_dos(moments: np.ndarray, n_bins: int = 512, jackson: bool = True):
    """Reconstruct the normalized DOS on [-1, 1] from KPM moments."""
    M = len(moments)
    mu = np.asarray(moments, dtype=np.float64).copy()
    if jackson:
        k = np.arange(M)
        g = ((M - k + 1) * np.cos(np.pi * k / (M + 1))
             + np.sin(np.pi * k / (M + 1)) / np.tan(np.pi / (M + 1))) / (M + 1)
        mu *= g
    x = np.cos(np.pi * (np.arange(n_bins) + 0.5) / n_bins)
    Tm = np.cos(np.outer(np.arccos(x), np.arange(M)))
    w = (2.0 - (np.arange(M) == 0)) * mu / mu[0]
    rho = (Tm @ w) / (np.pi * np.sqrt(1 - x**2))
    return x[::-1], rho[::-1]
