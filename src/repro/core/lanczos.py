"""Lanczos spectral inclusion interval (Alg. 1 step 1).

A few Lanczos steps on a random vector give Ritz value bounds; the residual
of the extremal Ritz pairs provides a rigorous safety margin so that
spec(A) ⊂ [λ_l, λ_r] (required for the Chebyshev map to stay in [-1,1]).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from .spans import span

__all__ = ["lanczos_interval"]


@jax.jit
def _lanczos_step(spmv, v, v_prev, beta):
    """One three-term step as one program (one host sync per step).
    ``spmv`` is an engine pytree (``core/spmv.py``), passed in so its
    operator arrays are parameters, not constants."""
    with jax.named_scope("fd.lanczos_step"):
        w = spmv(v)
        # a sum of products, not vdot: on a v5e the float64 vdot in this
        # program left the interval 3.1e-9 from IEEE at D = 705,432 (and
        # differently on one chip and on four), the sum 1.6e-13
        a = jnp.real(jnp.sum(jnp.conj(v) * w))
        w = w - a * v - beta * v_prev
        return w, a, jnp.linalg.norm(w)


def lanczos_interval(spmv, D: int, D_pad: int, dtype, key, steps: int = 30,
                     safety: float = 1.05, mask=None):
    """Return (lambda_l, lambda_r) enclosing spec(A).

    ``spmv`` acts on [D_pad, 1] arrays (any distributed layout); the
    tridiagonal coefficients are accumulated on the host (they are scalars,
    so this costs one tiny transfer per step — the paper's preparatory
    phase is negligible and we keep it simple). Padding rows are kept
    exactly zero so the padded operator's null modes never enter the
    Krylov space: by default the pad is the tail [D:D_pad) (the
    equal-rows partition), while a planned row decomposition
    (``core/partition.py``) passes its own ``mask`` — a [D_pad] bool of
    valid positions — because its pad rows sit at each block's end, not
    at the global end.

    The whole of it is the span ``lanczos`` (``core/spans.py``), counting
    its steps and host fetches.
    """
    with span("lanczos") as s:
        v = jax.random.normal(key, (D_pad, 1)).astype(dtype)
        if mask is None:
            mask = jnp.arange(D_pad) < D
        v = v * jnp.asarray(mask)[:, None]
        v = v / jnp.linalg.norm(v)
        alphas, betas = [], []
        v_prev = jnp.zeros_like(v)
        beta = 0.0
        for k in range(steps):
            w, a, b = _lanczos_step(spmv, v, v_prev, beta)
            a, b = float(a), float(b)
            alphas.append(a)
            betas.append(b)
            if b < 1e-12:
                break
            v_prev, v = v, w / b
        s.counts.update(steps=len(alphas), fetches=2 * len(alphas))
    T = np.diag(alphas)
    off = betas[: len(alphas) - 1]
    T += np.diag(off, 1) + np.diag(off, -1)
    theta, Y = np.linalg.eigh(T)
    resid = betas[len(alphas) - 1] * np.abs(Y[-1, :])  # Ritz residual bounds
    lo = float(theta[0] - resid[0])
    hi = float(theta[-1] + resid[-1])
    mid, half = 0.5 * (lo + hi), 0.5 * (hi - lo)
    return mid - safety * half, mid + safety * half
