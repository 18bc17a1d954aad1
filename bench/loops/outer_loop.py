"""Loop kind ``outer_loop``: FD's outer iteration, back to back.

Set-up runs FD's Lanczos interval (``init_state``) and one whole outer
iteration, which compiles every program the window uses. The window then
calls ``FilterDiag.step`` until the given seconds have passed; it starts
no iteration after that, and ends when the last one's filter output is
ready. A state that converges restarts from a new seeded block with the
same Lanczos interval.

A traced run drives the same iteration as its two halves, each inside a
span of its own that ends on a device sync: ``step_analyze``
(orthogonalize, Rayleigh-Ritz, intervals, filter choice; it waits for the
Ritz values) and ``step_filter`` (the Chebyshev filter, waited for). That
is ``FilterDiag.step`` with a sync after the filter, which the untraced
window does not add.

The check takes one iteration of the window, drawn from the seed, with
what its own programs returned (wrapped, not rerun): the orthonormal
block Q, and the filter's input, coefficients and output. On the host,
in float64 (complex128 for a complex matrix):

* ``filter_err``: the filter output against the same recurrence with the
  same mu, alpha, beta on the reference matrix, max |diff| / max |ref|.
  It covers the SpMV engine and the Chebyshev step;
* ``ortho_err``: max |Q^H Q - I| of the orthogonalized block.

The Ritz values are not compared: a v5e runs float64 matmuls as float32
passes, so they are float32-accurate, and the solver's float32 path
reads no worse.
"""
from __future__ import annotations

import time

import numpy as np
import scipy.sparse as sp

from harness import lower


class Loop:
    def __init__(self, fd, traffic: dict, seed: int, spans):
        self.fd, self.spans = fd, spans
        self.rng = np.random.default_rng(seed % 2 ** 64)
        self.seen = 0            # window iterations, for the sample
        self.kept = None         # the sampled iteration's outputs
        self._last = {}
        self.inner = {}          # the solver's own callables, unwrapped
        self._wrap("orthogonalize", lambda out, *a: {"Q": out})
        self._wrap("filter", lambda out, V, mu, alpha, beta: dict(
            V=V, mu=mu, alpha=alpha, beta=beta, Y=out))

    def _wrap(self, name, keep):
        inner = self.inner[name] = getattr(self.fd, name)

        def call(*args):
            out = inner(*args)
            self._last.update(keep(out, *args))
            return out

        setattr(self.fd, name, call)

    def _key(self):
        import jax

        return jax.random.PRNGKey(int(self.rng.integers(2 ** 31 - 1)))

    def _step_in_spans(self, state):
        """``FilterDiag.step`` as its two halves, each in a span."""
        import jax

        fd = self.fd
        with self.spans("analyze"):
            state = fd.step_analyze(state)
        if not state.done:
            with self.spans("filter"):
                state = fd.step_filter(state)
                jax.block_until_ready(state.V)
        return state

    def _iterate(self, traced: bool) -> tuple[int, int]:
        """One outer iteration: (vector SpMVs FD counted, filter degree;
        0 where the state converged and restarted)."""
        from repro.core import FDState

        before = self.state.total_spmvs
        state = (self._step_in_spans(self.state) if traced
                 else self.fd.step(self.state))
        spmvs = state.total_spmvs - before
        if state.done:
            self.state = FDState(V=self.fd.random_search_vectors(self._key()),
                                 lam=state.lam)
            return spmvs, 0
        self.state = state
        return spmvs, self._last["mu"].shape[0] - 1

    def warm(self):
        import jax

        self.state = self.fd.init_state(self._key())
        self._iterate(traced=False)
        jax.block_until_ready(self.state.V)

    def programs(self) -> dict:
        import jax.numpy as jnp

        from repro.core.chebyshev import scale_params

        V = self.state.V
        mu = jnp.zeros(self._last["mu"].shape, self._last["mu"].dtype)
        return {
            "orthogonalize": lower(self.inner["orthogonalize"], V),
            "ritz": lower(self.fd.ritz, V),
            "filter": lower(self.inner["filter"], V, mu,
                            *scale_params(*self.state.lam)),
        }

    def window(self, seconds: float, traced: bool = False) -> dict:
        import jax

        spmvs = iterations = steps = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            n, degree = self._iterate(traced)
            spmvs += n
            if degree:
                iterations += 1
                steps += degree
                self.seen += 1
                if self.rng.random() * self.seen < 1.0:
                    self.kept = dict(self._last)
        jax.block_until_ready(self.state.V)
        return {"attempted": iterations, "failed": 0, "iterations":
                iterations, "spmvs": spmvs, "filter_steps": steps}

    def fetch(self):
        """Copy the sampled outputs to the host; drop every device array."""
        kept = self.kept or {}
        self.kept = {k: np.asarray(v) for k, v in kept.items()}
        self.state = self.fd = None
        self._last = {}

    def check(self, A) -> tuple[dict, int]:
        if not self.kept:
            return {}, 0
        k, D = self.kept, A.shape[0]
        wide = np.complex128 if np.iscomplexobj(k["V"]) else np.float64
        V, Y, Q = (k[n][:D].astype(wide) for n in ("V", "Y", "Q"))
        Y_ref = chebyshev(A, V, k["mu"].astype(np.float64),
                          float(k["alpha"]), float(k["beta"]))
        gram = Q.conj().T @ Q
        return {
            "filter_err": float(np.abs(Y - Y_ref).max() / np.abs(Y_ref).max()),
            "ortho_err": float(np.abs(gram - np.eye(gram.shape[0])).max()),
        }, 1


def chebyshev(A, V, mu, alpha: float, beta: float):
    """p(A) V for p = sum_k mu_k T_k(alpha A + beta), three-term recurrence
    T_k = 2 (alpha A + beta) T_{k-1} - T_{k-2}."""
    B = (alpha * A + beta * sp.identity(A.shape[0], A.dtype)).tocsr()
    prev, cur = V, B @ V
    Y = mu[0] * V + mu[1] * cur
    for m in mu[2:]:
        nxt = B @ cur
        nxt *= 2
        nxt -= prev
        prev, cur = cur, nxt
        Y += m * cur
    return Y
