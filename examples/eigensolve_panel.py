"""Two orthogonal layers of parallelism, end to end.

    PYTHONPATH=src python examples/eigensolve_panel.py

Runs the SAME eigenproblem three ways on an 8-device mesh —
stack (8x1), panel (4x2), pillar (1x8) — and reports, per layout:
iterations, SpMVs, redistribution count, and the per-SpMV collective
bytes measured from the compiled HLO (which follow the χ metric exactly).
The eigenvalues agree across layouts and with dense eigh.

This script re-executes itself with 8 fake XLA devices on the CPU.
"""
import os
import subprocess
import sys

if "XLA_FLAGS" not in os.environ:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    sys.exit(subprocess.run([sys.executable] + sys.argv, env=env).returncode)

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np

from repro.core import FDConfig, FilterDiag, make_solver_mesh, panel, pillar, stack
from repro.core.layouts import Layout
from repro.core.metrics import chi_metrics
from repro.matrices import Hubbard


def main():
    mat = Hubbard(n_sites=6, n_fermions=3, U=4.0, ranpot=1.0)
    csr = mat.build_csr()
    w = np.linalg.eigvalsh(csr.to_dense())
    tau = float(w[len(w) // 3])
    print(f"matrix: {mat.describe()}, target tau={tau:+.4f}")
    for Np in (2, 4, 8):
        m = chi_metrics(mat, Np)
        print(f"  chi[{Np}] = {m.chi1:.2f}  (comm-bound for chi >> b_c/b_m)")

    results = {}
    for n_row, n_col, name in ((8, 1, "stack"), (4, 2, "panel 4x2"),
                               (1, 8, "pillar")):
        mesh = make_solver_mesh(n_row, n_col)
        cfg = FDConfig(n_target=3, n_search=16, target=tau, tol=1e-8,
                       max_iters=18)
        with mesh:
            fd = FilterDiag(csr, mesh, cfg)
            res = fd.solve()
        results[name] = res
        comm = fd.ell_panel.comm_bytes_per_spmv
        print(f"[{name:9s}] conv={res.n_converged} iters={res.iterations} "
              f"spmvs={res.total_spmvs} redists={res.redistributions} "
              f"filter-SpMV comm plan: {comm/1024:.0f} KiB/column-group")

    evs = [np.sort(r.eigenvalues[:3]) for r in results.values()]
    for e in evs[1:]:
        np.testing.assert_allclose(e[:3], evs[0][:3], atol=1e-7)
    for ev in evs[0]:
        assert np.abs(w - ev).min() < 1e-7
    print("OK — all layouts agree with each other and with dense eigh")


if __name__ == "__main__":
    main()
