"""Shared test utilities.

Tests in this process see the default single CPU device (the dry-run's
512-device override is process-local to dryrun.py). Distributed behaviour
is tested through subprocesses (run_distributed) so each gets its own
XLA_FLAGS device count.
"""
import os
import subprocess
import sys

import pytest

import jax

# XLA's CPU backend contracts a*b + c into an FMA, or not, depending on
# the fusion around it, so two differently structured programs that
# issue the same IEEE operations in the same order can still round
# differently. A test that demands bit-identity between such programs
# (kernel on vs off, s-step vs s = 1) runs its child with FMA off.
NO_FMA = "--xla_cpu_max_isa=AVX"

jax.config.update("jax_enable_x64", True)  # f64 kernel sweeps + FD residuals

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")


def run_distributed(script: str, n_devices: int = 8, timeout: int = 900,
                    x64: bool = True, xla_flags: str = "") -> str:
    """Run ``script`` in a subprocess with n fake CPU devices; returns stdout.
    ``xla_flags`` are appended to the child's XLA_FLAGS (e.g. ``NO_FMA``).
    Raises on nonzero exit."""
    pre = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = "
        f"'--xla_force_host_platform_device_count={n_devices} {xla_flags}'\n"
        "import jax\n"
        + ("jax.config.update('jax_enable_x64', True)\n" if x64 else "")
    )
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", pre + script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    if r.returncode != 0:
        raise AssertionError(
            f"distributed subprocess failed:\nSTDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
        )
    return r.stdout


@pytest.fixture(scope="session")
def rng():
    import numpy as np

    return np.random.default_rng(0)
