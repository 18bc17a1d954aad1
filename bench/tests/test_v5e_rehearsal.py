"""Compile rehearsal for a TPU v5e, made without the chip: the programs of
``spinchain22-outer`` (TSQR, Rayleigh-Ritz, the degree-32 filter) at the
cell's own size compile for a described v5e and fit its 16 GB. Their
``memory_analysis()`` is the prediction of ``hbm_gb``; nothing runs, so
nothing here is a device number.

The topology is described inside the fixture, never at import: one
process at a time may load the TPU compiler's library.
"""

import jax
import jax.numpy as jnp
import pytest

import harness

V5E_HBM = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # the TPU compiler cannot be loaded here
        pytest.skip(f"no v5e topology can be described here: {e}")
    finally:
        mp.undo()


def test_outer_cell_programs_fit_one_v5e(topo):
    cell = harness.load_cell("spinchain22-outer")
    fd = harness.make_solver(cell, topo.devices[:1])
    n_s = cell.config["solver"]["n_search"]
    V = jax.ShapeDtypeStruct((fd.D_pad, n_s), jnp.dtype(cell.config["dtype"]),
                             sharding=fd.stack_layout.vec_sharding(fd.mesh))
    mu = jax.ShapeDtypeStruct((cell.traffic["solver"]["degree_cap"] + 1,),
                              jnp.float64)
    need = harness.program_bytes({
        "orthogonalize": harness.lower(fd.orthogonalize, V),
        "ritz": harness.lower(fd.ritz, V),
        "filter": harness.lower(fd.filter, V, mu, 0.1, 0.0)})
    print(f"\n[v5e rehearsal] {cell.name} program HBM "
          f"{need}")
    assert 0 < max(need.values()) < V5E_HBM
