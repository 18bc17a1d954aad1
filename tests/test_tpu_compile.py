"""Compile rehearsals for a TPU v5e, made on the CPU.

The TPU compiler is installed next to JAX and compiles for a described
``v5e:2x2`` host without a chip attached: what it refuses here (block
shapes, Mosaic lowerings, dtypes) it would refuse on the chip. Nothing
runs, so these tests check that programs compile, not what they compute.

The topology is described inside a module fixture, never while a module
is imported: only one process at a time may load the TPU compiler's
library, and every test worker imports this file. Keep these tests in
this one file, so that one worker loads the library.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # the TPU compiler cannot be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("n_diag", [5, 64])
def test_cheb_dia_compiles_for_v5e(one_chip, n_diag):
    """The fused Chebyshev DIA step at a real shard width (R = 65,536
    rows, nb = 256 vectors, float32) lowers to one Mosaic kernel."""
    from repro.kernels import ops

    R, nb = 65536, 256
    offsets = tuple(range(-3 * (n_diag // 2), 3 * (n_diag - n_diag // 2), 3))

    def S(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    step = jax.jit(lambda dv, x, w2, a, b: ops.cheb_dia(
        offsets, dv, x, x, w2, a, b, interpret=False))
    hlo = step.lower(S((n_diag, R)), S((R, nb)), S((R, nb)), S(()),
                     S(())).compile().as_text()
    assert hlo.count("tpu_custom_call") >= 1


@pytest.mark.parametrize("comm", ["a2a", "compressed"])
def test_jnp_spmv_engine_compiles_on_v5e_2x2(topo, comm):
    """The jnp SpMV engine in float64 on a 2x2 panel of the described
    host: the halo exchange compiles to the collective it is built on."""
    jax.config.update("jax_enable_x64", True)
    from repro.core import build_dist_ell, make_solver_mesh, make_spmv, panel
    from repro.matrices import SpinChainXXZ

    mesh = make_solver_mesh(2, 2, devices=topo.devices)
    lay = panel(mesh)
    mat = SpinChainXXZ(12, 6)
    d_pad = -(-mat.D // 4) * 4
    ell = build_dist_ell(mat, 2, d_pad=d_pad)
    spmv = make_spmv(mesh, lay, ell, comm=comm)
    assert spmv.body == "jnp"
    x = jax.ShapeDtypeStruct((d_pad, 16), jnp.float64,
                             sharding=NamedSharding(mesh, lay.vec_pspec()))
    hlo = jax.jit(spmv).lower(x).compile().as_text()
    want = "all-to-all" if comm == "a2a" else "collective-permute"
    assert want in hlo


def test_complex128_refused_on_tpu_before_compile(topo, monkeypatch):
    """A complex family in the default float64 config becomes complex128,
    which aborts the TPU compiler: FilterDiag refuses it on a TPU mesh
    before it builds or traces anything, and runs it as complex64 with
    dtype='float32'."""
    jax.config.update("jax_enable_x64", True)
    from repro.core import FDConfig, FilterDiag, make_solver_mesh
    from repro.core import filter_diag
    from repro.matrices import Exciton

    mesh = make_solver_mesh(1, 1, devices=topo.devices[:1])
    mat = Exciton(L=2)
    real_build = filter_diag.build_dist_ell

    def no_build(*a, **k):
        raise AssertionError("complex128 reached the operator build")

    monkeypatch.setattr(filter_diag, "build_dist_ell", no_build)
    with pytest.raises(ValueError, match="complex128.*dtype='float32'"):
        FilterDiag(mat, mesh, FDConfig(n_search=8))
    monkeypatch.setattr(filter_diag, "build_dist_ell", real_build)
    fdd = FilterDiag(mat, mesh, FDConfig(n_search=8, dtype="float32"))
    assert fdd.dtype == np.complex64


def test_float64_tsqr_refused_on_multichip_tpu(topo):
    """A float64 QR inside shard_map does not compile across TPU chips,
    so FilterDiag refuses TSQR on a multi-chip TPU mesh and names SVQB,
    whose programs compile there."""
    jax.config.update("jax_enable_x64", True)
    from repro.core import FDConfig, FilterDiag, make_solver_mesh
    from repro.matrices import SpinChainXXZ

    mesh = make_solver_mesh(2, 2, devices=topo.devices)
    mat = SpinChainXXZ(10, 5)
    with pytest.raises(ValueError, match="ortho='svqb'"):
        FilterDiag(mat, mesh, FDConfig(n_search=8))
    fdd = FilterDiag(mat, mesh, FDConfig(n_search=8, ortho="svqb"))
    V = jax.ShapeDtypeStruct((fdd.D_pad, 8), jnp.float64,
                             sharding=fdd.stack_layout.vec_sharding(mesh))
    assert fdd.orthogonalize.lower(V).compile() is not None
