"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU), with
hypothesis sweeps over shapes/dtypes/offset patterns."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from tests._hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.cheb_dia import cheb_dia
from repro.kernels.ell_gather import build_tiles, ell_gather_spmv


def _mk_dia(rng, R, offsets, dtype):
    dvals = rng.standard_normal((len(offsets), R)).astype(dtype)
    idx = np.arange(R)
    for d, o in enumerate(offsets):
        dvals[d, (idx + o < 0) | (idx + o >= R)] = 0.0
    return dvals


@pytest.mark.parametrize("R,nb,br,bn", [
    (64, 128, 8, 128), (256, 128, 64, 128), (512, 256, 512, 128),
    (1024, 384, 256, 128),
])
def test_cheb_dia_shapes(R, nb, br, bn):
    rng = np.random.default_rng(R + nb)
    offsets = (-(R // 3), -7, -1, 0, 2, 9, R // 4)
    dvals = _mk_dia(rng, R, offsets, np.float32)
    x = rng.standard_normal((R, nb)).astype(np.float32)
    w1 = rng.standard_normal((R, nb)).astype(np.float32)
    w2 = rng.standard_normal((R, nb)).astype(np.float32)
    y_ref = np.asarray(ref.cheb_dia_ref(offsets, dvals, x, w1, w2, 1.1, -0.3))
    y = np.asarray(cheb_dia(offsets, jnp.asarray(dvals), jnp.asarray(x),
                            jnp.asarray(w1), jnp.asarray(w2), 1.1, -0.3,
                            br=br, bn=bn, interpret=True))
    np.testing.assert_allclose(y, y_ref, rtol=2e-5, atol=2e-5)


@given(
    seed=st.integers(0, 10_000),
    roff=st.lists(st.integers(-96, 96), min_size=1, max_size=6, unique=True),
    dtype=st.sampled_from([np.float32, np.float64]),
)
@settings(max_examples=15, deadline=None)
def test_cheb_dia_hypothesis(seed, roff, dtype):
    R, nb = 128, 128
    rng = np.random.default_rng(seed)
    offsets = tuple(sorted(roff))
    dvals = _mk_dia(rng, R, offsets, dtype)
    x = rng.standard_normal((R, nb)).astype(dtype)
    w1 = rng.standard_normal((R, nb)).astype(dtype)
    w2 = rng.standard_normal((R, nb)).astype(dtype)
    a, b = float(rng.normal()), float(rng.normal())
    y_ref = np.asarray(ref.cheb_dia_ref(offsets, dvals, x, w1, w2, a, b))
    y = np.asarray(cheb_dia(offsets, jnp.asarray(dvals), jnp.asarray(x),
                            jnp.asarray(w1), jnp.asarray(w2), a, b,
                            br=64, bn=128, interpret=True))
    tol = 1e-4 if dtype == np.float32 else 1e-10
    np.testing.assert_allclose(y, y_ref, rtol=tol, atol=tol)


def test_cheb_dia_complex_via_ops():
    rng = np.random.default_rng(3)
    R, nb = 128, 128
    offsets = (-8, -1, 0, 1, 8)
    dv = (rng.standard_normal((5, R)) + 1j * rng.standard_normal((5, R))).astype(np.complex64)
    idx = np.arange(R)
    for d, o in enumerate(offsets):
        dv[d, (idx + o < 0) | (idx + o >= R)] = 0.0
    x = (rng.standard_normal((R, nb)) + 1j * rng.standard_normal((R, nb))).astype(np.complex64)
    w1 = x * 0.3
    w2 = x[::-1] * 0.7
    y_ref = np.asarray(ref.cheb_dia_ref(offsets, jnp.asarray(dv), jnp.asarray(x),
                                        jnp.asarray(w1), jnp.asarray(w2), 0.9, 0.05))
    y = np.asarray(ops.cheb_dia(offsets, jnp.asarray(dv), jnp.asarray(x),
                                jnp.asarray(w1), jnp.asarray(w2), 0.9, 0.05,
                                interpret=True))
    np.testing.assert_allclose(y, y_ref, rtol=2e-4, atol=2e-4)


def test_cheb_dia_halo_region():
    """x longer than R (halo appended) with offsets pointing into it."""
    rng = np.random.default_rng(4)
    R, Rx, nb = 128, 256, 128
    offsets = (0, 100)  # reaches into [R, Rx)
    dvals = rng.standard_normal((2, R)).astype(np.float32)  # all valid: i+100 < 256
    x = rng.standard_normal((Rx, nb)).astype(np.float32)
    w1 = rng.standard_normal((R, nb)).astype(np.float32)
    w2 = rng.standard_normal((R, nb)).astype(np.float32)
    y_ref = np.asarray(ref.cheb_dia_ref(offsets, dvals, x, w1, w2, 1.0, 0.0))
    y = np.asarray(cheb_dia(offsets, jnp.asarray(dvals), jnp.asarray(x),
                            jnp.asarray(w1), jnp.asarray(w2), 1.0, 0.0,
                            br=64, bn=128, interpret=True))
    np.testing.assert_allclose(y, y_ref, rtol=2e-5, atol=2e-5)


@given(seed=st.integers(0, 1000), W=st.integers(1, 12),
       density=st.floats(0.2, 1.0))
@settings(max_examples=10, deadline=None)
def test_ell_gather_tiles(seed, W, density):
    rng = np.random.default_rng(seed)
    R, Rx, nb = 256, 2048, 128
    cols = rng.integers(0, Rx, size=(R, W)).astype(np.int32)
    vals = rng.standard_normal((R, W)).astype(np.float32)
    vals[rng.random((R, W)) >= density] = 0.0
    x = rng.standard_normal((Rx, nb)).astype(np.float32)
    tile_cb, tcols, tvals = build_tiles(cols, vals, Rx, br=256, bc=512)
    y_ref = np.asarray(ref.ell_spmv_ref(jnp.asarray(cols), jnp.asarray(vals),
                                        jnp.asarray(x)))
    y = np.asarray(ell_gather_spmv(jnp.asarray(tile_cb), jnp.asarray(tcols),
                                   jnp.asarray(tvals), jnp.asarray(x),
                                   br=256, bc=512, bn=128, interpret=True))
    np.testing.assert_allclose(y, y_ref, rtol=2e-4, atol=2e-4)


@given(
    seed=st.integers(0, 10_000),
    W=st.integers(1, 16),
    density=st.floats(0.1, 1.0),
    br=st.sampled_from([8, 32, 64, 256]),
    bc=st.sampled_from([256, 512, 1024]),
)
@settings(max_examples=12, deadline=None)
def test_build_tiles_partition_properties(seed, W, density, br, bc):
    """Tile-format invariants over random (W, density, br, bc):

    * every stored nonzero lands in exactly one tile slot, at its
      original (row, global column, value) — the (row, col, val)
      multisets of the ELL block and the tile batch are equal;
    * every padded slot holds value exactly 0 at a tile-local column
      inside [0, bc) — masked slots contribute a bit-neutral ``+ 0.0``;
    * the tiled contraction is *bit-identical* to the jnp scan reference
      (not just close): the tiles preserve the slot accumulation order.
    """
    rng = np.random.default_rng(seed)
    R, Rx, nb = 256, 2048, 8
    cols = rng.integers(0, Rx, size=(R, W)).astype(np.int32)
    vals = rng.standard_normal((R, W))
    vals[rng.random((R, W)) >= density] = 0.0
    x = rng.standard_normal((Rx, nb))
    tile_cb, tcols, tvals = build_tiles(cols, vals, Rx, br=br, bc=bc)
    RB, T = tile_cb.shape
    got = []
    for rb in range(RB):
        for t in range(T):
            cb = int(tile_cb[rb, t])
            tc, tv = tcols[rb, t], tvals[rb, t]
            assert ((tc >= 0) & (tc < bc)).all()  # tile-local columns
            rr, ww = np.nonzero(tv != 0)
            got += [(rb * br + int(r), cb * bc + int(tc[r, w]),
                     float(tv[r, w])) for r, w in zip(rr, ww)]
    rr, ww = np.nonzero(vals != 0)
    want = [(int(r), int(cols[r, w]), float(vals[r, w]))
            for r, w in zip(rr, ww)]
    assert sorted(got) == sorted(want)
    y_ref = np.asarray(ref.ell_spmv_ref(jnp.asarray(cols), jnp.asarray(vals),
                                        jnp.asarray(x)))
    y = np.asarray(ell_gather_spmv(jnp.asarray(tile_cb), jnp.asarray(tcols),
                                   jnp.asarray(tvals), jnp.asarray(x),
                                   br=br, bc=bc, bn=nb, interpret=True))
    assert np.array_equal(y, y_ref)


def test_ell_spmv_tiled_threads_accumulator_bit_identically():
    """The y0 operand of the tile kernel prepends the accumulator to the
    per-element addition chain — bit-identical to threading the same
    accumulator through the scan reference (the split-phase engines'
    local-then-halo order depends on this)."""
    rng = np.random.default_rng(11)
    R, Rx, nb, br, bc = 64, 512, 8, 8, 256
    cols = rng.integers(0, Rx, size=(R, 5)).astype(np.int32)
    vals = rng.standard_normal((R, 5))
    x = rng.standard_normal((Rx, nb))
    y0 = rng.standard_normal((R, nb))
    tile_cb, tcols, tvals = build_tiles(cols, vals, Rx, br=br, bc=bc)
    y_ref = np.asarray(ref.ell_spmv_acc_ref(jnp.asarray(y0),
                                            jnp.asarray(cols),
                                            jnp.asarray(vals),
                                            jnp.asarray(x)))
    y = np.asarray(ops.ell_spmv_tiled(jnp.asarray(tile_cb),
                                      jnp.asarray(tcols),
                                      jnp.asarray(tvals), jnp.asarray(x),
                                      y0=jnp.asarray(y0), br=br, bc=bc,
                                      interpret=True))
    assert np.array_equal(y, y_ref)


def test_plan_ell_tiles_fallback_seams():
    """plan_ell_tiles returns None exactly at its documented refusal
    seams — abstract operands (the dryrun surrogate), non-float dtypes,
    empty blocks, and rows no br candidate divides — and a real plan
    round-trips through ``.arrays()``."""
    rng = np.random.default_rng(0)
    P, R, W, Rx = 2, 64, 3, 256
    cols = rng.integers(0, Rx, size=(P, R, W)).astype(np.int32)
    vals = rng.standard_normal((P, R, W))
    plan = ops.plan_ell_tiles(cols, vals, Rx)
    assert plan is not None and plan.br in ops.ELL_BR_CANDIDATES
    assert len(plan.arrays()) == 3
    # abstract operands (ShapeDtypeStruct = the dryrun surrogate seam)
    abs_cols = jax.ShapeDtypeStruct(cols.shape, cols.dtype)
    assert ops.plan_ell_tiles(abs_cols, vals, Rx) is None
    assert ops.plan_ell_tiles(cols, jax.ShapeDtypeStruct(
        vals.shape, vals.dtype), Rx) is None
    # non-real-float values (complex ELL blocks keep the jnp path)
    assert ops.plan_ell_tiles(cols, vals.astype(np.complex128), Rx) is None
    # empty block and ragged rows
    assert ops.plan_ell_tiles(cols[:, :, :0], vals[:, :, :0], Rx) is None
    assert ops.plan_ell_tiles(cols[:, :60], vals[:, :60], Rx) is None  # 60: no br
    # a tracer is not concrete either (jit-staged operator arrays)
    assert jax.jit(lambda c: ops.plan_ell_tiles(c, vals, Rx) is None)(cols)


def test_ell_spmv_tiled_ragged_nb_falls_back_to_ref(monkeypatch):
    """On the hardware path (interpret=False) a vector count with no
    kernel lane block (nb=5), or a float64 operand, is refused with
    KernelRefused before the kernel is called — the scan is never
    substituted in silence. Interpret mode has bn=1, so the same
    operands run the kernel there, bit-identical to the scan."""
    rng = np.random.default_rng(1)
    R, Rx, nb = 64, 512, 5
    cols = rng.integers(0, Rx, size=(R, 4)).astype(np.int32)
    vals = rng.standard_normal((R, 4))
    x = rng.standard_normal((Rx, nb))
    tile_cb, tcols, tvals = build_tiles(cols, vals, Rx, br=8, bc=256)
    y = np.asarray(ops.ell_spmv_tiled(tile_cb, tcols, tvals,
                                      jnp.asarray(x), br=8, bc=256,
                                      interpret=True))
    y_ref = np.asarray(ref.ell_spmv_ref(jnp.asarray(cols), jnp.asarray(vals),
                                        jnp.asarray(x)))
    assert np.array_equal(y, y_ref)

    def boom(*a, **k):
        raise AssertionError("kernel must not be called on a refused seam")

    monkeypatch.setattr(ops, "ell_gather_spmv", boom)
    x32 = jnp.asarray(x, jnp.float32)
    with pytest.raises(ops.KernelRefused, match="nb=5"):
        ops.ell_spmv_tiled(tile_cb, tcols, tvals.astype(np.float32), x32,
                           br=8, bc=256, interpret=False)
    with pytest.raises(ops.KernelRefused, match="float64"):
        ops.ell_spmv_tiled(tile_cb, tcols, tvals, jnp.asarray(x),
                           br=8, bc=256, interpret=False)


def test_cheb_dia_fallback_seams_never_touch_kernel(monkeypatch):
    """Every documented ref-fallback seam of ops.cheb_dia — ragged R
    (no br), x rows not a multiple of br, force_ref — takes the
    reference path without invoking the Pallas kernel, pinned by making
    the kernel raise. On the hardware path the same shapes, a ragged nb
    and 64-bit operands raise KernelRefused instead of falling back."""
    def boom(*a, **k):
        raise AssertionError("kernel must not be called on a fallback seam")

    monkeypatch.setattr(ops, "_cheb_dia_kernel", boom)
    rng = np.random.default_rng(2)

    def case(R, nb, Rx, **kw):
        offsets = (-1, 0, 1)
        dvals = _mk_dia(rng, R, offsets, np.float64)
        x = rng.standard_normal((Rx, nb))
        w1 = rng.standard_normal((R, nb))
        w2 = rng.standard_normal((R, nb))
        y = np.asarray(ops.cheb_dia(offsets, jnp.asarray(dvals),
                                    jnp.asarray(x), jnp.asarray(w1),
                                    jnp.asarray(w2), 0.9, -0.1, **kw))
        y_ref = np.asarray(ref.cheb_dia_ref(offsets, dvals, x, w1, w2,
                                            0.9, -0.1))
        assert np.array_equal(y, y_ref), (R, nb, Rx, kw)

    case(100, 128, 100, interpret=True)   # ragged R: no br divides 100
    case(512, 128, 700, interpret=True)   # x rows not a multiple of br=512
    case(128, 128, 128, interpret=True, force_ref=True)

    def hw(R, nb, Rx, dtype):
        offsets = (-1, 0, 1)
        ops.cheb_dia(offsets, jnp.asarray(_mk_dia(rng, R, offsets, dtype)),
                     jnp.zeros((Rx, nb), dtype), jnp.zeros((R, nb), dtype),
                     jnp.zeros((R, nb), dtype), 0.9, -0.1, interpret=False)

    with pytest.raises(ops.KernelRefused, match="nb=100"):
        hw(128, 100, 128, np.float32)     # ragged nb on the hardware path
    with pytest.raises(ops.KernelRefused, match="R=100"):
        hw(100, 128, 100, np.float32)     # ragged R on the hardware path
    with pytest.raises(ops.KernelRefused, match="float64"):
        hw(128, 128, 128, np.float64)
    with pytest.raises(ops.KernelRefused, match="complex128"):
        hw(128, 128, 128, np.complex128)


def test_cheb_dia_complex_fallback_decides_once(monkeypatch):
    """A complex operand on a fallback seam runs ONE complex reference
    call — the ref-vs-kernel decision precedes the 4-plane real
    decomposition (the regression this pins: deciding per real plane ran
    four reference calls on every fallback)."""
    calls = []
    real_ref = ref.cheb_dia_ref

    def counting_ref(*a, **k):
        calls.append(a)
        return real_ref(*a, **k)

    monkeypatch.setattr(ref, "cheb_dia_ref", counting_ref)
    rng = np.random.default_rng(5)
    R, nb = 128, 128
    offsets = (-1, 0, 1)
    dv = (rng.standard_normal((3, R))
          + 1j * rng.standard_normal((3, R))).astype(np.complex128)
    x = (rng.standard_normal((R, nb))
         + 1j * rng.standard_normal((R, nb))).astype(np.complex128)
    y = np.asarray(ops.cheb_dia(offsets, jnp.asarray(dv), jnp.asarray(x),
                                jnp.asarray(x * 0.2), jnp.asarray(x * 0.1),
                                0.8, 0.3, interpret=True, force_ref=True))
    assert len(calls) == 1  # one complex ref call, not four real planes
    y_ref = np.asarray(real_ref(offsets, dv, x, x * 0.2, x * 0.1, 0.8, 0.3))
    assert np.array_equal(y, y_ref)


def test_pick_block_and_too_small():
    """_pick_block returns the first dividing candidate or None; the
    interpret-mode _too_small guard trips exactly below 8 rows or an
    empty vector block."""
    assert ops._pick_block(256, (256, 128)) == 256
    assert ops._pick_block(384, (256, 128)) == 128
    assert ops._pick_block(100, (256, 128, 64, 32, 16, 8)) is None
    assert ops._pick_block(100, (256, 128, 64, 32, 16, 8, 4, 2, 1)) == 4
    w = np.zeros((4, 8))
    assert ops._too_small(np.zeros((1, 4)), w)       # R < 8
    assert ops._too_small(np.zeros((1, 16)), np.zeros((16, 0)))  # nb < 1
    assert not ops._too_small(np.zeros((1, 16)), np.zeros((16, 8)))


def test_dia_matches_matrix_family():
    """DIA kernel on the actual Exciton stencil == CSR matvec."""
    from repro.matrices import Exciton
    from repro.matrices.matfree import dia_from_family

    fam = Exciton(L=2)  # D = 375
    offsets, dvals, R = dia_from_family(fam, pad_to=128)
    csr = fam.build_csr()
    rng = np.random.default_rng(0)
    nb = 128
    x = (rng.standard_normal((R, nb)) + 1j * rng.standard_normal((R, nb))).astype(np.complex64)
    x[fam.D:] = 0
    w1 = np.zeros_like(x)
    w2 = np.zeros_like(x)
    y = np.asarray(ops.cheb_dia(tuple(offsets), jnp.asarray(dvals), jnp.asarray(x),
                                jnp.asarray(w1), jnp.asarray(w2), 0.5, 0.0,
                                interpret=True))
    y_ref = csr.matvec(np.asarray(x)[: fam.D])
    np.testing.assert_allclose(y[: fam.D], y_ref, rtol=2e-4, atol=2e-4)
