"""AdamW (+ quantized moments) and gradient compression."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from tests._hypothesis_compat import given, settings, st

from repro.optim import adamw


def _toy_params(key):
    k1, k2 = jax.random.split(key)
    return {"w": jax.random.normal(k1, (64, 32)), "b": jax.random.normal(k2, (32,))}


def _run_steps(moment_dtype, n=120):
    cfg = adamw.AdamWConfig(lr=5e-2, moment_dtype=moment_dtype, grad_clip=1e3,
                            warmup_steps=2, total_steps=n, weight_decay=0.0)
    params = _toy_params(jax.random.PRNGKey(0))
    target = jax.tree.map(lambda p: p * 0.0 + 1.0, params)
    state = adamw.init_state(cfg, params)

    @jax.jit
    def step(params, state):
        def loss(p):
            return sum(jnp.sum((a - b) ** 2) for a, b in
                       zip(jax.tree.leaves(p), jax.tree.leaves(target)))

        l, g = jax.value_and_grad(loss)(params)
        params, state, m = adamw.apply_updates(cfg, params, g, state)
        return params, state, l

    for _ in range(n):
        params, state, l = step(params, state)
    return params, float(l)


def test_adamw_descends():
    _, l32 = _run_steps("float32")
    assert l32 < 400.0  # started at ~4100 (sum of squares of N(0,1)-1)


@pytest.mark.parametrize("dt", ["bfloat16", "int8"])
def test_quantized_moments_track_fp32(dt):
    p32, l32 = _run_steps("float32")
    pq, lq = _run_steps(dt)
    # quantized-state training follows the fp32 trajectory and converges
    assert lq < 2.5 * l32 + 50.0, (dt, lq, l32)
    for a, b in zip(jax.tree.leaves(p32), jax.tree.leaves(pq)):
        err = np.abs(np.asarray(a) - np.asarray(b)).max()
        assert err < 0.5, (dt, err)


@given(seed=st.integers(0, 1000), scale=st.floats(1e-6, 1e3))
@settings(max_examples=20, deadline=None)
def test_q8_roundtrip_error_bound(seed, scale):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(1000) * scale, jnp.float32)
    codes, scales = adamw._q8_encode(x)
    y = adamw._q8_decode(codes, scales, x.shape)
    # block-quantization error <= half step of the block max
    blockmax = np.abs(np.asarray(x)).max()
    assert np.abs(np.asarray(y) - np.asarray(x)).max() <= blockmax / 127.0 + 1e-12


def test_grad_clip_applied():
    cfg = adamw.AdamWConfig(grad_clip=1e-3, moment_dtype="float32")
    params = {"w": jnp.ones((8,))}
    g = {"w": jnp.full((8,), 1e3)}
    st_ = adamw.init_state(cfg, params)
    p2, _, m = adamw.apply_updates(cfg, params, g, st_)
    assert float(m["grad_norm"]) > 1e3  # reported raw norm
    assert np.abs(np.asarray(p2["w"]) - np.asarray(params["w"])).max() < 0.1


def test_cross_pod_compression_error_feedback():
    """int8 cross-pod reduce == exact mean within quantization error, and
    the error-feedback residual carries the difference."""
    out = __import__("tests.conftest", fromlist=["run_distributed"]).run_distributed(
        """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P, NamedSharding
from repro.optim.compress import make_cross_pod_reduce
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,) * 3)
spec = {"w": P(None, "model")}
red = make_cross_pod_reduce(mesh, spec, enabled=True)
rng = np.random.default_rng(0)
g_global = rng.standard_normal((2, 16, 8)).astype(np.float32)  # per-pod grads
# build a pod-sharded array: dim 0 = pod-dependent value
with mesh:
    garr = jax.device_put(jnp.asarray(g_global.reshape(2*16, 8)),
                          NamedSharding(mesh, P("pod", "model")))
    # reinterpret: each pod holds [16,8] distinct grads
    g = {"w": garr.reshape(2, 16, 8)[0] * 0}  # placeholder shape [16,8]
    # simpler: run shard_map directly via the reduce on a pod-varying array
    def mk(x):
        return jax.device_put(jnp.asarray(x), NamedSharding(mesh, P(None, "model")))
    # emulate pod-varying values with an explicit pod-major concat trick:
    from jax import shard_map
    def podval(_):
        i = jax.lax.axis_index("pod").astype(jnp.float32)
        return jnp.full((16, 8), 1.0 + i)
    pv = shard_map(podval, mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False)(jnp.zeros(()))
    g = {"w": pv}
    e = {"w": jnp.zeros((16, 8))}
    (gm, em) = red(g, e)
    gm = np.asarray(jax.device_get(gm["w"]))
    # mean of pods holding 1.0 and 2.0 is 1.5 everywhere
    assert np.allclose(gm, 1.5, atol=2.5/127 + 1e-6), gm[:2,:2]
print("COMPRESS OK")
""", n_devices=8)
    assert "COMPRESS OK" in out
