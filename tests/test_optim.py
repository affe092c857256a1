"""Optimizer + schedules + gradient compression numerics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st   # optional-hypothesis shim

from repro import optim
from repro.optim import grad_compress, schedules


def test_adamw_converges_on_quadratic():
    cfg = optim.AdamWConfig(lr=0.1, weight_decay=0.0, bf16_moments=False,
                            grad_clip=0)
    params = {"x": jnp.asarray([5.0, -3.0])}
    st_ = optim.init(params, cfg)
    for _ in range(200):
        g = jax.tree.map(lambda p: 2 * p, params)   # d/dx x^2
        params, st_ = optim.update(g, st_, params, cfg)
    assert float(jnp.max(jnp.abs(params["x"]))) < 0.05


def test_master_weights_allow_tiny_updates():
    cfg = optim.AdamWConfig(lr=1e-4, weight_decay=0.0, master_weights=True)
    params = {"x": jnp.ones((4,), jnp.bfloat16)}
    st_ = optim.init(params, cfg)
    g = {"x": jnp.ones((4,), jnp.bfloat16)}
    for _ in range(100):
        params, st_ = optim.update(g, st_, params, cfg)
    # master accumulates sub-bf16 deltas; params eventually move
    assert float(st_.master["x"][0]) < 1.0 - 1e-3


def test_grad_clip():
    cfg = optim.AdamWConfig(grad_clip=1.0, bf16_moments=False)
    g = {"x": jnp.asarray([100.0, 0.0])}
    assert float(optim.adamw.global_norm(g)) == pytest.approx(100.0)


def test_schedules_shapes():
    lr0 = float(schedules.warmup_cosine(0, peak=1.0, warmup=10, total=100))
    lr_w = float(schedules.warmup_cosine(10, peak=1.0, warmup=10, total=100))
    lr_end = float(schedules.warmup_cosine(100, peak=1.0, warmup=10,
                                           total=100))
    assert lr0 == 0.0 and lr_w == pytest.approx(1.0)
    assert lr_end == pytest.approx(0.1, abs=1e-5)   # floor_frac


# -- gradient compression -----------------------------------------------------

def test_int8_quantization_error_bounded(rng):
    x = jnp.asarray(rng.standard_normal(4096).astype(np.float32))
    amax = float(jnp.max(jnp.abs(x)))
    scale = amax / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    err = float(jnp.max(jnp.abs(q.astype(jnp.float32) * scale - x)))
    assert err <= scale / 2 + 1e-7


def test_int8_ring_mean_single_device_mesh():
    """n=1 ring degenerates to quantize+dequantize (shard_map on 1 device)."""
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("pod",))
    x = jnp.asarray(np.random.default_rng(0)
                    .standard_normal(256).astype(np.float32))

    from repro.distributed import sharding
    f = sharding.shard_map(
        lambda v: grad_compress.int8_ring_mean(v, "pod", 1),
        mesh, in_specs=jax.sharding.PartitionSpec(),
        out_specs=jax.sharding.PartitionSpec(), check_vma=False)
    with sharding.mesh_context(mesh):
        out = f(x)
    amax = float(jnp.max(jnp.abs(x)))
    assert float(jnp.max(jnp.abs(out - x))) <= amax / 127.0


def test_error_feedback_invariant(rng):
    """g_pre == reduced + residual (what EF carries is exactly what was lost)."""
    g = {"w": jnp.asarray(rng.standard_normal(128).astype(np.float32))}
    res = grad_compress.ef_init(g)
    g_pre = grad_compress.ef_pre(g, res)
    # fake a lossy reduction: quantize to 1 decimal
    reduced = jax.tree.map(lambda x: jnp.round(x, 1), g_pre)
    new_res = grad_compress.ef_post(g_pre, reduced)
    recon = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                         reduced, new_res)
    np.testing.assert_allclose(np.asarray(recon["w"]),
                               np.asarray(g_pre["w"]), atol=0.01)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 100), n=st.integers(2, 8))
def test_int8_ring_math_property(seed, n):
    """Pure-python model of the ring: mean of quantized == quantized mean."""
    r = np.random.default_rng(seed)
    xs = r.standard_normal((n, 64)).astype(np.float32)
    amax = np.abs(xs).max()
    scale = max(amax, 1e-30) / 127.0
    qs = np.clip(np.round(xs / scale), -127, 127)
    ring_mean = qs.sum(0) * scale / n
    true_mean = xs.mean(0)
    assert np.max(np.abs(ring_mean - true_mean)) <= scale / 2 + 1e-6
