"""Moonlight's block against plain references: MLA with a full-rank query,
sigmoid routing with a correction bias, the bias's own update, dropless
routing over the held experts, and one chip's share of a layer."""
import dataclasses as dc
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.launch import mesh as mesh_lib
from repro.launch import train
from repro.distributed import sharding
from repro.models import mla as mla_lib
from repro.models import moe as moe_lib
from repro.models import params as P
from repro.models import transformer


def _cfg(**moe):
    cfg = base.get("moonlight-16b-a3b", smoke=True)
    return dc.replace(cfg, moe=dc.replace(cfg.moe, **moe)) if moe else cfg


def _f32(tree):
    return jax.tree.map(lambda t: np.asarray(t, np.float32), tree)


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (np.arange(half) / half)
    ang = pos[:, None] * inv[None, :]
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rms(x, scale, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * scale


# -- MLA without q_lora ---------------------------------------------------------

def _mla_plain(p, x, cfg):
    """One sequence (S, D): full-rank query, latent K/V, causal softmax."""
    m, s = cfg.mla, x.shape[0]
    pos = np.arange(s, dtype=np.float64)
    q = np.einsum("sd,dhk->shk", x, p["wq"])
    q = np.concatenate([q[..., :m.qk_nope],
                        _rope(q[..., m.qk_nope:], pos, cfg.rope_theta)], -1)
    kv = x @ p["wkv_a"]
    c = _rms(kv[:, :m.kv_lora], p["kv_norm"], cfg.norm_eps)
    k_rope = _rope(kv[:, None, m.kv_lora:], pos, cfg.rope_theta)
    k = np.concatenate([np.einsum("sl,lhk->shk", c, p["wk_b"]),
                        np.broadcast_to(k_rope, (s, cfg.n_heads, m.qk_rope))],
                       -1)
    v = np.einsum("sl,lhk->shk", c, p["wv_b"])
    sc = np.einsum("shk,thk->hst", q, k) / math.sqrt(q.shape[-1])
    sc = np.where(np.tril(np.ones((s, s), bool)), sc, -np.inf)
    pr = np.exp(sc - sc.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    return np.einsum("shk,hkd->sd", np.einsum("hst,thk->shk", pr, v), p["wo"])


def test_mla_full_rank_query_train_and_decode():
    cfg = _cfg()
    assert cfg.mla.q_lora is None
    p = jax.tree.map(lambda t: t.astype(jnp.float32),
                     P.materialize(jax.random.PRNGKey(3), mla_lib.mla_spec(cfg)))
    assert "wq" in p and "wq_a" not in p
    b, s = 2, 32
    x = jax.random.normal(jax.random.PRNGKey(4), (b, s, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    out = np.asarray(mla_lib.mla_attention(p, x, cfg, pos, q_chunk=8,
                                           kv_chunk=8))
    pn = _f32(p)
    want = np.stack([_mla_plain(pn, np.asarray(x[i], np.float64), cfg)
                     for i in range(b)])
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-4)
    # absorbed decode of the last token over the latent cache
    ckv, krope = mla_lib.mla_new_cache_entry(p, x, cfg, pos)
    dec = mla_lib.mla_decode(p, x[:, -1:], cfg, ckv, krope,
                             jnp.full((b,), s, jnp.int32))
    np.testing.assert_allclose(np.asarray(dec[:, 0]), want[:, -1],
                               rtol=2e-4, atol=2e-4)


# -- routing --------------------------------------------------------------------

def test_sigmoid_selection_with_bias_and_scaled_weights():
    cfg = _cfg()
    m = cfg.moe
    rng = np.random.default_rng(0)
    router = rng.standard_normal((cfg.d_model, m.n_experts)).astype(np.float32)
    bias = rng.standard_normal(m.n_experts).astype(np.float32) * 0.5
    x = rng.standard_normal((5, 7, cfg.d_model)).astype(np.float32)
    scores, top_e, top_w = moe_lib.select(jnp.asarray(router),
                                          jnp.asarray(bias), jnp.asarray(x), m)
    s = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ router)))
    np.testing.assert_allclose(np.asarray(scores), s, rtol=1e-5, atol=1e-6)
    want_e = np.argsort(-(s + bias), axis=-1, kind="stable")[..., :m.top_k]
    np.testing.assert_array_equal(np.asarray(top_e), want_e)
    w = np.take_along_axis(s, want_e, -1)
    w = w / w.sum(-1, keepdims=True) * 2.446
    np.testing.assert_allclose(np.asarray(top_w), w, rtol=1e-5)
    # the bias picks experts and never weighs them
    assert not np.array_equal(
        np.asarray(moe_lib.select(jnp.asarray(router), None,
                                  jnp.asarray(x), m)[1]), want_e)


def _batch(cfg, b=2, s=16, seed=1):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (b, s + 1), 0,
                              cfg.vocab_size)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def test_bias_takes_no_gradient_no_decay_and_moves_by_load():
    cfg = _cfg()
    gamma = cfg.moe.bias_update_rate
    mesh = mesh_lib.make_mesh((1, 1), ("data", "model"))
    shape = base.ShapeConfig("t", "train", 16, 2)
    batch = _batch(cfg)
    with sharding.mesh_context(mesh):
        state = train.init_state(cfg, jax.random.PRNGKey(0),
                                 train.StepConfig().opt)
        bias0 = jax.random.normal(jax.random.PRNGKey(5),
                                  state["params"]["moe_blocks"]["moe"]
                                  ["e_bias"].shape) * 0.01
        state["params"]["moe_blocks"]["moe"]["e_bias"] = bias0
        grads = jax.grad(lambda p: transformer.train_loss(p, cfg, batch))(
            state["params"])
        assert not np.any(np.asarray(grads["moe_blocks"]["moe"]["e_bias"]))
        _, stats = transformer.train_loss_and_stats(state["params"], cfg,
                                                    batch)
        step = jax.jit(train.make_train_step(cfg, mesh, train.StepConfig(),
                                             shape=shape))
        # step 1 of the schedule: a nonzero learning rate, so decay would show
        state["count"] = jnp.int32(50)
        new, metrics = step(state, batch)
    load = np.asarray(stats["load"])                     # (layers, E)
    assert load.shape == bias0.shape
    np.testing.assert_array_equal(load.sum(-1),
                                  2 * 16 * cfg.moe.top_k)
    moved = np.asarray(new["params"]["moe_blocks"]["moe"]["e_bias"]) \
        - np.asarray(bias0)
    want = gamma * np.sign(load.mean(-1, keepdims=True) - load)
    np.testing.assert_allclose(moved, want, atol=1e-7)
    assert not np.any(np.asarray(new["mu"]["moe_blocks"]["moe"]["e_bias"]))
    counters = jax.device_get(metrics["moe"])
    assert counters["routed_pairs"] == 2 * 2 * 16 * cfg.moe.top_k
    assert counters["max_abs_bias"] == pytest.approx(
        np.abs(np.asarray(bias0) + want).max(), rel=1e-6)


# -- dropless over the held experts -------------------------------------------

def _layer_loop(p, x2d, cfg, first=0):
    """Per-token loop: the held experts' weighted outputs for each token."""
    m = cfg.moe
    held = p["w_gate"].shape[0]
    bias = p.get("e_bias", np.zeros(m.n_experts, np.float32))
    out = np.zeros_like(x2d)
    for t, xt in enumerate(x2d):
        s = 1.0 / (1.0 + np.exp(-(xt @ p["router"])))
        top = np.argsort(-(s + bias), kind="stable")[:m.top_k]
        w = s[top] / s[top].sum() * m.routed_scale
        for e, we in zip(top, w):
            if first <= e < first + held:
                i = e - first
                h = _silu(xt @ p["w_gate"][i]) * (xt @ p["w_up"][i])
                out[t] += we * (h @ p["w_down"][i])
    return out


def _moe_params(cfg, seed=7):
    p = P.materialize(jax.random.PRNGKey(seed), moe_lib.moe_spec(cfg))
    return jax.tree.map(lambda t: t.astype(jnp.float32), p)


def test_dropless_planted_router_drops_nothing():
    """A router that sends every token to expert 3: the capacity path would
    keep ~cf x T x k / E of them; the dropless path computes all."""
    cfg = _cfg()
    p = _moe_params(cfg)
    p["e_bias"] = jnp.zeros(cfg.moe.n_experts).at[3].set(100.0)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 16, cfg.d_model))
    y, stats = moe_lib.moe_ffn(p, x, cfg)
    assert float(stats["routed"]) == 2 * 16 * cfg.moe.top_k
    assert float(stats["load"][3]) == 32
    assert float(stats["max_load"]) == pytest.approx(cfg.moe.n_experts
                                                     / cfg.moe.top_k)
    pn = _f32(p)
    x2d = np.asarray(x, np.float32).reshape(-1, cfg.d_model)
    want = _layer_loop(pn, x2d, cfg)
    h = _silu(x2d @ pn["shared_gate"]) * (x2d @ pn["shared_up"])
    want += h @ pn["shared_down"]
    np.testing.assert_allclose(np.asarray(y).reshape(want.shape), want,
                               rtol=2e-4, atol=2e-4)


def test_eight_shares_add_up_to_the_uncut_layer():
    """Guide section 4: each of 8 chips holds 2 of 16 experts; their routed
    parts, plus the shared experts once, equal the whole layer."""
    cfg = _cfg(n_experts=16, top_k=4)
    whole = _moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 16, cfg.d_model))
    y, _ = moe_lib.moe_ffn(whole, x, cfg)
    shared = moe_lib.shared_experts(whole, x)
    x2d = x.reshape(-1, cfg.d_model)
    _, top_e, top_w = moe_lib.select(whole["router"], whole["e_bias"], x2d,
                                     cfg.moe)
    total = shared
    for chip in range(8):
        share = dict(whole, **{k: whole[k][2 * chip:2 * chip + 2]
                               for k in ("w_gate", "w_up", "w_down")})
        part, sizes = moe_lib.routed_experts(share, x2d, top_e, top_w,
                                             first=2 * chip)
        np.testing.assert_allclose(
            np.asarray(part), _layer_loop(_f32(share), np.asarray(x2d), cfg,
                                          first=2 * chip),
            rtol=2e-4, atol=2e-4)
        total = total + part.reshape(x.shape)
    np.testing.assert_allclose(np.asarray(total), np.asarray(y),
                               rtol=1e-5, atol=1e-5)
    # and the held-share configuration builds exactly one share's weights
    cut = base.chip_share(dc.replace(base.get("moonlight-16b-a3b", smoke=True),
                                     moe=cfg.moe), n_layers=3, n_held=2,
                          vocab_size=257)
    assert moe_lib.moe_spec(cut)["w_gate"].shape[0] == 2
    assert moe_lib.moe_spec(cut)["router"].shape[1] == 16


def test_chip_share_counts_its_active_params():
    cfg = base.get("moonlight-16b-a3b")
    cut = base.chip_share(cfg, n_layers=5, n_held=8, vocab_size=20480)
    assert cut.n_params() == 568_484_608
    per_expert = 3 * 2048 * 1408
    assert cut.n_params() - cut.n_active_params() == round(
        (8 - 6 * 8 / 64) * per_expert * 4)
    with pytest.raises(ValueError):
        base.chip_share(cfg, n_layers=1, n_held=8, vocab_size=20480)


def test_rows_outside_every_group_never_reach_the_result(monkeypatch):
    """A grouped matmul leaves the rows past its groups unwritten (on the
    TPU they hold whatever the buffer held), in its output and in its
    input's gradient. Planted as NaN, they change neither the layer nor
    any gradient."""
    cfg = _cfg()
    p = _moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 16, cfg.d_model))

    def loss(p, x):
        y, stats = moe_lib.moe_ffn(p, x, cfg)
        return jnp.sum(y ** 2) + stats["loss"]

    want = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    real = jax.lax.ragged_dot

    def garbage(lhs, rhs, group_sizes, **kw):
        rows = (jnp.arange(lhs.shape[0]) >= jnp.sum(group_sizes))[:, None]
        fill = lambda t: jnp.where(rows, jnp.nan, t)   # noqa: E731

        @jax.custom_vjp
        def f(lhs, rhs):
            return fill(real(lhs, rhs, group_sizes, **kw))

        def fwd(lhs, rhs):
            return f(lhs, rhs), (lhs, rhs)

        def bwd(res, g):
            _, vjp = jax.vjp(lambda a, b: real(a, b, group_sizes, **kw), *res)
            d_lhs, d_rhs = vjp(g)
            return fill(d_lhs), d_rhs

        f.defvjp(fwd, bwd)
        return f(lhs, rhs)

    monkeypatch.setattr(jax.lax, "ragged_dot", garbage)
    got = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
