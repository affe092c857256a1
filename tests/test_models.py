"""Model substrate: per-arch smoke steps + mixer-vs-oracle checks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.models import attention as attn_lib
from repro.models import params as P
from repro.models import ssm as ssm_lib
from repro.models import transformer
from repro.models import xlstm as xlstm_lib
from repro.models import moe as moe_lib


@pytest.mark.parametrize("arch", base.ARCH_IDS)
def test_arch_smoke_train_step(arch):
    """Reduced config: one forward/train step, output shapes + no NaNs."""
    cfg = base.get(arch, smoke=True)
    prm = P.materialize(jax.random.PRNGKey(0), transformer.param_spec(cfg))
    B, S = 2, 32
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    if cfg.frontend:
        batch["prefix"] = jnp.zeros((B, cfg.frontend_tokens, cfg.d_model),
                                    jnp.bfloat16)
    logits, aux, mask = transformer.forward(prm, cfg, batch["tokens"],
                                            prefix_embeds=batch.get("prefix"))
    s_total = S + (cfg.frontend_tokens if cfg.frontend else 0)
    assert logits.shape == (B, s_total, cfg.padded_vocab)
    assert not bool(jnp.isnan(logits.astype(jnp.float32)).any())
    loss, grads = jax.value_and_grad(
        lambda p: transformer.train_loss(p, cfg, batch))(prm)
    assert np.isfinite(float(loss))
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in jax.tree.leaves(grads)))
    assert np.isfinite(float(gn)) and float(gn) > 0


@pytest.mark.parametrize("arch", base.ARCH_IDS)
def test_full_config_numbers_match_brief(arch):
    """The FULL configs carry the exact published numbers."""
    cfg = base.get(arch)
    expected = {
        "granite-3-2b": (40, 2048, 32, 8, 8192, 49155),
        "qwen3-4b": (36, 2560, 32, 8, 9728, 151936),
        "smollm-135m": (30, 576, 9, 3, 1536, 49152),
        "qwen1.5-110b": (80, 8192, 64, 8, 49152, 152064),
        "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
        "deepseek-v3-671b": (61, 7168, 128, 128, 2048, 129280),
        "moonlight-16b-a3b": (27, 2048, 16, 16, 1408, 163840),
        "internvl2-26b": (48, 6144, 48, 8, 16384, 92553),
        "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
        "xlstm-1.3b": (48, 2048, 4, 4, 0, 50304),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
           cfg.vocab_size)
    assert got == expected
    if arch == "deepseek-v3-671b":
        assert cfg.moe.n_experts == 256 and cfg.moe.top_k == 8
        assert cfg.mla is not None and cfg.mtp_weight > 0
    if arch == "moonlight-16b-a3b":
        m = cfg.moe
        assert (m.n_experts, m.top_k, m.d_expert, m.n_shared_experts,
                m.first_dense, m.dense_d_ff, m.held) == (
                    64, 6, 1408, 2, 1, 11264, 64)
        assert (m.routed_scale, m.capacity_factor) == (2.446, None)
        assert m.bias_update_rate > 0 and m.seq_aux_weight > 0
        assert cfg.mla == base.MLAConfig(q_lora=None, kv_lora=512,
                                         qk_nope=128, qk_rope=64, v_head=128)
        assert (cfg.rope_theta, cfg.norm_eps, cfg.tie_embeddings) == (
            50000.0, 1e-5, False)
    if arch == "qwen3-4b":
        assert cfg.qk_norm
    if arch == "qwen1.5-110b":
        assert cfg.qkv_bias
    if arch == "hymba-1.5b":
        assert cfg.ssm is not None and cfg.ssm.d_state == 16
    if arch == "xlstm-1.3b":
        assert cfg.xlstm is not None


# -- attention ---------------------------------------------------------------

@pytest.mark.parametrize("hq,n_kv", [(8, 8), (8, 2), (9, 3)])
@pytest.mark.parametrize("window", [0, 7])
def test_flash_attention_matches_reference(hq, n_kv, window, rng):
    B, S, D = 2, 64, 16
    q = jnp.asarray(rng.standard_normal((B, S, hq, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, S, n_kv, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, S, n_kv, D)).astype(np.float32))
    out = attn_lib.flash_attention(q, k, v, causal=True, window=window,
                                   q_chunk=16, kv_chunk=16)
    ref = attn_lib.reference_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("saved_bytes", [0, 1 << 40])
def test_flash_attention_gradients_kept_or_recomputed(saved_bytes, rng,
                                                      monkeypatch):
    """Both backward paths (score blocks kept, or recomputed past
    ``SAVED_SCORES_BYTES``) give the oracle's gradients."""
    monkeypatch.setattr(attn_lib, "SAVED_SCORES_BYTES", saved_bytes)
    B, S, hq, n_kv, D = 2, 64, 4, 2, 16
    q, k, v = (jnp.asarray(rng.standard_normal((B, S, h, D)).astype(np.float32))
               for h in (hq, n_kv, n_kv))
    w = jnp.asarray(rng.standard_normal((B, S, hq, D)).astype(np.float32))
    loss = lambda f: lambda q, k, v: jnp.sum(f(q, k, v) * w)   # noqa: E731
    got = jax.grad(loss(lambda q, k, v: attn_lib.flash_attention(
        q, k, v, q_chunk=16, kv_chunk=16)), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(attn_lib.reference_attention),
                    argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_decode_attention_matches_last_row(rng):
    B, S, H, D = 2, 32, 4, 16
    q = jnp.asarray(rng.standard_normal((B, S, H, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, S, H, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, S, H, D)).astype(np.float32))
    full = attn_lib.reference_attention(q, k, v, causal=True)
    lengths = jnp.full((B,), S, jnp.int32)
    dec = attn_lib.decode_attention(q[:, -1:], k, v, lengths)
    np.testing.assert_allclose(np.asarray(dec[:, 0]), np.asarray(full[:, -1]),
                               rtol=2e-4, atol=2e-4)


# -- SSM / xLSTM oracles -------------------------------------------------------

def test_ssm_chunked_matches_stepwise():
    cfg = base.get("hymba-1.5b", smoke=True)
    spec = ssm_lib.ssm_spec(cfg)
    p = P.materialize(jax.random.PRNGKey(2), spec)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 3 * cfg.ssm.chunk, cfg.d_model),
                          jnp.float32)
    fast = ssm_lib.ssm_mixer(p, x, cfg)
    slow = ssm_lib.ssm_mixer_reference(p, x, cfg)
    np.testing.assert_allclose(np.asarray(fast), np.asarray(slow),
                               rtol=2e-3, atol=2e-3)


def test_ssm_decode_matches_parallel():
    cfg = base.get("hymba-1.5b", smoke=True)
    p = P.materialize(jax.random.PRNGKey(2), ssm_lib.ssm_spec(cfg))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 12, cfg.d_model),
                          jnp.float32)
    full = ssm_lib.ssm_mixer(p, x, cfg)
    di = ssm_lib.d_inner(cfg)
    state = {"h": jnp.zeros((2, di, cfg.ssm.d_state), jnp.float32),
             "conv": jnp.zeros((2, cfg.ssm.d_conv - 1, di), jnp.float32)}
    outs = []
    for t in range(12):
        y, state = ssm_lib.ssm_decode(p, x[:, t:t + 1], cfg, state)
        outs.append(y)
    step = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(step), np.asarray(full),
                               rtol=2e-3, atol=2e-3)


def test_mlstm_chunkwise_matches_stepwise():
    cfg = base.get("xlstm-1.3b", smoke=True)
    p = P.materialize(jax.random.PRNGKey(5), xlstm_lib.mlstm_spec(cfg))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 2 * cfg.xlstm.chunk,
                                                  cfg.d_model), jnp.float32)
    fast = xlstm_lib.mlstm_mixer(p, x, cfg)
    slow = xlstm_lib.mlstm_mixer_reference(p, x, cfg)
    np.testing.assert_allclose(np.asarray(fast), np.asarray(slow),
                               rtol=5e-3, atol=5e-3)


# -- MoE ------------------------------------------------------------------------

def test_moe_matches_reference_and_routes():
    cfg = base.get("deepseek-v3-671b", smoke=True)      # the capacity path
    p = P.materialize(jax.random.PRNGKey(7), moe_lib.moe_spec(cfg))
    # f32 routing: bf16 would flip near-tie expert choices vs the oracle
    p = jax.tree.map(lambda t: t.astype(jnp.float32), p)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 16, cfg.d_model),
                          jnp.float32)
    y, aux = moe_lib.moe_ffn(p, x, cfg)
    y_ref = moe_lib.moe_ffn_reference(p, x, cfg)
    assert y.shape == x.shape
    assert float(aux["loss"]) >= 0
    yf = np.asarray(y, dtype=np.float32).reshape(-1, cfg.d_model)
    yr = np.asarray(y_ref, dtype=np.float32).reshape(-1, cfg.d_model)
    # per-token relative error; allow a small tie-flip fraction
    err = (np.linalg.norm(yf - yr, axis=1)
           / np.maximum(np.linalg.norm(yr, axis=1), 1e-6))
    assert np.mean(err < 0.05) >= 0.9, f"token match rate {np.mean(err<0.05)}"
