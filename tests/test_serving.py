"""Serving: prefill+decode ≡ full forward; ring SWA; batched engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.models import attention as attn_lib
from repro.models import params as P
from repro.models import transformer
from repro.serving import engine as E
from repro.serving import kvcache

DECODE_ARCHS = ["smollm-135m", "qwen1.5-110b", "deepseek-v3-671b",
                "moonlight-16b-a3b", "hymba-1.5b", "xlstm-1.3b",
                "musicgen-medium"]


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_prefill_decode_matches_forward(arch):
    cfg = base.get(arch, smoke=True)
    prm = P.materialize(jax.random.PRNGKey(0), transformer.param_spec(cfg))
    B, S = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 2), 0,
                              cfg.vocab_size)
    ref, _, _ = transformer.forward(prm, cfg, toks)
    prefill = E.make_prefill(cfg, max_len=S + 4)
    decode = E.make_decode(cfg)
    lg_p, cache, lens = prefill(prm, toks[:, :S])
    np.testing.assert_allclose(
        np.asarray(lg_p[:, -1], np.float32),
        np.asarray(ref[:, S - 1], np.float32), rtol=0.1, atol=0.1)
    for t in range(2):
        lg_d, cache, lens = decode(prm, cache, toks[:, S + t:S + t + 1], lens)
        np.testing.assert_allclose(
            np.asarray(lg_d[:, 0], np.float32),
            np.asarray(ref[:, S + t], np.float32), rtol=0.15, atol=0.15)
    assert int(lens[0]) == S + 2


def test_swa_ring_cache_equals_full_within_window(rng):
    """A ring KV of size `window` must reproduce windowed attention exactly."""
    B, S, H, D, W = 1, 24, 2, 8, 8
    q = jnp.asarray(rng.standard_normal((B, S, H, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, S, H, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, S, H, D)).astype(np.float32))
    full = attn_lib.reference_attention(q, k, v, causal=True, window=W)
    ring_k = jnp.zeros((B, W, H, D))
    ring_v = jnp.zeros((B, W, H, D))
    for t in range(S):
        slot = t % W
        ring_k = ring_k.at[:, slot].set(k[:, t])
        ring_v = ring_v.at[:, slot].set(v[:, t])
        lengths = jnp.full((B,), t + 1, jnp.int32)
        o = attn_lib.decode_attention(q[:, t:t + 1], ring_k, ring_v, lengths,
                                      window=W, ring=True)
        np.testing.assert_allclose(np.asarray(o[:, 0]),
                                   np.asarray(full[:, t]),
                                   rtol=2e-4, atol=2e-4, err_msg=f"t={t}")


@pytest.mark.parametrize("arch", ["smollm-135m", "hymba-1.5b", "xlstm-1.3b"])
def test_cache_layout_and_bytes(arch):
    cfg = base.get(arch, smoke=True)
    cache = kvcache.init_cache(cfg, batch=2, max_len=32)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(cache))
    assert nbytes == kvcache.cache_bytes(cfg, 2, 32)
    spec = kvcache.cache_spec(cfg, 2, 32)
    assert jax.tree_util.tree_structure(spec) == \
        jax.tree_util.tree_structure(cache)


def test_mla_cache_is_compressed():
    """MLA latent cache must be ~heads*(nope+rope+v)/(kv_lora+rope) smaller."""
    cfg = base.get("deepseek-v3-671b")
    mla_bytes = kvcache.cache_bytes(cfg, 1, 1024)
    m = cfg.mla
    naive = (cfg.n_layers * 1024 *
             cfg.n_heads * (m.qk_nope + m.qk_rope + m.v_head) * 2)
    assert mla_bytes < naive / 30   # >30x reduction


def test_engine_state_version_tracks_cache_mutation():
    """The snapshot store's no-op shortcut relies on the version hint
    moving exactly when the cache does."""
    cfg = base.get("smollm-135m", smoke=True)
    prm = P.materialize(jax.random.PRNGKey(0), transformer.param_spec(cfg))
    eng = E.ServingEngine(cfg, prm, slots=2, prompt_len=8, max_len=32)
    assert eng.state_version == 0
    v0 = eng.state_version
    rng = np.random.default_rng(0)
    req = E.Request(0, rng.integers(0, cfg.vocab_size, 8), max_new=4)
    assert eng.admit(req)
    assert eng.state_version == v0 + 1          # prefill wrote slot 0
    eng.step()
    assert eng.state_version == v0 + 2
    payload = eng.snapshot_payload()
    assert payload["version"] == eng.state_version
    assert payload["cache"] is eng.cache
    assert set(eng.insitu_providers()) >= {"serving_state", "lengths",
                                           "kv_snapshot"}
    # idle engine (no admit/step): the hint is stable
    assert eng.snapshot_payload()["version"] == payload["version"]


def test_serving_engine_batched_requests():
    cfg = base.get("smollm-135m", smoke=True)
    prm = P.materialize(jax.random.PRNGKey(0), transformer.param_spec(cfg))
    eng = E.ServingEngine(cfg, prm, slots=2, prompt_len=8, max_len=32)
    rng = np.random.default_rng(0)
    reqs = [E.Request(i, rng.integers(0, cfg.vocab_size, 8), max_new=4)
            for i in range(3)]
    eng.run(reqs, max_steps=40)
    assert all(r.done for r in reqs)
    assert all(len(r.out) == 4 for r in reqs)
    # determinism: same prompt -> same completion
    r2 = E.Request(9, reqs[0].prompt, max_new=4)
    eng2 = E.ServingEngine(cfg, prm, slots=1, prompt_len=8, max_len=32)
    eng2.run([r2], max_steps=40)
    assert r2.out == reqs[0].out
