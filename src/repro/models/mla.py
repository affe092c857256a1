"""Multi-head Latent Attention (DeepSeek-V3) with absorbed decode path.

Training/prefill materializes per-head K/V from the shared 512-d latent (the
published training recipe). Decode uses the *absorbed* formulation: queries are
projected into latent space (q^T W_UK folded), attention runs directly against
the cached latent + shared rope key, and W_UV is folded into the output
projection — so the KV cache is (kv_lora + qk_rope) = 576 floats/token/layer
instead of heads*(nope+rope+v) = 40960. That 71x cache shrink is the
arch-level analog of the paper's in-situ data reduction, and is why this arch
is the technique-representative hillclimb cell. The query projection is
low-rank (``q_lora``, DeepSeek-V3) or full-rank (``q_lora`` None, Moonlight).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig, ModelConfig
from repro.models import attention as attn_lib
from repro.models.layers import apply_rope, rmsnorm
from repro.models.params import ParamSpec


def mla_spec(cfg: ModelConfig, layers: Optional[int] = None) -> dict:
    m = cfg.mla
    h, d = cfg.n_heads, cfg.d_model
    qk = m.qk_nope + m.qk_rope

    def mk(shape, axes, **kw):
        if layers is not None:
            shape = (layers,) + shape
            axes = ("layers",) + axes
        return ParamSpec(shape, axes, **kw)

    if m.q_lora:
        q = {"wq_a": mk((d, m.q_lora), ("embed", "lora")),
             "q_norm": mk((m.q_lora,), ("lora",), dtype=jnp.float32,
                          init="ones"),
             "wq_b": mk((m.q_lora, h, qk), ("lora", "heads", "head_dim"))}
    else:
        q = {"wq": mk((d, h, qk), ("embed", "heads", "head_dim"))}
    return {
        **q,
        "wkv_a": mk((d, m.kv_lora + m.qk_rope), ("embed", "lora")),
        "kv_norm": mk((m.kv_lora,), ("lora",), dtype=jnp.float32, init="ones"),
        "wk_b": mk((m.kv_lora, h, m.qk_nope), ("lora", "heads", "head_dim")),
        "wv_b": mk((m.kv_lora, h, m.v_head), ("lora", "heads", "head_dim")),
        "wo": mk((h, m.v_head, d), ("heads", "head_dim", "embed")),
    }


def _project_q(p, x, cfg: ModelConfig, positions):
    m = cfg.mla
    if m.q_lora:
        q_lat = jnp.einsum("bsd,dl->bsl", x, p["wq_a"])
        q_lat = rmsnorm({"scale": p["q_norm"]}, q_lat, cfg.norm_eps)
        q = jnp.einsum("bsl,lhk->bshk", q_lat, p["wq_b"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = q[..., : m.qk_nope], q[..., m.qk_nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _project_kv_latent(p, x, cfg: ModelConfig, positions):
    m = cfg.mla
    kv = jnp.einsum("bsd,dl->bsl", x, p["wkv_a"])
    c_kv, k_rope = kv[..., : m.kv_lora], kv[..., m.kv_lora:]
    c_kv = rmsnorm({"scale": p["kv_norm"]}, c_kv, cfg.norm_eps)
    # shared (MQA-style) rope key: one head
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)
    return c_kv, k_rope[:, :, 0, :]


def mla_attention(p, x, cfg: ModelConfig, positions, *, q_chunk=None,
                  kv_chunk=None) -> jax.Array:
    """Training/prefill path: materialized per-head K/V, chunked flash."""
    m = cfg.mla
    b, s, _ = x.shape
    with jax.named_scope("mla_attention"):
        q_nope, q_rope = _project_q(p, x, cfg, positions)
        c_kv, k_rope = _project_kv_latent(p, x, cfg, positions)
        k_nope = jnp.einsum("bsl,lhk->bshk", c_kv, p["wk_b"])
        v = jnp.einsum("bsl,lhk->bshk", c_kv, p["wv_b"])
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (b, s, cfg.n_heads, m.qk_rope))],
            axis=-1)
        o = attn_lib.flash_attention(
            q, k, v, causal=True,
            q_chunk=q_chunk or cfg.q_chunk, kv_chunk=kv_chunk or cfg.kv_chunk,
            unroll=cfg.unroll_scans)
        return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def mla_decode(p, x, cfg: ModelConfig, cache_ckv, cache_krope, length):
    """Absorbed decode: attention in latent space over the compressed cache.

    cache_ckv:   (B, S, kv_lora)  — already contains the current token's entry.
    cache_krope: (B, S, qk_rope)
    length:      (B,) valid prefix length including the current token.
    """
    m = cfg.mla
    b = x.shape[0]
    pos = (length - 1)[:, None]  # current absolute position, (B,1)
    q_nope, q_rope = _project_q(p, x, cfg, pos)
    # absorb W_UK: q_lat[h] = q_nope[h] @ W_UK[h]^T  -> latent-space query
    q_lat = jnp.einsum("bshk,lhk->bshl", q_nope, p["wk_b"])
    scale = 1.0 / math.sqrt(m.qk_nope + m.qk_rope)
    s_lat = jnp.einsum("bshl,btl->bhst", q_lat, cache_ckv)
    s_rope = jnp.einsum("bshk,btk->bhst", q_rope, cache_krope)
    scores = (s_lat + s_rope).astype(jnp.float32) * scale  # (B,H,1,S)
    valid = jnp.arange(cache_ckv.shape[1])[None, :] < length[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, attn_lib.NEG_INF)
    pr = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum("bhst,btl->bshl", pr.astype(cache_ckv.dtype), cache_ckv)
    # absorb W_UV into the output projection
    o = jnp.einsum("bshl,lhk->bshk", o_lat, p["wv_b"])
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def mla_new_cache_entry(p, x, cfg: ModelConfig, positions):
    """(c_kv, k_rope) for the token(s) in x — what decode appends to the cache."""
    return _project_kv_latent(p, x, cfg, positions)
