"""Decoder-LM assembly for all assigned families.

Layers are scan-stacked (one compiled block body regardless of depth — this is
what keeps the 61-layer/671B dry-run compilable) and rematerialized under grad.
Families:
  dense / audio / vlm : [GQA attn + SwiGLU] x N
  moe                 : [attn (MLA or GQA) + (dense | MoE) ffn], deepseek MTP head
  hybrid (hymba)      : [parallel attn+SSM fused + SwiGLU], SWA + global layers
  ssm (xlstm)         : super-blocks of 7 mLSTM + 1 sLSTM
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models import frontends, hymba, mla as mla_lib, moe as moe_lib
from repro.models import ssm as ssm_lib, xlstm as xlstm_lib
from repro.models.layers import (cross_entropy, embed, embed_spec, mlp,
                                 mlp_spec, rmsnorm, rmsnorm_spec, unembed,
                                 apply_rope)
from repro.models.params import ParamSpec

PyTree = Any


# ---------------------------------------------------------------------------
# GQA attention params + apply
# ---------------------------------------------------------------------------

def attn_spec(cfg: ModelConfig, layers: Optional[int] = None) -> dict:
    d, h, n, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim

    def mk(shape, axes, **kw):
        if layers is not None:
            shape = (layers,) + shape
            axes = ("layers",) + axes
        return ParamSpec(shape, axes, **kw)

    spec = {
        "wq": mk((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": mk((d, n, hd), ("embed", "kv_heads", "head_dim")),
        "wv": mk((d, n, hd), ("embed", "kv_heads", "head_dim")),
        "wo": mk((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        spec["bq"] = mk((h, hd), ("heads", "head_dim"), init="zeros")
        spec["bk"] = mk((n, hd), ("kv_heads", "head_dim"), init="zeros")
        spec["bv"] = mk((n, hd), ("kv_heads", "head_dim"), init="zeros")
    if cfg.qk_norm:
        spec["q_norm"] = mk((hd,), ("head_dim",), dtype=jnp.float32, init="ones")
        spec["k_norm"] = mk((hd,), ("head_dim",), dtype=jnp.float32, init="ones")
    return spec


def project_qkv(p, x, cfg: ModelConfig, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dnk->bsnk", x, p["wk"])
    v = jnp.einsum("bsd,dnk->bsnk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = rmsnorm({"scale": p["q_norm"]}, q, cfg.norm_eps)
        k = rmsnorm({"scale": p["k_norm"]}, k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_attention(p, x, cfg: ModelConfig, positions, *, window=0,
                  q_chunk=None, kv_chunk=None):
    q, k, v = project_qkv(p, x, cfg, positions)
    o = attn_lib.flash_attention(
        q, k, v, causal=True, window=window,
        q_chunk=q_chunk or cfg.q_chunk, kv_chunk=kv_chunk or cfg.kv_chunk,
        unroll=cfg.unroll_scans)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


# ---------------------------------------------------------------------------
# Param spec for the whole model
# ---------------------------------------------------------------------------

def _dense_block_spec(cfg: ModelConfig, layers: int, d_ff: int) -> dict:
    spec = {
        "ln1": rmsnorm_spec(cfg.d_model, layers),
        "ln2": rmsnorm_spec(cfg.d_model, layers),
        "mlp": mlp_spec(cfg.d_model, d_ff, layers),
    }
    if cfg.mla is not None:
        spec["attn"] = mla_lib.mla_spec(cfg, layers)
    else:
        spec["attn"] = attn_spec(cfg, layers)
    return spec


def _moe_block_spec(cfg: ModelConfig, layers: int) -> dict:
    spec = {
        "ln1": rmsnorm_spec(cfg.d_model, layers),
        "ln2": rmsnorm_spec(cfg.d_model, layers),
        "moe": moe_lib.moe_spec(cfg, layers),
    }
    if cfg.mla is not None:
        spec["attn"] = mla_lib.mla_spec(cfg, layers)
    else:
        spec["attn"] = attn_spec(cfg, layers)
    return spec


def _hybrid_block_spec(cfg: ModelConfig, layers: int) -> dict:
    return {
        "ln1": rmsnorm_spec(cfg.d_model, layers),
        "ln2": rmsnorm_spec(cfg.d_model, layers),
        "attn": attn_spec(cfg, layers),
        "ssm": ssm_lib.ssm_spec(cfg, layers),
        "fusion": hymba.fusion_spec(cfg, layers),
        "mlp": mlp_spec(cfg.d_model, cfg.d_ff, layers),
    }


def param_spec(cfg: ModelConfig) -> dict:
    spec: dict = {"embed": embed_spec(cfg.padded_vocab, cfg.d_model,
                                      cfg.tie_embeddings)}
    if cfg.family in ("dense", "audio", "vlm"):
        spec["blocks"] = _dense_block_spec(cfg, cfg.n_layers, cfg.d_ff)
    elif cfg.family == "moe":
        m = cfg.moe
        if m.first_dense:
            spec["dense_blocks"] = _dense_block_spec(
                cfg, m.first_dense, m.dense_d_ff or cfg.d_ff)
        spec["moe_blocks"] = _moe_block_spec(cfg, cfg.n_layers - m.first_dense)
        if cfg.mtp_weight > 0:
            spec["mtp"] = {
                "proj": ParamSpec((2 * cfg.d_model, cfg.d_model),
                                  ("embed", "embed")),
                "block": _dense_block_spec(
                    cfg, 1, m.dense_d_ff or 4 * cfg.d_model),
                "ln": rmsnorm_spec(cfg.d_model),
            }
    elif cfg.family == "hybrid":
        n_global = len(hymba.global_layer_ids(cfg))
        spec["global_blocks"] = _hybrid_block_spec(cfg, n_global)
        spec["swa_blocks"] = _hybrid_block_spec(cfg, cfg.n_layers - n_global)
    elif cfg.family == "ssm":
        x = cfg.xlstm
        n_super = cfg.n_layers // x.slstm_every
        spec["super"] = {
            "mlstm": xlstm_lib.mlstm_spec(cfg, layers=None),
            "slstm": xlstm_lib.slstm_spec(cfg, layers=None),
        }
        # stack: (n_super, per_super-1) for mlstm, (n_super,) for slstm
        spec["super"]["mlstm"] = jax.tree.map(
            lambda s: ParamSpec((n_super, x.slstm_every - 1) + s.shape,
                                ("layers", "layers") + s.axes, s.dtype, s.init,
                                s.scale),
            spec["super"]["mlstm"],
            is_leaf=lambda t: isinstance(t, ParamSpec))
        spec["super"]["slstm"] = jax.tree.map(
            lambda s: ParamSpec((n_super,) + s.shape, ("layers",) + s.axes,
                                s.dtype, s.init, s.scale),
            spec["super"]["slstm"],
            is_leaf=lambda t: isinstance(t, ParamSpec))
    else:
        raise ValueError(f"unknown family {cfg.family}")
    spec["final_norm"] = rmsnorm_spec(cfg.d_model)
    return spec


# ---------------------------------------------------------------------------
# Blocks (shared by train forward and serving prefill)
# ---------------------------------------------------------------------------

def _attn_branch(p, xn, cfg, positions, window, q_chunk, kv_chunk):
    if cfg.mla is not None:
        return mla_lib.mla_attention(p, xn, cfg, positions,
                                     q_chunk=q_chunk, kv_chunk=kv_chunk)
    return gqa_attention(p, xn, cfg, positions, window=window,
                         q_chunk=q_chunk, kv_chunk=kv_chunk)


def dense_block(p, x, cfg, positions, *, window=0, q_chunk=None, kv_chunk=None):
    xn = rmsnorm(p["ln1"], x, cfg.norm_eps)
    x = x + _attn_branch(p["attn"], xn, cfg, positions, window, q_chunk, kv_chunk)
    xn = rmsnorm(p["ln2"], x, cfg.norm_eps)
    return x + mlp(p["mlp"], xn)


def moe_block(p, x, cfg, positions, *, q_chunk=None, kv_chunk=None):
    xn = rmsnorm(p["ln1"], x, cfg.norm_eps)
    x = x + _attn_branch(p["attn"], xn, cfg, positions, 0, q_chunk, kv_chunk)
    xn = rmsnorm(p["ln2"], x, cfg.norm_eps)
    y, aux = moe_lib.moe_ffn(p["moe"], xn, cfg)
    return x + y, aux


def hybrid_block(p, x, cfg, positions, *, window=0, q_chunk=None, kv_chunk=None):
    xn = rmsnorm(p["ln1"], x, cfg.norm_eps)
    a = gqa_attention(p["attn"], xn, cfg, positions, window=window,
                      q_chunk=q_chunk, kv_chunk=kv_chunk)
    s = ssm_lib.ssm_mixer(p["ssm"], xn, cfg)
    x = x + hymba.fuse(p["fusion"], a, s, cfg)
    xn = rmsnorm(p["ln2"], x, cfg.norm_eps)
    return x + mlp(p["mlp"], xn)


def _scan_blocks(stacked, x, body, cfg, n: int):
    """Scan a stacked param tree over the sequence axis 0; remat per block."""
    fn = jax.checkpoint(body) if cfg.remat else body

    def step(carry, layer_params):
        return fn(carry, layer_params), None

    if not cfg.scan_layers:
        for i in range(n):
            x = fn(x, jax.tree.map(lambda t: t[i], stacked))
        return x
    x, _ = jax.lax.scan(step, x, stacked)
    return x


def _scan_blocks_aux(stacked, x, body, cfg, n: int):
    """Like ``_scan_blocks`` for blocks that return ``(x, stats)``: the
    per-layer stats come back stacked on a leading layer axis."""
    fn = jax.checkpoint(body) if cfg.remat else body

    if not cfg.scan_layers:
        outs = []
        for i in range(n):
            x, st = fn(x, jax.tree.map(lambda t: t[i], stacked))
            outs.append(st)
        return x, jax.tree.map(lambda *a: jnp.stack(a), *outs)
    return jax.lax.scan(fn, x, stacked)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward(params: PyTree, cfg: ModelConfig, tokens: jax.Array, *,
            prefix_embeds: Optional[jax.Array] = None,
            q_chunk: Optional[int] = None, kv_chunk: Optional[int] = None,
            bspec=None, h0: Optional[jax.Array] = None):
    """tokens: (B, S_tok) int32. Returns (logits, aux, loss_mask): ``aux``
    is ``{"loss": auxiliary loss}`` plus, for MoE layers, their per-layer
    routing stats stacked (``moe.moe_ffn``).

    ``h0`` (optional) is a precomputed token embedding — used by the pod-ring
    train step, which hoists the embedding gather out of its manual region.
    """
    h = embed(params["embed"], tokens) if h0 is None else h0
    loss_mask = jnp.ones(tokens.shape, jnp.float32)
    if cfg.frontend is not None and prefix_embeds is not None:
        h, loss_mask = frontends.splice_prefix(h, prefix_embeds)
    if bspec is not None:
        h = jax.lax.with_sharding_constraint(h, bspec)
    b, s, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    aux = {"loss": jnp.zeros((), jnp.float32)}

    if cfg.family in ("dense", "audio", "vlm"):
        body = functools.partial(
            lambda x, p: dense_block(p, x, cfg, positions,
                                     q_chunk=q_chunk, kv_chunk=kv_chunk))
        h = _scan_blocks(params["blocks"], h, body, cfg, cfg.n_layers)

    elif cfg.family == "moe":
        m = cfg.moe
        if m.first_dense:
            body_d = lambda x, p: dense_block(p, x, cfg, positions,
                                              q_chunk=q_chunk, kv_chunk=kv_chunk)
            h = _scan_blocks(params["dense_blocks"], h, body_d, cfg,
                             m.first_dense)
        body_m = lambda x, p: moe_block(p, x, cfg, positions,
                                        q_chunk=q_chunk, kv_chunk=kv_chunk)
        h, aux = _scan_blocks_aux(params["moe_blocks"], h, body_m, cfg,
                                  cfg.n_layers - m.first_dense)
        aux = dict(aux, loss=jnp.sum(aux["loss"]))

    elif cfg.family == "hybrid":
        h = _hybrid_forward(params, cfg, h, positions, q_chunk, kv_chunk)

    elif cfg.family == "ssm":
        h = _xlstm_forward(params, cfg, h)

    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = unembed(params["embed"], h, cfg.vocab_size)
    return logits, aux, loss_mask


def _hybrid_forward(params, cfg, h, positions, q_chunk, kv_chunk):
    """Interleave global (full-attn) and SWA block groups in layer order."""
    gids = hymba.global_layer_ids(cfg)
    body_g = lambda x, p: hybrid_block(p, x, cfg, positions, window=0,
                                       q_chunk=q_chunk, kv_chunk=kv_chunk)
    body_s = lambda x, p: hybrid_block(p, x, cfg, positions,
                                       window=cfg.swa_window,
                                       q_chunk=q_chunk, kv_chunk=kv_chunk)
    g_idx, s_idx = 0, 0
    # group consecutive layers of the same kind, scanning each group
    kinds = ["g" if i in gids else "s" for i in range(cfg.n_layers)]
    i = 0
    while i < cfg.n_layers:
        j = i
        while j < cfg.n_layers and kinds[j] == kinds[i]:
            j += 1
        count = j - i
        if kinds[i] == "g":
            part = jax.tree.map(lambda t: t[g_idx:g_idx + count],
                                params["global_blocks"])
            h = _scan_blocks(part, h, body_g, cfg, count)
            g_idx += count
        else:
            part = jax.tree.map(lambda t: t[s_idx:s_idx + count],
                                params["swa_blocks"])
            h = _scan_blocks(part, h, body_s, cfg, count)
            s_idx += count
        i = j
    return h


def _xlstm_forward(params, cfg, h):
    x = cfg.xlstm
    per = x.slstm_every - 1
    n_super = cfg.n_layers // x.slstm_every

    def super_body(carry, p_super):
        def m_body(c, p_layer):
            return xlstm_lib.mlstm_mixer(p_layer, c, cfg), None

        m_fn = jax.checkpoint(lambda c, p: m_body(c, p)[0]) if cfg.remat else (
            lambda c, p: m_body(c, p)[0])

        def m_step(c, p_layer):
            return m_fn(c, p_layer), None

        if cfg.scan_layers:
            carry, _ = jax.lax.scan(m_step, carry, p_super["mlstm"])
        else:
            for i in range(per):
                carry = m_fn(carry,
                             jax.tree.map(lambda t: t[i], p_super["mlstm"]))
        s_fn = (jax.checkpoint(lambda c: xlstm_lib.slstm_mixer(
            p_super["slstm"], c, cfg)[0]) if cfg.remat else
            (lambda c: xlstm_lib.slstm_mixer(p_super["slstm"], c, cfg)[0]))
        return s_fn(carry), None

    if cfg.scan_layers:
        h, _ = jax.lax.scan(super_body, h, params["super"])
    else:
        for i in range(n_super):
            h, _ = super_body(h, jax.tree.map(lambda t: t[i],
                                              params["super"]))
    return h


# ---------------------------------------------------------------------------
# Train loss
# ---------------------------------------------------------------------------

def train_loss(params: PyTree, cfg: ModelConfig, batch: dict,
               **kw) -> jax.Array:
    return train_loss_and_stats(params, cfg, batch, **kw)[0]


def train_loss_and_stats(params: PyTree, cfg: ModelConfig, batch: dict, *,
                         bspec=None, q_chunk=None, kv_chunk=None, h0=None,
                         mtp_pre=None, gather_free: bool = False):
    """(loss, stats): the train loss and the MoE layers' routing stats
    (``forward``'s ``aux`` without its loss; empty for other families)."""
    logits, aux, fmask = forward(
        params, cfg, batch["tokens"], prefix_embeds=batch.get("prefix"),
        q_chunk=q_chunk, kv_chunk=kv_chunk, bspec=bspec, h0=h0)
    mask = fmask
    labels = batch["labels"]
    # with a modality prefix, the hidden sequence is longer than the token
    # sequence; left-pad labels (and any user mask) into the prefix region,
    # whose loss_mask is already zero.
    s_pre = logits.shape[1] - labels.shape[1]
    if s_pre:
        labels = jnp.pad(labels, ((0, 0), (s_pre, 0)))
    if "mask" in batch:
        m = batch["mask"]
        if s_pre:
            m = jnp.pad(m, ((0, 0), (s_pre, 0)))
        mask = mask * m
    loss = cross_entropy(logits, labels, mask, gather_free=gather_free)
    if cfg.family == "moe" and cfg.mtp_weight > 0:
        loss = loss + cfg.mtp_weight * _mtp_loss(
            params, cfg, logits, batch, mtp_pre=mtp_pre,
            gather_free=gather_free)
    stats = {k: v for k, v in aux.items() if k != "loss"}
    return loss + aux["loss"], stats


def _mtp_loss(params, cfg, logits, batch, mtp_pre=None, gather_free=False):
    """DeepSeek-style multi-token prediction: one extra block predicts t+2.

    Simplified MTP module: concat(hidden-proxy, next-token embedding) ->
    projection -> one dense block -> shared unembed. Faithful in structure
    (shared embedding/head, sequential conditioning), reduced to depth 1.
    """
    # proxy hidden: embedding of the *current* labels (teacher forcing)
    if mtp_pre is not None:
        cur, emb = mtp_pre
    else:
        emb = embed(params["embed"], batch["labels"])
        cur = embed(params["embed"], batch["tokens"])
    h = jnp.concatenate([cur, emb], axis=-1)
    h = jnp.einsum("bse,ed->bsd", h, params["mtp"]["proj"])
    b, s, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    blk = jax.tree.map(lambda t: t[0], params["mtp"]["block"])
    h = dense_block(blk, h, cfg, positions)
    h = rmsnorm(params["mtp"]["ln"], h, cfg.norm_eps)
    logits2 = unembed(params["embed"], h, cfg.vocab_size)
    labels2 = jnp.roll(batch["labels"], -1, axis=1)
    mask2 = jnp.ones(labels2.shape, jnp.float32).at[:, -1].set(0.0)
    if "mask" in batch:
        mask2 = mask2 * batch["mask"]
    return cross_entropy(logits2, labels2, mask2, gather_free=gather_free)
