"""Chunked (flash-style) attention in pure JAX, GQA/MHA/SWA + decode paths.

Why chunked: the 32k-prefill and 4k-train shapes would otherwise materialize
S x S score tensors per head (e.g. 32768^2 x heads), which no 16 GB chip holds.
The classic online-softmax recurrence over KV chunks bounds live memory to
(q_chunk x kv_chunk) per head group, which is also the structure a TPU flash
kernel tiles into VMEM. Causality is exact *and* flop-exact: q-chunks are a
python loop (unrolled in HLO), and the inner lax.scan for q-chunk i only runs
over the kv-chunks it can actually see — no masked-out flops are issued, so
cost_analysis() reflects true causal work (roofline honesty).

GQA never materializes repeated KV heads: scores are computed in grouped
layout (batch, kv_head, group, q, k).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _grouped(q: jax.Array, n_kv: int) -> jax.Array:
    """(B,S,Hq,D) -> (B,S,N,G,D) with N=kv heads, G=Hq//N."""
    b, s, hq, d = q.shape
    return q.reshape(b, s, n_kv, hq // n_kv, d)


def _chunk_scores(q5, k, scale):
    # q5: (B,Sq,N,G,D), k: (B,Sk,N,D) -> (B,N,G,Sq,Sk) fp32
    return jnp.einsum("bsngd,btnd->bngst", q5, k).astype(jnp.float32) * scale


def flash_attention(
    q: jax.Array,          # (B, Sq, Hq, D)
    k: jax.Array,          # (B, Sk, N, D)
    v: jax.Array,          # (B, Sk, N, Dv)
    *,
    causal: bool = True,
    q_offset: int = 0,     # absolute position of q[0] (prefill continuation)
    window: int = 0,       # 0 = full; else sliding window (causal only)
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    unroll: bool = False,  # python-loop the kv chunks (cost-exact lowering)
) -> jax.Array:
    b, sq, hq, d = q.shape
    _, sk, n_kv, dv = v.shape
    scale = 1.0 / math.sqrt(d)
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, sk)
    if sq % q_chunk or sk % kv_chunk:
        # fall back to one chunk when shapes don't tile (smoke configs)
        q_chunk, kv_chunk = sq, sk

    g = hq // n_kv
    out = []
    n_q_chunks = sq // q_chunk
    for i in range(n_q_chunks):
        qs = i * q_chunk                       # chunk start (relative)
        q_abs = q_offset + qs                  # absolute start
        qi = _grouped(q[:, qs:qs + q_chunk], n_kv)
        # visible kv range for this q chunk
        hi_abs = q_abs + q_chunk if causal else sk
        hi = min(sk, hi_abs) if causal else sk
        lo = 0
        if window:
            # earliest key visible to the FIRST q row of this chunk
            lo = max(0, q_abs - (window - 1))
            lo = (lo // kv_chunk) * kv_chunk   # align down to chunk grid
        n_kv_chunks = max(1, math.ceil((hi - lo) / kv_chunk))

        q_pos = q_abs + jnp.arange(q_chunk)

        def body(carry, j, qi=qi, lo=lo, q_pos=q_pos):
            m, l, acc = carry
            start = lo + j * kv_chunk
            kj = jax.lax.dynamic_slice_in_dim(k, start, kv_chunk, axis=1)
            vj = jax.lax.dynamic_slice_in_dim(v, start, kv_chunk, axis=1)
            s = _chunk_scores(qi, kj, scale)   # (B,N,G,Sq,KV)
            kv_pos = start + jnp.arange(kv_chunk)
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask &= q_pos[:, None] >= kv_pos[None, :]
            if window:
                mask &= q_pos[:, None] - kv_pos[None, :] < window
            s = jnp.where(mask, s, NEG_INF)
            # the running max only shifts the exponent and cancels in
            # acc / l, so it carries no gradient; differentiating through
            # the max gave NaN dq/dk for bf16 inputs on a TPU v5e
            m_new = jax.lax.stop_gradient(
                jnp.maximum(m, jnp.max(s, axis=-1)))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bngst,btnd->bngsd", p.astype(vj.dtype), vj)
            acc_new = acc * corr[..., None].astype(acc.dtype) + pv.astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, n_kv, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, n_kv, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, n_kv, g, q_chunk, dv), jnp.float32)
        if unroll:
            carry = (m0, l0, a0)
            for j in range(n_kv_chunks):
                carry, _ = body(carry, j)
            m, l, acc = carry
        else:
            (m, l, acc), _ = jax.lax.scan(
                body, (m0, l0, a0), jnp.arange(n_kv_chunks))
        o = acc / jnp.maximum(l[..., None], 1e-37)
        # (B,N,G,Sq,Dv) -> (B,Sq,Hq,Dv)
        o = o.transpose(0, 3, 1, 2, 4).reshape(b, q_chunk, hq, dv)
        out.append(o.astype(v.dtype))
    return jnp.concatenate(out, axis=1) if len(out) > 1 else out[0]


def decode_attention(
    q: jax.Array,            # (B, 1, Hq, D)
    k_cache: jax.Array,      # (B, S, N, D)
    v_cache: jax.Array,      # (B, S, N, Dv)
    length: jax.Array,       # (B,) valid prefix length (after current insert)
    *,
    window: int = 0,
    ring: bool = False,      # cache is a ring buffer (SWA): all slots valid
) -> jax.Array:
    """Single-token attention over a (possibly ring) KV cache."""
    b, s, n_kv, dv = v_cache.shape
    hq = q.shape[2]
    g = hq // n_kv
    scale = 1.0 / math.sqrt(q.shape[-1])
    q5 = q.reshape(b, 1, n_kv, g, -1)
    scores = jnp.einsum("bsngd,btnd->bngst", q5, k_cache).astype(jnp.float32)
    scores = scores * scale                       # (B,N,G,1,S)
    pos = jnp.arange(s)
    if ring:
        valid = pos[None, :] < jnp.minimum(length, s)[:, None]
    else:
        valid = pos[None, :] < length[:, None]
        if window:
            valid &= pos[None, :] >= (length[:, None] - window)
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bngst,btnd->bngsd", p.astype(v_cache.dtype), v_cache)
    return o.transpose(0, 3, 1, 2, 4).reshape(b, 1, hq, dv)


def gather_pages(pages: jax.Array, page_table: jax.Array) -> jax.Array:
    """Materialize per-request sequences from a block-indexed page pool.

    ``pages``: (num_pages, page_size, ...) — one pool shared by every
    request; ``page_table``: (B, P) int32 page ids (unused entries point at
    the reserved scratch page 0 and are masked by ``length`` downstream).
    Returns (B, P*page_size, ...) in token order: position ``t`` of row
    ``b`` lives in page ``page_table[b, t // page_size]`` at offset
    ``t % page_size`` — the same token ordering as a dense slab, which is
    what keeps paged logits bit-identical to the slab path.
    """
    b, p = page_table.shape
    g = pages[page_table]                 # (B, P, page_size, ...)
    return g.reshape(b, p * pages.shape[1], *pages.shape[2:])


def scatter_token(pages: jax.Array, new: jax.Array, page_table: jax.Array,
                  lengths: jax.Array, page_size: int) -> jax.Array:
    """Write one new token per row into its page: position ``lengths[b]``.

    ``new``: (B, ...) — the freshly projected k/v (or MLA latent) rows.
    Rows whose length exceeds the table (inactive/finished requests) clamp
    to their last table entry, which the engine keeps pointed at the
    scratch page — the write lands in garbage no reader ever attends to.
    """
    b, p = page_table.shape
    idx = jnp.minimum(lengths // page_size, p - 1)
    page = page_table[jnp.arange(b), idx]
    return pages.at[page, lengths % page_size].set(new.astype(pages.dtype))


def paged_decode_attention(
    q: jax.Array,             # (B, 1, Hq, D)
    k_pages: jax.Array,       # (num_pages, page_size, N, D)
    v_pages: jax.Array,       # (num_pages, page_size, N, Dv)
    page_table: jax.Array,    # (B, P) int32
    length: jax.Array,        # (B,) valid prefix length (after insert)
    *,
    use_kernel: Optional[bool] = None,
) -> jax.Array:
    """Single-token attention over a paged KV cache.

    The default path gathers each row's pages into token order and reuses
    :func:`decode_attention` — positions past ``length`` gather scratch or
    stale pages but are masked to NEG_INF exactly like the dense slab's
    zero padding, so the result is bit-identical to a dense cache holding
    the same tokens. ``use_kernel`` (default: TPU only) switches to the
    fused Pallas gather-attention kernel in
    :mod:`repro.kernels.paged_attention`, which never materializes the
    gathered (B, S, N, D) copy.
    """
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if use_kernel:
        from repro.kernels import paged_attention as PK
        return PK.paged_decode_attention(q, k_pages, v_pages, page_table,
                                         length)
    k = gather_pages(k_pages, page_table)
    v = gather_pages(v_pages, page_table)
    return decode_attention(q, k, v, length)


def reference_attention(q, k, v, *, causal=True, q_offset=0, window=0):
    """O(S^2) oracle used by tests."""
    b, sq, hq, d = q.shape
    n_kv = k.shape[2]
    q5 = _grouped(q, n_kv)
    s = _chunk_scores(q5, k, 1.0 / math.sqrt(d))
    q_pos = q_offset + jnp.arange(sq)
    kv_pos = jnp.arange(k.shape[1])
    mask = jnp.ones((sq, k.shape[1]), bool)
    if causal:
        mask &= q_pos[:, None] >= kv_pos[None, :]
    if window:
        mask &= q_pos[:, None] - kv_pos[None, :] < window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bngst,btnd->bngsd", p.astype(v.dtype), v)
    return o.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, v.shape[-1])
