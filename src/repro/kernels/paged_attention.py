"""Fused gather + online-softmax decode attention over a paged KV cache.

The jnp reference path in ``models/attention.paged_decode_attention``
materializes the gathered cache — ``k_pages[page_table]`` allocates a
(B, P, page_size, N, D) copy in HBM every decode step, i.e. the whole
*logical* cache is re-written once per token just to feed one (B,1) query.
This kernel keeps the pool in place: the grid is (request row, page of its
chain), the page table rides in as scalar prefetch (available before the
body runs, the standard paged-attention trick) and picks which pool page
the pipeline DMAs into VMEM for each step, and the flash-style
online-softmax recurrence runs across the chain — live memory is one
(page_size, N, D) tile per step instead of the gathered sequence.

Positions past ``length`` are masked to NEG_INF exactly like the dense
slab's padding, so scratch/stale pages never contribute. Numerics match the
gather path to float tolerance (the accumulation order differs: per-page
online softmax vs one full-row softmax), so the engine keeps the gather
path wherever bitwise parity with the dense engine is asserted — this
kernel is the TPU fast path.

Off-TPU this runs in interpret mode (kernel tests); on TPU it compiles
natively.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
# the kernel computes in f32: on the TPU a default-precision f32 matmul may
# round its operands to bf16 (the CPU interpreter ignores the setting)
F32 = jax.lax.Precision.HIGHEST


def _decode_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, page_size: int, n_kv: int,
                   group: int):
    """Grid step (b, j): attend row b's query to page j of its chain.

    The page arrives as a (page_size, N, D) VMEM block picked by the
    scalar-prefetched page table, so the pipeline's DMA fetches page j + 1
    while page j is computed; the online-softmax state lives in VMEM
    scratch across the chain and the output is written after its last page.
    """
    b, j = pl.program_id(0), pl.program_id(1)
    length = len_ref[b]
    hq = n_kv * group
    d_k = q_ref.shape[-1]
    scale = 1.0 / math.sqrt(d_k)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    q3 = q_ref[0, 0].astype(jnp.float32).reshape(n_kv, group, d_k)
    k = k_ref[...].astype(jnp.float32)                    # (PS, N, Dk)
    v = v_ref[...].astype(jnp.float32)                    # (PS, N, Dv)
    m, l, acc = m_ref[...], l_ref[...], acc_ref[...]
    # (N,G,D) x (PS,N,D) -> (N,G,PS), batched over kv heads
    s = jax.lax.dot_general(
        q3, k, (((2,), (2,)), ((0,), (1,))), precision=F32,
        preferred_element_type=jnp.float32) * scale
    pos = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, page_size), 2)
    s = jnp.where(pos < length, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    # (N,G,PS) x (PS,N,Dv) -> (N,G,Dv)
    pv = jax.lax.dot_general(
        p, v, (((2,), (0,)), ((0,), (1,))), precision=F32,
        preferred_element_type=jnp.float32)
    acc_new = acc * corr + pv
    m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        out = acc_new / jnp.maximum(l_new, 1e-37)
        o_ref[0, 0] = out.reshape(hq, acc_new.shape[-1]).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,            # (B, 1, Hq, Dk)
    k_pages: jax.Array,      # (num_pages, page_size, N, Dk)
    v_pages: jax.Array,      # (num_pages, page_size, N, Dv)
    page_table: jax.Array,   # (B, P) int32
    length: jax.Array,       # (B,) valid prefix length
    *,
    interpret: bool | None = None,
) -> jax.Array:
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, _, hq, _ = q.shape
    _, page_size, n_kv, d_k = k_pages.shape
    d_v = v_pages.shape[-1]
    pages_per_seq = page_table.shape[1]
    group = hq // n_kv
    kernel = functools.partial(_decode_kernel, page_size=page_size,
                               n_kv=n_kv, group=group)

    def page_block(i, j, table, _lengths):
        return (table[i * pages_per_seq + j], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,               # page table + lengths in SMEM
        grid=(b, pages_per_seq),
        in_specs=[
            pl.BlockSpec((1, 1, hq, d_k), lambda i, j, *_: (i, 0, 0, 0)),
            pl.BlockSpec((None, page_size, n_kv, d_k), page_block),
            pl.BlockSpec((None, page_size, n_kv, d_v), page_block),
        ],
        out_specs=pl.BlockSpec((1, 1, hq, d_v), lambda i, j, *_: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_kv, group, 1), jnp.float32),
            pltpu.VMEM((n_kv, group, 1), jnp.float32),
            pltpu.VMEM((n_kv, group, d_v), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, 1, hq, d_v), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(page_table.reshape(-1).astype(jnp.int32),
      length.astype(jnp.int32), q, k_pages, v_pages)
