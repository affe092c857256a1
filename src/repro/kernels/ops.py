"""Jit'd public wrappers around the spectral-lossy Pallas kernels.

``spectral_compress(x, eps)`` / ``spectral_decompress(c)`` are the device-side
lossy codec used by core/lossy.py (checkpoint compression), the hybrid in-situ
step, and optim/grad_compress.py. On CPU (tests, this container) the kernels
run in interpret mode; on TPU they compile natively — callers never care.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref, spectral_lossy as K
from repro.kernels.ref import BLOCK, Compressed


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_blocks(xb: jax.Array, tile: int) -> jax.Array:
    n = xb.shape[0]
    pad = (-n) % tile
    if pad:
        xb = jnp.pad(xb, ((0, pad), (0, 0)))
    return xb


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 1).bit_length() if n > 2 else max(n, 1)


def _bucket_rows(n_blocks: int) -> int:
    """Shape bucket for the fused tree path: next power-of-two block count
    (>= HIST_TILE, so every bucket stays tile-aligned)."""
    return max(K.HIST_TILE, _next_pow2(n_blocks))


def _pad_to_rows(xb: jax.Array, rows: int) -> jax.Array:
    pad = rows - xb.shape[0]
    if pad:
        xb = jnp.pad(xb, ((0, pad), (0, 0)))
    return xb


# ---------------------------------------------------------------------------
# Tile autotuning. Keyed by the same pow2 shape buckets as the fused-tree
# trace cache, so a tile is measured at most once per bucket per process.
# Off-TPU the defaults are returned untouched (interpret-mode timings would
# tune the python interpreter, not the hardware).
# ---------------------------------------------------------------------------

_TILE_CANDIDATES = {"hist": (8, 16, 32), "quant": (32, 64, 128, 256)}
_DEFAULT_TILE = {"hist": K.HIST_TILE, "quant": K.QUANT_TILE}
_TUNED: dict[tuple, int] = {}


def _measure_tile(kind: str, bucket_rows: int) -> int:
    """Time each candidate tile on the real kernel at the bucket shape and
    keep the fastest. Candidates and buckets are both powers of two, so no
    candidate ever needs padding."""
    import time as _time
    cands = [t for t in _TILE_CANDIDATES[kind] if t <= bucket_rows]
    if not cands:
        return min(_TILE_CANDIDATES[kind])
    best, best_dt = cands[0], float("inf")
    xb = jnp.ones((bucket_rows, BLOCK), jnp.float32)
    tvec = jnp.full((bucket_rows,), 1e-3, jnp.float32)
    for t in cands:
        if kind == "hist":
            fn = jax.jit(functools.partial(
                K.dct_hist_coarse, interpret=False, tile=t))
            args = (xb,)
        else:
            fn = jax.jit(functools.partial(
                K.threshold_quant, interpret=False, tile=t))
            args = (xb, tvec)
        out = fn(*args)
        jax.block_until_ready(out)  # compile + warm outside the timer
        t0 = _time.perf_counter()
        for _ in range(3):
            out = fn(*args)
        jax.block_until_ready(out)
        dt = (_time.perf_counter() - t0) / 3
        if dt < best_dt:
            best, best_dt = t, dt
    return best


def _tuned_tile(kind: str, bucket_rows: int, backend: str) -> int:
    key = (kind, bucket_rows, backend)
    if key not in _TUNED:
        _TUNED[key] = (_measure_tile(kind, bucket_rows)
                       if backend == "tpu" else _DEFAULT_TILE[kind])
    return _TUNED[key]


def tuned_tiles() -> dict:
    """Snapshot of the (kind, bucket_rows, backend) -> tile cache, for
    benchmark/report introspection."""
    return dict(_TUNED)


def _tiles_for(n_blocks: int) -> tuple[int, int]:
    if _interpret():
        return K.HIST_TILE, K.QUANT_TILE
    b = _bucket_rows(n_blocks)
    return (_tuned_tile("hist", b, "tpu"), _tuned_tile("quant", b, "tpu"))


def _compress_math(xb, eps: float, interpret: bool,
                   hist_tile: int, quant_tile: int):
    """Shared body of the single-tensor compress jits.

    TPU path is the two-level histogram: a coarse 32-bin pass, in-graph
    coarse-bin selection, then a 16-bin refine pass restricted to the coarse
    bin straddling the eps^2 energy budget — O(elem x 48) binning FLOPs
    instead of O(elem x 512), same bin edges as the flat selector.
    """
    if interpret:
        # off-TPU: the pure-jnp oracle compiles to the same math (tests
        # assert bit-equal q); interpret-mode pallas is kept for kernel
        # tests only — it executes the kernel body per-block in python.
        y = ref.dct_blocks(xb)
        _, energies = ref.energy_histogram(y)
        t = ref.threshold_from_histogram(energies, eps)
        return ref.quantize_blocks(y, t)
    y, _, ce = K.dct_hist_coarse(xb, interpret=False, tile=hist_tile)
    c, cc, base, budget = ref.select_coarse(ce, eps)
    _, fe = K.hist_refine(y, cc, interpret=False, tile=hist_tile)
    t = ref.select_fine(fe, c, cc, base, budget)
    return K.threshold_quant(y, t, interpret=False, tile=quant_tile)


@functools.partial(jax.jit, static_argnames=(
    "eps", "interpret", "hist_tile", "quant_tile"))
def _compress_padded(xb: jax.Array, eps: float, interpret: bool,
                     hist_tile: int = K.HIST_TILE,
                     quant_tile: int = K.QUANT_TILE):
    return _compress_math(xb, eps, interpret, hist_tile, quant_tile)


def spectral_compress(x: jax.Array, eps: float = 1e-2) -> Compressed:
    """Lossy-compress one tensor on device. Relative-L2 error <~ eps + quant."""
    xb, n = ref.blockize(x)
    hist_tile, quant_tile = _tiles_for(xb.shape[0])
    xb = _pad_blocks(xb, hist_tile)
    q, scale = _compress_padded(xb, float(eps), _interpret(),
                                hist_tile, quant_tile)
    return Compressed(q, scale, n, tuple(x.shape), x.dtype)


@functools.partial(jax.jit, static_argnames=(
    "eps", "interpret", "chunk_blocks", "hist_tile", "quant_tile"))
def _compress_padded_chunks(xb: jax.Array, eps: float, interpret: bool,
                            chunk_blocks: int, hist_tile: int,
                            quant_tile: int):
    """Same math as ``_compress_padded`` but the int8 output is pre-split
    into frame-chunk-aligned device buffers inside the jit — no extra device
    round-trip between quantize and codec chunking."""
    q, scale = _compress_math(xb, eps, interpret, hist_tile, quant_tile)
    n = q.shape[0]
    chunks = tuple(q[off:min(off + chunk_blocks, n)]
                   for off in range(0, n, chunk_blocks))
    return chunks, scale


def spectral_compress_chunked(x: jax.Array, eps: float = 1e-2, *,
                              chunk_blocks: int = 4096):
    """Fused quantize + frame-chunking: lossy-compress one tensor and return
    its int8 coefficients already split into ``chunk_blocks``-row device
    buffers (4096 blocks x 256 B = the codec's 1 MiB frame chunk), so the
    host framing path can D2H-copy and losslessly pack chunk-by-chunk
    instead of synchronising on one monolithic buffer.

    Returns ``(chunks, scale, n_elements)`` with ``concat(chunks)`` bitwise
    equal to ``spectral_compress(x, eps).q``.
    """
    xb, n = ref.blockize(x)
    hist_tile, quant_tile = _tiles_for(xb.shape[0])
    xb = _pad_blocks(xb, hist_tile)
    chunks, scale = _compress_padded_chunks(
        xb, float(eps), _interpret(), int(chunk_blocks),
        hist_tile, quant_tile)
    return chunks, scale, n


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decompress_padded(q, scale, interpret: bool):
    if interpret:
        return ref.idct_blocks(ref.dequantize_blocks(q, scale))
    return K.dequant_idct(q, scale, interpret=False)


def spectral_decompress(c: Compressed) -> jax.Array:
    xb = _decompress_padded(c.q, c.scale, _interpret())
    return ref.unblockize(xb, c.n_elements, c.shape, c.dtype)


@functools.partial(jax.jit, static_argnames=(
    "eps", "interpret", "hist_tile", "quant_tile"))
def _compress_tree_packed(blocks: tuple, eps: float, interpret: bool,
                          hist_tile: int = K.HIST_TILE,
                          quant_tile: int = K.QUANT_TILE):
    """ONE fused dispatch over pre-bucketed per-leaf block groups.

    ``blocks`` are the already-blockized leaves (f32 ``(rows_i, BLOCK)``,
    each padded to a power-of-two row count by the caller — the
    shape-bucketed trace cache); they are concatenated into one
    (total_blocks, BLOCK) buffer and the DCT runs once over the packed
    buffer. Thresholds stay *per leaf* — selection statistics are
    segment-summed back to per-leaf histograms — so the result is
    bit-identical to the per-leaf path (zero pad blocks carry zero energy
    and cannot move any leaf's threshold). The jit trace therefore keys on
    the *bucketed* row counts: an elastic mesh that resizes its leaves
    re-traces only when a leaf crosses a power-of-two block-count boundary,
    bounding compilation to O(log(max_blocks)) variants per leaf instead of
    one per shape.
    """
    counts = [b.shape[0] for b in blocks]
    packed = jnp.concatenate(blocks, 0) if len(blocks) > 1 else blocks[0]
    if interpret:
        # off-TPU: packed pure-jnp oracle (XLA compiles the unrolled
        # per-leaf selection into the same single program).
        y = ref.dct_blocks(packed)
        qs, ss = [], []
        off = 0
        for c in counts:
            yb = y[off:off + c]
            off += c
            _, energies = ref.energy_histogram(yb)
            t = ref.threshold_from_histogram(energies, eps)
            q, s = ref.quantize_blocks(yb, t)
            qs.append(q)
            ss.append(s)
        return tuple(qs), tuple(ss)
    # TPU: two-level selection in one fused graph — a coarse tiled pass,
    # per-leaf segment-summed coarse histograms, then a tiled refine pass
    # driven by each block's leaf coarse index. Tile rows never straddle
    # leaves (each leaf is padded to a pow2 bucket >= hist_tile), so
    # per-tile histograms segment-sum exactly to the per-leaf histograms
    # the per-leaf kernels would have produced.
    import numpy as _np
    y, _, eng_t = K.dct_hist_coarse_tiled(packed, interpret=False,
                                          tile=hist_tile)
    tile_seg = jnp.asarray(_np.repeat(_np.arange(len(counts)),
                                      [c // hist_tile for c in counts]))
    seg_ce = jnp.zeros((len(counts), ref.NBINS_COARSE), jnp.float32
                       ).at[tile_seg].add(eng_t)
    cs, ccs, bases, budgets = jax.vmap(
        lambda e: ref.select_coarse(e, eps))(seg_ce)
    block_seg = jnp.asarray(_np.repeat(_np.arange(len(counts)), counts))
    _, fine_t = K.hist_refine_tiled(y, ccs[block_seg], interpret=False,
                                    tile=hist_tile)
    seg_fe = jnp.zeros((len(counts), ref.NBINS_FINE), jnp.float32
                       ).at[tile_seg].add(fine_t)
    t_seg = jax.vmap(ref.select_fine)(seg_fe, cs, ccs, bases, budgets)
    q, s = K.threshold_quant(y, t_seg[block_seg], interpret=False,
                             tile=quant_tile)
    qs, ss, off = [], [], 0
    for c in counts:
        qs.append(q[off:off + c])
        ss.append(s[off:off + c])
        off += c
    return tuple(qs), tuple(ss)


def _packed_tree_args(flat, selected: list, eps: float):
    """Blocks and static arguments of the fused tree dispatch: every
    selected leaf blockized and padded to its pow2 bucket, plus the tiles.
    Returns ``(args, keep_rows)``; ``args`` feed ``_compress_tree_packed``.
    """
    blocks, keep_rows = [], []
    for i in selected:
        xb, _ = ref.blockize(flat[i][1])
        real = xb.shape[0] + ((-xb.shape[0]) % K.HIST_TILE)
        keep_rows.append(real)
        blocks.append(_pad_to_rows(xb, _bucket_rows(real)))
    hist_tile, quant_tile = _tiles_for(max(b.shape[0] for b in blocks))
    # tiles must never straddle leaves: clamp to the smallest bucket
    # (both are powers of two, so the smaller divides every bucket).
    hist_tile = min(hist_tile, min(b.shape[0] for b in blocks))
    args = (tuple(blocks), float(eps), _interpret(), hist_tile, quant_tile)
    return args, keep_rows


def _selected_leaves(state, policy):
    flat, treedef = jax.tree_util.tree_flatten_with_path(state)
    selected = [i for i, (path, leaf) in enumerate(flat)
                if leaf is not None and policy is not None
                and policy(jax.tree_util.keystr(path))]
    return flat, treedef, selected


def spectral_compress_tree(state, eps: float = 1e-2,
                           policy=None, *, fused: bool = True):
    """Device stage of the hybrid checkpoint pipeline: lossy-compress every
    leaf ``policy(keystr)`` selects; other leaves pass through untouched.

    Returns the same tree structure with ``Compressed`` leaves where the
    policy fired — the hand-off then ships int8 coefficients + scales.

    ``fused`` (default) packs all selected leaves into one flat blocked
    buffer and compresses the whole tree in a single fused dispatch
    (bit-identical to the per-leaf path, which ``fused=False`` preserves
    for comparison). Each leaf's block count is padded up to the next
    power of two before the fused call, so the jit trace cache buckets
    elastic-mesh shape drift instead of re-tracing per tree shape; the
    zero pad blocks carry no energy (thresholds are unchanged) and are
    sliced off the result.
    """
    flat, treedef, selected = _selected_leaves(state, policy)
    new_leaves = [leaf for _, leaf in flat]
    if fused and len(selected) > 1:
        args, keep_rows = _packed_tree_args(flat, selected, eps)
        qs, scales = _compress_tree_packed(*args)
        for i, q, scale, real in zip(selected, qs, scales, keep_rows):
            leaf = flat[i][1]
            new_leaves[i] = Compressed(q[:real], scale[:real],
                                       int(leaf.size),
                                       tuple(leaf.shape), leaf.dtype)
    else:
        for i in selected:
            new_leaves[i] = spectral_compress(flat[i][1], eps)
    return jax.tree_util.tree_unflatten(treedef, new_leaves)


def lower_compress_tree(state, eps: float = 1e-2, policy=None):
    """The fused dispatch ``spectral_compress_tree`` runs for ``state``,
    lowered (``.compile().as_text()`` shows whether the Pallas kernels or
    the jnp oracle are in it)."""
    flat, _, selected = _selected_leaves(state, policy)
    if len(selected) < 2:
        raise ValueError("the fused dispatch needs >= 2 selected leaves")
    args, _ = _packed_tree_args(flat, selected, eps)
    return _compress_tree_packed.lower(*args)


def packed_tree_cache_size() -> int:
    """Number of compiled variants of the fused tree kernel (trace-cache
    introspection for the shape-bucketing tests/benchmarks)."""
    return _compress_tree_packed._cache_size()


# ---------------------------------------------------------------------------
# In-graph variant (hybrid in-situ: runs *inside* the jitted train step, like
# NEKO's on-GPU lossy pass). Takes/returns plain arrays so it can live in a
# pjit'd computation; threshold selection happens in-graph too.
# ---------------------------------------------------------------------------

def compress_in_graph(x: jax.Array, eps: float = 1e-2,
                      interpret: bool | None = None):
    """Returns (q:int8 (nb,B), scale:f32 (nb,)) — ~4-8x fewer D2H bytes.

    jnp DCT+histogram (XLA fuses these fine) so the op can inline into a
    sharded train step without a pallas_call on non-TPU backends; on TPU the
    pallas path is used.
    """
    if interpret is None:
        interpret = _interpret()
    xb, _ = ref.blockize(x)
    xb = _pad_blocks(xb, K.HIST_TILE)
    if interpret:
        y = ref.dct_blocks(xb)
        _, energies = ref.energy_histogram(y)
        t = ref.threshold_from_histogram(energies, eps)
        return ref.quantize_blocks(y, t)
    y, _, ce = K.dct_hist_coarse(xb, interpret=False)
    c, cc, base, budget = ref.select_coarse(ce, eps)
    _, fe = K.hist_refine(y, cc, interpret=False)
    t = ref.select_fine(fe, c, cc, base, budget)
    return K.threshold_quant(y, t, interpret=False)
