"""Pallas TPU kernels for the spectral lossy codec (hybrid in-situ, §IV-B).

The paper's hybrid mode runs the physics-based lossy compression *on the
accelerator* (deeply coupled with NEKO) and only ships the reduced data to the
host for lossless coding. Its GPU implementation is dominated by two sort
kernels (finding F7) — a poor fit for the TPU, which has no efficient global
sort in the VPU. The TPU-native redesign (see kernels/ref.py for the oracle):

  kernel 1 (dct_hist_coarse): Y = X @ D^T on the MXU, and a one-pass absolute
                             log2-|Y| COARSE histogram (32 bins, each covering
                             16 fine bins) of (count, energy), accumulated
                             across the grid — sort-free selection statistics.
                             Binning is computed as mat-vecs against a one-hot
                             bin matrix, so even the "scatter" is MXU work.
  kernel 1b (hist_refine):   fine (count, energy) histogram of the 16 fine
                             bins inside the one coarse bin that straddles the
                             eps^2 energy budget. Together with the coarse
                             pass this is O(elements x 48) binning FLOPs at
                             the full 512-bin threshold resolution; the flat
                             O(elements x 512) ``dct_hist`` kernel is kept as
                             the reference/benchmark baseline.
  select (cheap, in-graph):  threshold = largest fine bin edge whose
                             below-edge cumulative energy fits the eps^2
                             budget (ref.select_coarse / ref.select_fine).
  kernel 2 (threshold_quant): zero sub-threshold coeffs, int8-quantize with a
                             per-block scale.
  kernel 3 (dequant_idct):   decompression, X̂ = (q * scale) @ D.

Tiling: blocks are BLOCK=256 wide (2 x 128 lanes; the DCT matmul contraction
dim is 256 — MXU-aligned). The flat histogram kernel uses a small block-tile
(8) so its (NBINS x elements) one-hot stays ~4 MB in VMEM; quant/dequant
kernels use 64-block tiles (64 x 256 f32 = 64 KB per operand). Layouts
follow the TPU's block rules (last two block dims divisible by (8, 128) or
whole): per-block thresholds, scales and coarse bins travel as (n_blocks, 1)
columns in (tile, 1) blocks, histograms as (1, nbins) rows, per-tile
histograms as (n_tiles, 1, nbins), and the one-hot contraction takes 2-D
(1, N) x (nbins, N)^T operands. Every kernel
takes a ``tile=`` override so ``ops.py`` can swap in an autotuned tile per
power-of-two shape bucket; buffers whose block count is not a tile multiple
are zero-padded up to it and the result sliced back (a prime block count must
never silently degrade the launch to single-block grid steps).

All kernels run under interpret=True on CPU (tests/CI) and compile for TPU
unchanged; ``ops.py`` picks the mode from the backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import (BLOCK, LOG2_HI, LOG2_LO, NBINS, NBINS_COARSE,
                               NBINS_FINE, dct_matrix)

HIST_TILE = 8      # blocks per grid step in the histogram passes
QUANT_TILE = 64    # blocks per grid step in quant/dequant passes
# f32 operands mean f32 math: on the TPU a default-precision f32 matmul may
# round its operands to bf16 (the CPU interpreter ignores the setting)
F32 = jax.lax.Precision.HIGHEST


def _check_blocks(xb: jax.Array, tile: int, name: str) -> None:
    """Loud shape validation (a bare assert would vanish under python -O)."""
    if xb.ndim != 2 or xb.shape[1] != BLOCK:
        raise ValueError(
            f"{name}: expected a (n_blocks, {BLOCK}) blocked buffer, got "
            f"shape {tuple(xb.shape)}")
    if xb.shape[0] % tile:
        raise ValueError(
            f"{name}: n_blocks={xb.shape[0]} must be a multiple of the "
            f"{tile}-block tile (pad with ops._pad_blocks first)")


def _pad_rows(buf: jax.Array, pad: int, value: float = 0.0) -> jax.Array:
    if not pad:
        return buf
    return jnp.pad(buf, ((0, pad), (0, 0)), constant_values=value)


def _tile_and_pad(n_blocks: int, want: int) -> tuple[int, int]:
    """Full-width tile for an arbitrary block count: never shrink the tile
    to a divisor (a prime ``n_blocks`` used to degrade to tile=1 — an
    n_blocks-step grid of single-block kernel invocations); instead the
    caller zero-pads to the next tile multiple and slices the result."""
    tile = max(1, min(want, n_blocks))
    return tile, (-n_blocks) % tile


def _bin_idx(a: jax.Array) -> jax.Array:
    """Flat 512-level bin index (same math as ref.bin_index; the coarse
    kernel derives coarse bins by integer division so coarse/fine binning
    can never disagree near a bin boundary)."""
    lg = jnp.where(a > 0, jnp.log2(jnp.maximum(a, 1e-38)), LOG2_LO)
    return jnp.clip(((lg - LOG2_LO) * (NBINS / (LOG2_HI - LOG2_LO)))
                    .astype(jnp.int32), 0, NBINS - 1)


# ---------------------------------------------------------------------------
# kernel 1: DCT + histogram accumulation
# ---------------------------------------------------------------------------

def _dct_and_bins(x_ref, d_ref, y_ref):
    """Shared kernel prologue: DCT matmul + flat bin indices of the tile,
    flattened to one (1, TILE*BLOCK) row."""
    x = x_ref[...].astype(jnp.float32)          # (TILE, BLOCK)
    d = d_ref[...]                              # (BLOCK, BLOCK)
    y = jax.lax.dot_general(                    # y = x @ d.T   (MXU)
        x, d, (((1,), (1,)), ((), ())), precision=F32,
        preferred_element_type=jnp.float32)
    y_ref[...] = y
    a = jnp.abs(y.reshape(1, -1))               # (1, TILE*BLOCK)
    return a * a, _bin_idx(a)


def _onehot_hist(a2, idx, nbins, weight=None):
    """One-hot binning as matmul work (no scatter on the VPU).

    ``a2``/``idx`` (and the optional 0/1 ``weight``) are (1, N) rows. The
    one-hot is built transposed, (nbins, N), so ``idx`` broadcasts down the
    sublanes, and both sums are (1, N) x (nbins, N)^T contractions — the
    2-D operand form the TPU's matmul lowering accepts. Returns (1, nbins)
    counts and energies.
    """
    bins = jax.lax.broadcasted_iota(jnp.int32, (nbins, a2.shape[1]), 0)
    onehot = (idx == bins).astype(jnp.float32)
    if weight is not None:
        onehot = onehot * weight
    dims = (((1,), (1,)), ((), ()))
    cnt = jax.lax.dot_general(jnp.ones_like(a2), onehot, dims,
                              preferred_element_type=jnp.float32)
    eng = jax.lax.dot_general(a2, onehot, dims, precision=F32,
                              preferred_element_type=jnp.float32)
    return cnt, eng


def _refine_bins(y_ref, c_ref):
    """Refine-pass prologue: (1, N) energies, fine-bin indices and 0/1
    membership of the elements that fall in their block's coarse bin."""
    a = jnp.abs(y_ref[...])                      # (TILE, BLOCK)
    idx = _bin_idx(a)                            # flat bins
    c = c_ref[...]                               # (TILE, 1) coarse bin/block
    member = (idx // NBINS_FINE) == c
    fine = jnp.where(member, idx - c * NBINS_FINE, 0).reshape(1, -1)
    w = member.astype(jnp.float32).reshape(1, -1)
    return (a * a).reshape(1, -1), fine, w


def _column(v: jax.Array, n_blocks: int, dtype) -> jax.Array:
    """Scalar or per-block vector -> (n_blocks, 1) column. Per-block values
    ride in (tile, 1) blocks: a rank-1 (tile,) block would have to be a
    multiple of 128 lanes on the TPU."""
    v = jnp.asarray(v, dtype)
    if v.ndim == 0 or v.size == 1:
        v = jnp.broadcast_to(v.reshape(()), (n_blocks,))
    return v.reshape(n_blocks, 1)


def _hist_shapes(nbins: int, n_tiles: int | None = None):
    """Out specs/shapes of a histogram pair: one accumulated (1, nbins)
    row, or one (1, nbins) row per grid step of an (n_tiles, 1, nbins)
    array — both keep the last two block dims whole, as the TPU requires."""
    if n_tiles is None:
        spec = pl.BlockSpec((1, nbins), lambda i: (0, 0))
        shape = jax.ShapeDtypeStruct((1, nbins), jnp.float32)
    else:
        spec = pl.BlockSpec((None, 1, nbins), lambda i: (i, 0, 0))
        shape = jax.ShapeDtypeStruct((n_tiles, 1, nbins), jnp.float32)
    return [spec, spec], [shape, shape]


def _dct_hist_kernel(x_ref, d_ref, y_ref, cnt_ref, eng_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        eng_ref[...] = jnp.zeros_like(eng_ref)

    a2, idx = _dct_and_bins(x_ref, d_ref, y_ref)
    cnt, eng = _onehot_hist(a2, idx, NBINS)
    cnt_ref[...] += cnt
    eng_ref[...] += eng


def _dct_call(kernel, xb: jax.Array, tile: int, nbins: int, *,
              tiled: bool, interpret: bool):
    """DCT + histogram pallas_call shared by the four histogram passes."""
    n_blocks = xb.shape[0]
    n_tiles = n_blocks // tile
    d = jnp.asarray(dct_matrix(BLOCK))
    h_specs, h_shapes = _hist_shapes(nbins, n_tiles if tiled else None)
    y, cnt, eng = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tile, BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK, BLOCK), lambda i: (0, 0)),
        ],
        out_specs=[pl.BlockSpec((tile, BLOCK), lambda i: (i, 0))] + h_specs,
        out_shape=[jax.ShapeDtypeStruct((n_blocks, BLOCK), jnp.float32)]
        + h_shapes,
        interpret=interpret,
    )(xb, d)
    rows = (n_tiles, nbins) if tiled else (nbins,)
    return y, cnt.reshape(rows), eng.reshape(rows)


def dct_hist(xb: jax.Array, *, interpret: bool = True,
             tile: int | None = None):
    """xb: (n_blocks, BLOCK) f32 -> (y, counts, energies).

    The flat 512-bin histogram pass — kept as the baseline the two-level
    (``dct_hist_coarse`` + ``hist_refine``) pair is benchmarked against.
    """
    tile = tile or HIST_TILE
    _check_blocks(xb, tile, "dct_hist")
    return _dct_call(_dct_hist_kernel, xb, tile, NBINS, tiled=False,
                     interpret=interpret)


# ---------------------------------------------------------------------------
# kernel 1 (two-level): DCT + coarse 32-bin histogram
# ---------------------------------------------------------------------------

def _dct_hist_coarse_kernel(x_ref, d_ref, y_ref, cnt_ref, eng_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        eng_ref[...] = jnp.zeros_like(eng_ref)

    a2, idx = _dct_and_bins(x_ref, d_ref, y_ref)
    cnt, eng = _onehot_hist(a2, idx // NBINS_FINE, NBINS_COARSE)
    cnt_ref[...] += cnt
    eng_ref[...] += eng


def dct_hist_coarse(xb: jax.Array, *, interpret: bool = True,
                    tile: int | None = None):
    """xb: (n_blocks, BLOCK) f32 -> (y, counts (32,), energies (32,)).

    First pass of the two-level selector: same DCT matmul as ``dct_hist``
    but the one-hot binning runs against 32 coarse bins (each covering 16
    fine bins of the flat histogram) — O(elements x 32) binning FLOPs.
    """
    tile = tile or HIST_TILE
    _check_blocks(xb, tile, "dct_hist_coarse")
    return _dct_call(_dct_hist_coarse_kernel, xb, tile, NBINS_COARSE,
                     tiled=False, interpret=interpret)


# ---------------------------------------------------------------------------
# kernel 1r (two-level): fine refine histogram inside one coarse bin
# ---------------------------------------------------------------------------

def _hist_refine_kernel(y_ref, c_ref, cnt_ref, eng_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        eng_ref[...] = jnp.zeros_like(eng_ref)

    a2, fine, w = _refine_bins(y_ref, c_ref)
    cnt, eng = _onehot_hist(a2, fine, NBINS_FINE, w)
    cnt_ref[...] += cnt
    eng_ref[...] += eng


def _refine_call(kernel, y: jax.Array, coarse: jax.Array, tile: int, *,
                 tiled: bool, interpret: bool):
    """Refine-histogram pallas_call shared by the two refine passes."""
    n_blocks = y.shape[0]
    n_tiles = n_blocks // tile
    h_specs, h_shapes = _hist_shapes(NBINS_FINE, n_tiles if tiled else None)
    cnt, eng = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tile, BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
        ],
        out_specs=h_specs,
        out_shape=h_shapes,
        interpret=interpret,
    )(y, _column(coarse, n_blocks, jnp.int32))
    rows = (n_tiles, NBINS_FINE) if tiled else (NBINS_FINE,)
    return cnt.reshape(rows), eng.reshape(rows)


def hist_refine(y: jax.Array, coarse: jax.Array, *, interpret: bool = True,
                tile: int | None = None):
    """y: (n_blocks, BLOCK) DCT coefficients, coarse: per-block coarse bin
    (scalar or (n_blocks,) int32 — per-block so one invocation refines a
    packed multi-leaf buffer) -> (counts (16,), energies (16,)).

    Second pass of the two-level selector: fine (count, energy) histogram
    of the 16 fine bins inside each block's coarse bin — O(elements x 16)
    binning FLOPs. Elements outside the coarse bin contribute exactly 0.0,
    so each fine energy is bitwise the flat histogram's bin 16*coarse+k.
    """
    tile = tile or HIST_TILE
    _check_blocks(y, tile, "hist_refine")
    return _refine_call(_hist_refine_kernel, y, coarse, tile, tiled=False,
                        interpret=interpret)


# ---------------------------------------------------------------------------
# kernel 1b: DCT + per-tile histogram (fused-tree variant)
# ---------------------------------------------------------------------------
#
# Same DCT matmul and one-hot binning as kernel 1, but instead of
# accumulating one global histogram across the grid, each grid step writes
# its own (count, energy) row. The caller segment-sums tile rows back to
# per-leaf histograms — which is how ONE kernel invocation over a packed
# multi-leaf buffer still yields per-leaf thresholds (leaves are padded to
# HIST_TILE multiples before packing, so no tile straddles two leaves).

def _dct_hist_tiled_kernel(x_ref, d_ref, y_ref, cnt_ref, eng_ref):
    a2, idx = _dct_and_bins(x_ref, d_ref, y_ref)
    cnt_ref[...], eng_ref[...] = _onehot_hist(a2, idx, NBINS)


def dct_hist_tiled(xb: jax.Array, *, interpret: bool = True,
                   tile: int | None = None):
    """xb: (n_blocks, BLOCK) f32 -> (y, counts (n_tiles, NBINS), energies)."""
    tile = tile or HIST_TILE
    _check_blocks(xb, tile, "dct_hist_tiled")
    return _dct_call(_dct_hist_tiled_kernel, xb, tile, NBINS, tiled=True,
                     interpret=interpret)


def _dct_hist_coarse_tiled_kernel(x_ref, d_ref, y_ref, cnt_ref, eng_ref):
    a2, idx = _dct_and_bins(x_ref, d_ref, y_ref)
    cnt_ref[...], eng_ref[...] = _onehot_hist(a2, idx // NBINS_FINE,
                                              NBINS_COARSE)


def dct_hist_coarse_tiled(xb: jax.Array, *, interpret: bool = True,
                          tile: int | None = None):
    """xb -> (y, counts (n_tiles, 32), energies (n_tiles, 32)).

    Per-tile coarse histograms for the fused multi-leaf dispatch: the
    caller segment-sums tile rows back to per-leaf coarse histograms
    (leaves are padded to tile multiples, so no tile straddles two leaves).
    """
    tile = tile or HIST_TILE
    _check_blocks(xb, tile, "dct_hist_coarse_tiled")
    return _dct_call(_dct_hist_coarse_tiled_kernel, xb, tile, NBINS_COARSE,
                     tiled=True, interpret=interpret)


def _hist_refine_tiled_kernel(y_ref, c_ref, cnt_ref, eng_ref):
    a2, fine, w = _refine_bins(y_ref, c_ref)
    cnt_ref[...], eng_ref[...] = _onehot_hist(a2, fine, NBINS_FINE, w)


def hist_refine_tiled(y: jax.Array, coarse: jax.Array, *,
                      interpret: bool = True, tile: int | None = None):
    """y: (n_blocks, BLOCK), coarse: (n_blocks,) int32 per-block coarse bin
    -> (counts (n_tiles, 16), energies (n_tiles, 16)).

    Tiled refine pass for the fused multi-leaf dispatch: every block of a
    leaf carries the leaf's selected coarse bin, tile rows segment-sum back
    to per-leaf fine histograms.
    """
    tile = tile or HIST_TILE
    _check_blocks(y, tile, "hist_refine_tiled")
    return _refine_call(_hist_refine_tiled_kernel, y, coarse, tile,
                        tiled=True, interpret=interpret)


# ---------------------------------------------------------------------------
# kernel 2: threshold + int8 quantize
# ---------------------------------------------------------------------------

def _threshold_quant_kernel(y_ref, t_ref, q_ref, s_ref):
    y = y_ref[...]                               # (TILE, BLOCK) f32
    t = t_ref[...]                               # (TILE, 1) block thresholds
    kept = jnp.where(jnp.abs(y) >= t, y, 0.0)
    amax = jnp.max(jnp.abs(kept), axis=-1, keepdims=True)   # (TILE, 1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(kept / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale.astype(jnp.float32)


def threshold_quant(y: jax.Array, t: jax.Array, *, interpret: bool = True,
                    tile: int | None = None):
    """``t`` is a scalar threshold or a per-block (n_blocks,) vector — the
    latter lets one invocation quantize a packed multi-leaf buffer where
    every leaf carries its own eps-derived threshold.

    Block counts that are not a tile multiple are zero-padded up to it and
    the pad rows sliced off the result (each block quantizes independently,
    so real rows are bit-identical); the tile itself is never shrunk.
    """
    if y.ndim != 2 or y.shape[1] != BLOCK:
        raise ValueError(
            f"threshold_quant: expected (n_blocks, {BLOCK}) coefficients, "
            f"got shape {tuple(y.shape)}")
    n_blocks = y.shape[0]
    tile, pad = _tile_and_pad(n_blocks, tile or QUANT_TILE)
    t = _pad_rows(_column(t, n_blocks, jnp.float32), pad)
    y = _pad_rows(y, pad)
    q, s = pl.pallas_call(
        _threshold_quant_kernel,
        grid=((n_blocks + pad) // tile,),
        in_specs=[
            pl.BlockSpec((tile, BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile, BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks + pad, BLOCK), jnp.int8),
            jax.ShapeDtypeStruct((n_blocks + pad, 1), jnp.float32),
        ],
        interpret=interpret,
    )(y, t)
    return q[:n_blocks], s[:n_blocks, 0]


# ---------------------------------------------------------------------------
# kernel 3: dequantize + inverse DCT
# ---------------------------------------------------------------------------

def _dequant_idct_kernel(q_ref, s_ref, d_ref, x_ref):
    y = q_ref[...].astype(jnp.float32) * s_ref[...]      # s: (TILE, 1)
    x_ref[...] = jax.lax.dot_general(            # x = y @ d    (MXU)
        y, d_ref[...], (((1,), (0,)), ((), ())), precision=F32,
        preferred_element_type=jnp.float32)


def dequant_idct(q: jax.Array, scale: jax.Array, *, interpret: bool = True,
                 tile: int | None = None):
    if q.ndim != 2 or q.shape[1] != BLOCK:
        raise ValueError(
            f"dequant_idct: expected (n_blocks, {BLOCK}) int8 coefficients, "
            f"got shape {tuple(q.shape)}")
    n_blocks = q.shape[0]
    tile, pad = _tile_and_pad(n_blocks, tile or QUANT_TILE)
    d = jnp.asarray(dct_matrix(BLOCK))
    q = _pad_rows(q, pad)
    # pad rows dequantize 0*1 -> 0
    scale = _pad_rows(_column(scale, n_blocks, jnp.float32), pad, 1.0)
    x = pl.pallas_call(
        _dequant_idct_kernel,
        grid=((n_blocks + pad) // tile,),
        in_specs=[
            pl.BlockSpec((tile, BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK, BLOCK), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile, BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks + pad, BLOCK), jnp.float32),
        interpret=interpret,
    )(q, scale, d)
    return x[:n_blocks] if pad else x
