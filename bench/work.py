"""The benchmark's yardstick arithmetic: chip peaks and the work of each step.

Everything here is computed from published sizes and shapes alone, never
from the program's compiled cost analysis: a fused or re-tiled kernel is
then read against the same work as the one it replaces.

- ``PEAKS``: one row per JAX ``device_kind``, with its source. A kind with
  no row is an error, not a default.
- ``model_flops``: 6·N·D for a training step (forward and backward, no
  recomputation) plus the causal attention term.
- ``compress_work``: operations and bytes of the device compress stage of a
  hybrid checkpoint, from the selected leaves' shapes only.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Iterable, Mapping


@dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float        # FLOP/s
    hbm_bytes: float         # bytes/s
    source: str


PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(197e12, 819e9,
                             'Google Cloud documentation, "TPU v5e"'),
}


def peaks(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})") from None


def ideal_seconds(flops: float, nbytes: float, pk: ChipPeaks) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / pk.bf16_flops, nbytes / pk.hbm_bytes)


# ---------------------------------------------------------------------------
# model FLOPs (decoder LM with GQA attention and a SwiGLU MLP)
# ---------------------------------------------------------------------------

def head_dim(cfg: Mapping) -> int:
    return int(cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"])


def n_params(cfg: Mapping) -> int:
    """Parameters touched per token: embedding (and untied head), blocks."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, n, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    ff = cfg["intermediate_size"]
    per_layer = (d * h * hd + 2 * d * n * hd + h * hd * d   # q, k, v, o
                 + 3 * d * ff                                # gate, up, down
                 + 2 * d)                                    # two RMSNorms
    if cfg.get("qk_norm"):
        per_layer += 2 * hd
    emb = v * d * (1 if cfg["tie_word_embeddings"] else 2)
    return emb + cfg["num_hidden_layers"] * per_layer + d


def model_flops(cfg: Mapping, *, kind: str, seq_len: int, batch: int) -> float:
    """FLOPs of one step: 6·N·D for ``train`` (2·N·D forward only) plus the
    causal attention term (4 FLOPs per q·k pair forward, 12 with backward).
    Recomputation is not counted."""
    tokens = batch * seq_len
    mult, attn_mult = (6.0, 12.0) if kind == "train" else (2.0, 4.0)
    attn = (attn_mult * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * head_dim(cfg) * seq_len * 0.5 * tokens)
    return mult * n_params(cfg) * tokens + attn


# ---------------------------------------------------------------------------
# device compress stage of a hybrid checkpoint
# ---------------------------------------------------------------------------

COMPRESS_BLOCK = 256     # the spectral codec's block length (its data format)


def compress_work(leaf_sizes: Iterable[int]) -> tuple[float, float]:
    """(FLOPs, bytes) of compressing leaves of these element counts.

    Each leaf is read once as f32, its int8 coefficients and one f32 scale
    per block are written, and each block takes a dense DCT (one
    BLOCK x BLOCK matrix product, 2·BLOCK FLOPs per coefficient). Block
    counts are the leaf's own, never a padded bucket, and selection passes
    (histograms) are not counted: they are the implementation's, not the
    stage's necessary work.
    """
    flops = nbytes = 0.0
    for n in leaf_sizes:
        blocks = math.ceil(n / COMPRESS_BLOCK)
        flops += 2.0 * COMPRESS_BLOCK * blocks * COMPRESS_BLOCK
        nbytes += 4.0 * n + 1.0 * n + 4.0 * blocks
    return flops, nbytes


# ---------------------------------------------------------------------------
# serving: prefill and decode FLOPs, paged-attention bytes
# ---------------------------------------------------------------------------

def serve_flops(cfg: Mapping, prompt_lens: Iterable[int],
                decode_contexts: Iterable[int]) -> float:
    """Forward FLOPs of prefilling these prompts (their own tokens, never
    the padding a prefill program adds) and of decoding one token at each
    of these context lengths: 2·N per token plus causal attention."""
    n = n_params(cfg)
    per_pair = 4.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * head_dim(cfg)
    total = 0.0
    for s in prompt_lens:
        total += 2.0 * n * s + per_pair * s * s * 0.5
    for c in decode_contexts:
        total += 2.0 * n + per_pair * c
    return total


def paged_attn_bytes(cfg: Mapping, contexts: Iterable[int],
                     dtype_bytes: int = 2) -> float:
    """HBM bytes one decode step's attention needs over all layers: each
    active row's K and V for its valid length, its query and its output.
    The same count whether a gather or a fused kernel does the work."""
    hd, layers = head_dim(cfg), cfg["num_hidden_layers"]
    kv = 2 * cfg["num_key_value_heads"] * hd * dtype_bytes
    qo = 2 * cfg["num_attention_heads"] * hd * dtype_bytes
    return float(layers * sum(c * kv + qo for c in contexts))
