"""The yardstick arithmetic of the DeepSeek-V3-layout MoE cells: model FLOPs
of a training step and the work of the grouped expert matmuls, from the
configuration file's sizes and the routed (token, slot) pairs the program
counted, never from the program's compiled cost analysis.

Sizes are those of ``bench/configs/<name>.json`` (the share held on this
chip: its layers, its ``n_routed_experts``, its vocabulary slice).
"""
from __future__ import annotations

from typing import Mapping


def _q_dim(cfg: Mapping) -> int:
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def mla_params(cfg: Mapping) -> int:
    """Matrix parameters of one MLA layer (norm scales left out)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kv, ql = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    q = d * ql + ql * h * _q_dim(cfg) if ql else d * h * _q_dim(cfg)
    return (q + d * (kv + cfg["qk_rope_head_dim"])
            + kv * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)


def expert_params(cfg: Mapping) -> int:
    """One routed expert's SwiGLU."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def token_params(cfg: Mapping) -> int:
    """Matrix parameters every token multiplies through, routed experts
    left out: attention, dense SwiGLUs, shared experts, routers and the
    output head (the input embedding is a lookup, no FLOPs)."""
    d = cfg["hidden_size"]
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    per_moe = (3 * d * cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
               + d * cfg["published"]["n_routed_experts"])
    return (cfg["num_hidden_layers"] * mla_params(cfg)
            + n_dense * 3 * d * cfg["intermediate_size"]
            + n_moe * per_moe + d * cfg["vocab_size"])


def step_flops(cfg: Mapping, *, seq_len: int, batch: int,
               routed_pairs: float) -> float:
    """Model FLOPs of one training step (forward and backward, no
    recomputation): 6 per parameter per token it touches, 6 x 3·d·f per
    (token, slot) pair a held expert computed, and causal attention at
    3 x 2 x (q·k dims + v dims) per visible (query, key) pair and head."""
    tokens = seq_len * batch
    pairs = batch * seq_len * (seq_len + 1) / 2
    attn = (6.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * (_q_dim(cfg) + cfg["v_head_dim"]) * pairs)
    return (6.0 * token_params(cfg) * tokens
            + 6.0 * expert_params(cfg) * routed_pairs + attn)


# the grouped matmuls of one training step: gate, up and down, each run
# forward, again in the rematerialised forward, and twice backward (the
# input's gradient and the weights')
KERNEL_CALLS = 3 * 4


def expert_matmul_work(cfg: Mapping, routed_pairs: float,
                       layers: int) -> tuple[float, float]:
    """(FLOPs, HBM bytes) the grouped expert matmuls need over
    ``routed_pairs`` (token, slot) pairs in ``layers`` layer passes: per
    call 2·P·d·f FLOPs, and the rows in, the held experts' weights and the
    rows out once each, in bfloat16."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    w = cfg["n_routed_experts"] * d * f
    flops = KERNEL_CALLS * 2.0 * routed_pairs * d * f
    per_call_rows = routed_pairs * (d + f)            # rows in + rows out
    nbytes = (KERNEL_CALLS * 2.0 * per_call_rows
              + KERNEL_CALLS * layers * 2.0 * w)
    return flops, nbytes
