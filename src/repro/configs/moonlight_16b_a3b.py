"""moonlight-16b-a3b [moe] — MLA, 64 routed experts top-6 + 2 shared.

[hf:moonshotai/Moonlight-16B-A3B config.json]: a DeepSeek-V3 layout at 27
layers, d 2048; MLA with a full-rank query (``q_lora_rank`` null),
``kv_lora_rank`` 512, nope 128 / rope 64 / v 128; one leading dense layer
(SwiGLU 11264), then 26 MoE layers of 64 experts (width 1408, top-6) and 2
shared; sigmoid scores, selection by score plus a per-expert correction
bias (noaux_tc), weights normalised x 2.446; untied vocabulary 163,840.
The bias update rate and the sequence-wise balance weight are not in the
config: DeepSeek-V3's (arXiv:2412.19437) 0.001 and 0.0001.
"""
from repro.configs.base import MLAConfig, MoEConfig, ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,         # MLA: per-head KV derived from the shared latent
    d_ff=1408,
    vocab_size=163840,
    rope_theta=50000.0,
    norm_eps=1e-5,
    tie_embeddings=False,
    mla=MLAConfig(q_lora=None, kv_lora=512, qk_nope=128, qk_rope=64,
                  v_head=128),
    moe=MoEConfig(
        n_experts=64,
        top_k=6,
        d_expert=1408,
        n_shared_experts=2,
        first_dense=1,
        dense_d_ff=11264,
        capacity_factor=None,
        routed_scale=2.446,
        bias_update_rate=0.001,
        seq_aux_weight=0.0001,
    ),
    source="hf:moonshotai/Moonlight-16B-A3B",
)


def smoke() -> ModelConfig:
    return ModelConfig(
        name="moonlight-16b-a3b-smoke",
        family="moe",
        n_layers=3,            # 1 dense + 2 moe
        d_model=64,
        n_heads=4,
        n_kv_heads=4,
        d_ff=96,
        vocab_size=257,
        rope_theta=50000.0,
        mla=MLAConfig(q_lora=None, kv_lora=24, qk_nope=16, qk_rope=8,
                      v_head=16),
        moe=MoEConfig(
            n_experts=8,
            top_k=2,
            d_expert=96,
            n_shared_experts=2,
            first_dense=1,
            dense_d_ff=128,
            capacity_factor=None,
            routed_scale=2.446,
            bias_update_rate=0.001,
            seq_aux_weight=0.0001,
        ),
        q_chunk=16,
        kv_chunk=16,
    )
