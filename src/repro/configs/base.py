"""Config system: model configs, shape sets, and the config registry.

Every assigned architecture gets one module in this package defining
``CONFIG`` (the exact published numbers) and ``smoke()`` (a reduced config of
the same family for CPU tests). ``repro.configs.get(name)`` resolves either.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int                # routed experts of a layer (router outputs)
    top_k: int
    d_expert: int                 # per-expert ffn hidden
    n_shared_experts: int = 0
    first_dense: int = 0          # leading dense layers (deepseek: 3)
    dense_d_ff: int = 0           # d_ff of those dense layers
    # None: dropless routing over the held experts (grouped matmul); a
    # number: GShard capacity C = ceil(chunk*k/E * cf), overflow dropped
    capacity_factor: Optional[float] = 1.25
    token_chunk: int = 32768      # GShard dispatch group: ~2k tokens/device x 16 DP
    router_aux_weight: float = 0.001
    router_z_weight: float = 0.0001
    # split each capacity chunk into n_groups DP-local dispatch groups
    # (groups sharded over the dp axes): the dispatch/combine einsums then
    # contract DP-locally and only the (g,e,c,d)->expert transition
    # crosses shards
    grouped_dispatch: bool = False
    n_groups: int = 16
    # the dropless path routes as DeepSeek-V3 and Moonlight publish it
    # (``scoring_func`` sigmoid, ``topk_method`` noaux_tc,
    # ``norm_topk_prob`` true), weights times ``routed_scaling_factor``
    routed_scale: float = 1.0
    # auxiliary-loss-free balancing (arXiv:2412.19437): a per-expert bias
    # added to the scores for selection only, moved by +-bias_update_rate
    # after each step against each expert's load; 0 keeps no bias
    bias_update_rate: float = 0.0
    seq_aux_weight: float = 0.0   # alpha of the sequence-wise balance loss
    # routed experts this chip holds, experts [0, n_held) (0: all): one
    # chip's share of an expert-parallel deployment
    n_held: int = 0

    @property
    def held(self) -> int:
        return self.n_held or self.n_experts


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora: Optional[int] = 1536  # None: full-rank query projection
    kv_lora: int = 512
    qk_nope: int = 128
    qk_rope: int = 64
    v_head: int = 128


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    chunk: int = 128
    dt_rank: int = 0  # 0 -> ceil(d_model/16)


@dataclasses.dataclass(frozen=True)
class XLSTMConfig:
    slstm_every: int = 8          # one sLSTM block per this many blocks
    m_proj_factor: float = 2.0
    s_proj_factor: float = 4.0 / 3.0
    chunk: int = 128
    conv_kernel: int = 4


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0             # 0 -> d_model // n_heads
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    mtp_weight: float = 0.0       # deepseek multi-token-prediction loss weight
    ssm: Optional[SSMConfig] = None
    xlstm: Optional[XLSTMConfig] = None
    # hybrid-attention (hymba): sliding window on all but global_layers
    swa_window: int = 0           # 0 -> full attention everywhere
    n_global_layers: int = 0      # leading/trailing/middle full-attn layers
    # modality frontend stub: number of precomputed embedding tokens prepended
    frontend: Optional[str] = None   # None | 'vit' | 'audio'
    frontend_tokens: int = 0
    # attention chunking (flash) — structural VMEM/memory bound, perf lever
    q_chunk: int = 512
    kv_chunk: int = 1024
    # distribution policy
    rules: str = "default"        # default | pure_dp (see distributed/sharding)
    remat: bool = True
    scan_layers: bool = True
    # cost-exact lowering: unroll ALL internal lax.scans (attention kv loop,
    # ssm/mlstm chunk loops, moe token chunks) so XLA cost_analysis counts
    # every iteration. Used by the dry-run's depth-extrapolation variants
    # ONLY — the deployed config keeps scans (compile size).
    unroll_scans: bool = False
    # which shapes this arch supports (long_500k only for sub-quadratic)
    sub_quadratic: bool = False
    # notes for DESIGN/EXPERIMENTS
    source: str = ""

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def padded_vocab(self) -> int:
        return _round_up(self.vocab_size, 256)

    def n_params(self) -> int:
        """Analytic parameter count (embedding + blocks), for 6ND roofline."""
        from repro.models import transformer  # local import, avoids cycle
        from repro.models import params as P
        return P.param_count(transformer.param_spec(self))

    def n_active_params(self) -> int:
        """Params touched per token (MoE: shared experts plus, of the held
        routed experts, the top_k x held / n_experts a token uses on
        average)."""
        total = self.n_params()
        if self.moe is None:
            return total
        m = self.moe
        per_expert = 3 * self.d_model * m.d_expert
        n_moe_layers = self.n_layers - m.first_dense
        inactive = (m.held - m.top_k * m.held / m.n_experts) \
            * per_expert * n_moe_layers
        return int(round(total - inactive))


def chip_share(cfg: ModelConfig, *, n_layers: int, n_held: int,
               vocab_size: int) -> ModelConfig:
    """One chip's share of an expert-parallel deployment of ``cfg``: its
    first ``n_layers`` layers (the rest lie on further pipeline stages),
    ``n_held`` of each MoE layer's routed experts (the router keeps all
    its outputs), and a slice of ``vocab_size`` rows of the vocabulary."""
    m = cfg.moe
    if m is None or not m.first_dense < n_layers <= cfg.n_layers:
        raise ValueError(f"{cfg.name}: no MoE layer in {n_layers} layers")
    if not 0 < n_held <= m.n_experts or vocab_size > cfg.vocab_size:
        raise ValueError(f"{cfg.name}: a share holds 1..{m.n_experts} "
                         f"experts and at most {cfg.vocab_size} ids")
    return dataclasses.replace(
        cfg, name=f"{cfg.name}-share", n_layers=n_layers,
        vocab_size=vocab_size, moe=dataclasses.replace(m, n_held=n_held))


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str          # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524288, 1),
}

# smoke-test shape (CPU, tiny)
SMOKE_SHAPE = ShapeConfig("smoke", "train", 64, 2)

ARCH_IDS: Sequence[str] = (
    "granite-3-2b",
    "qwen3-4b",
    "smollm-135m",
    "qwen1.5-110b",
    "musicgen-medium",
    "deepseek-v3-671b",
    "moonlight-16b-a3b",
    "internvl2-26b",
    "hymba-1.5b",
    "xlstm-1.3b",
)

_MODULES = {
    "granite-3-2b": "granite_3_2b",
    "qwen3-4b": "qwen3_4b",
    "smollm-135m": "smollm_135m",
    "qwen1.5-110b": "qwen1_5_110b",
    "musicgen-medium": "musicgen_medium",
    "deepseek-v3-671b": "deepseek_v3_671b",
    "moonlight-16b-a3b": "moonlight_16b_a3b",
    "internvl2-26b": "internvl2_26b",
    "hymba-1.5b": "hymba_1_5b",
    "xlstm-1.3b": "xlstm_1_3b",
}


def get(name: str, smoke: bool = False) -> ModelConfig:
    import importlib

    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[name]}")
    return mod.smoke() if smoke else mod.CONFIG


def cells(arch: str):
    """The (arch x shape) dry-run cells for one arch, honoring skips."""
    cfg = get(arch)
    out = []
    for s in ("train_4k", "prefill_32k", "decode_32k", "long_500k"):
        if s == "long_500k" and not cfg.sub_quadratic:
            continue
        out.append(SHAPES[s])
    return out
