"""Three-term roofline from a compiled dry-run artifact (no hardware needed).

  compute    = HLO_FLOPs_per_device / peak_FLOP/s
  memory     = HLO_bytes_per_device / HBM_bw
  collective = wire_bytes_per_device / ICI_link_bw

The SPMD-partitioned HLO is a *per-device* program, so cost_analysis() flops/
bytes are already per-device; dividing global quantities by chip count gives
the same numbers (the brief's formulas). Collective wire bytes are parsed
from the HLO text: for every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute we take the per-device operand size and
apply the standard ring-algorithm wire multiplier:

  all-reduce       2 * s * (g-1)/g      (reduce-scatter + all-gather phases)
  all-gather       out * (g-1)/g        (each shard forwarded g-1 times)
  reduce-scatter   in * (g-1)/g
  all-to-all       s * (g-1)/g
  collective-permute  s                 (one hop)

Hardware peaks come from one table keyed by the JAX ``device_kind`` of the
chip the program runs on (or is modelled for); a kind without a row is an
error, never a default.
"""
from __future__ import annotations

import json
import re
from dataclasses import dataclass, field, asdict
from typing import Any, Optional


@dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks."""
    flops_bf16: float        # FLOP/s
    hbm_bw: float            # HBM bytes/s
    ici_link_bw: float       # bytes/s per ICI link
    source: str

    @property
    def ridge_intensity(self) -> float:
        """FLOPs per HBM byte at the roofline's knee."""
        return self.flops_bf16 / self.hbm_bw


V5E = "TPU v5 lite"          # jax.Device.device_kind of a TPU v5e chip

PEAKS: dict[str, ChipPeaks] = {
    # 1,600 Gbit/s of chip-to-chip interconnect over 4 links = 50 GB/s each
    V5E: ChipPeaks(197e12, 819e9, 50e9,
                   'Google Cloud documentation, "TPU v5e"'),
}


def peaks(device_kind: str) -> ChipPeaks:
    """The peaks of ``device_kind``; raises for a kind with no row."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                     "all-to-all", "collective-permute")

# `%x.1 = bf16[16,1024]{1,0} all-gather(...)` — also matches tuple-less async
_OP_RE = re.compile(
    r"=\s*(?:\()?\s*(\w+)\[([\d,]*)\][^\s]*\s+"
    r"(all-reduce-start|all-reduce|all-gather-start|all-gather|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)"
    r"\(")

_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


@dataclass
class CollectiveOp:
    kind: str
    dtype: str
    shape: tuple
    elem_bytes: int
    group_size: int
    wire_bytes: float

    @property
    def tensor_bytes(self) -> int:
        n = 1
        for d in self.shape:
            n *= d
        return n * self.elem_bytes


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        first = m.group(1)
        return len([t for t in first.split(",") if t.strip() != ""])
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        # replica_groups=[G,g]<=[...]: G groups of g members
        return int(m.group(2))
    return default


def _wire_multiplier(kind: str, g: int) -> float:
    if kind.startswith("collective-permute"):
        return 1.0            # one hop, independent of any group annotation
    if g <= 1:
        return 0.0
    frac = (g - 1) / g
    if kind.startswith("all-reduce"):
        return 2.0 * frac
    if kind.startswith("all-gather"):
        return frac           # applied to the (gathered) result size below
    if kind.startswith("reduce-scatter"):
        return frac           # applied to the (full) operand size
    if kind.startswith("all-to-all"):
        return frac
    return 1.0                # collective-permute: one hop


def parse_collectives(hlo_text: str, default_group: int = 1
                      ) -> list[CollectiveOp]:
    ops: list[CollectiveOp] = []
    for line in hlo_text.splitlines():
        if not any(k in line for k in _COLLECTIVE_KINDS):
            continue
        if "-done" in line:          # async pair: count the -start only
            continue
        m = _OP_RE.search(line)
        if not m:
            continue
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        shape = tuple(int(d) for d in dims.split(",") if d) if dims else ()
        g = _group_size(line, default_group)
        if dtype not in _DTYPE_BYTES:
            # exotic element type (e.g. f8e8m0): keep the op with zero
            # elem/wire bytes instead of dropping it silently — analyze()
            # surfaces the undercount in the report note and per-kind table.
            ops.append(CollectiveOp(kind.replace("-start", ""), dtype, shape,
                                    0, g, 0.0))
            continue
        eb = _DTYPE_BYTES[dtype]
        n = 1
        for d in shape:
            n *= d
        size = n * eb
        # result-size semantics per kind: all-gather result is the gathered
        # tensor; reduce-scatter result is the shard (operand = shard * g)
        if kind.startswith("reduce-scatter"):
            wire = size * g * _wire_multiplier(kind, g)
        else:
            wire = size * _wire_multiplier(kind, g)
        ops.append(CollectiveOp(kind.replace("-start", ""), dtype, shape, eb,
                                g, wire))
    return ops


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops_per_device: float
    hlo_bytes_per_device: float
    wire_bytes_per_device: float
    compute_s: float
    memory_s: float                    # structural model (see memory_model)
    collective_s: float
    model_flops_global: float
    useful_flops_ratio: float          # MODEL_FLOPS / (HLO_FLOPs * chips)
    bottleneck: str
    device_kind: str                   # whose peaks price the terms
    hlo_memory_s: float = 0.0          # unfused upper bound, reference only
    model_bytes_per_device: float = 0.0
    collectives_by_kind: dict = field(default_factory=dict)
    memory_per_device_bytes: Optional[dict] = None
    note: str = ""

    @property
    def step_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def roofline_fraction(self) -> float:
        """useful-compute time / modelled step time (MFU-like, structural)."""
        if self.step_s <= 0 or self.chips <= 0:
            return 0.0
        ideal = self.model_flops_global / (
            self.chips * peaks(self.device_kind).flops_bf16)
        return ideal / self.step_s

    def to_json(self) -> str:
        d = asdict(self)
        d["step_s"] = self.step_s
        d["roofline_fraction"] = self.roofline_fraction()
        return json.dumps(d, indent=1)


def analyze(*, arch: str, shape: str, mesh_desc: str, chips: int,
            device_kind: str, cost: dict, hlo_text: str,
            model_flops_global: float,
            memory_stats: Optional[dict] = None,
            default_group: int = 1,
            wire_bytes_override: Optional[float] = None,
            model_bytes_per_device: Optional[float] = None) -> RooflineReport:
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    ops = parse_collectives(hlo_text, default_group)
    wire = (wire_bytes_override if wire_bytes_override is not None
            else sum(o.wire_bytes for o in ops))
    by_kind: dict[str, dict] = {}
    unknown = [o for o in ops if o.elem_bytes == 0]
    for o in ops:
        e = by_kind.setdefault(o.kind, {"count": 0, "wire_bytes": 0.0,
                                        "tensor_bytes": 0})
        e["count"] += 1
        e["wire_bytes"] += o.wire_bytes
        e["tensor_bytes"] += o.tensor_bytes
        if o.elem_bytes == 0:
            e["unknown_dtype"] = e.get("unknown_dtype", 0) + 1
    note = ""
    if unknown:
        dts = ", ".join(sorted({o.dtype for o in unknown}))
        note = (f"{len(unknown)} collective op(s) with unknown dtype(s) "
                f"[{dts}] counted with zero wire bytes — collective term "
                "is a lower bound")
    chip = peaks(device_kind)
    compute_s = flops / chip.flops_bf16
    hlo_memory_s = byts / chip.hbm_bw
    mem_bytes = (model_bytes_per_device if model_bytes_per_device is not None
                 else byts)
    memory_s = mem_bytes / chip.hbm_bw
    collective_s = wire / chip.ici_link_bw
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    useful = (model_flops_global / (flops * chips)) if flops > 0 else 0.0
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_desc, chips=chips,
        hlo_flops_per_device=flops, hlo_bytes_per_device=byts,
        wire_bytes_per_device=wire, compute_s=compute_s, memory_s=memory_s,
        collective_s=collective_s, model_flops_global=model_flops_global,
        useful_flops_ratio=useful, bottleneck=bottleneck,
        device_kind=device_kind,
        hlo_memory_s=hlo_memory_s,
        model_bytes_per_device=float(mem_bytes),
        collectives_by_kind=by_kind, memory_per_device_bytes=memory_stats,
        note=note)


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS: 6·N_active·D for training (fwd+bwd), 2·N_active·D for
    forward-only kinds (prefill/decode), plus the causal attention term
    (4 flops per q·k pair fwd, 12 with backward)."""
    tokens = shape.global_batch * (1 if shape.kind == "decode"
                                   else shape.seq_len)
    n = cfg.n_active_params()
    param_mult = 6.0 if shape.kind == "train" else 2.0
    attn_mult = 12.0 if shape.kind == "train" else 4.0
    base = param_mult * n * tokens
    hd = cfg.resolved_head_dim
    s_kv = shape.seq_len
    causal_frac = 0.5 if shape.kind != "decode" else 1.0
    attn = (attn_mult * cfg.n_layers * cfg.n_heads * hd * s_kv * causal_frac
            * tokens)
    return base + attn
