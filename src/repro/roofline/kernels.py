"""Per-kernel roofline placement from compiled cost analysis.

``kernel_report(fn, args)`` lowers + compiles ``fn`` with ``jax.jit`` and
reads ``cost_analysis()`` flops / bytes-accessed to place the kernel on the
single-chip compute/memory roofline of the chip it runs on, or of
``device_kind`` (peaks from the table in ``analysis.py``):

  compute_s = flops / peak bf16 FLOP/s
  memory_s  = bytes / HBM bytes/s
  bound     = whichever ceiling is higher; intensity vs the ridge point
              (peak_flops / hbm_bw) tells the same story per byte.

``measure=True`` additionally times the compiled executable and records the
achieved fraction (roofline time / measured time). Off-TPU both numbers
describe the *interpret/XLA-CPU* artifact, not the TPU kernel — callers that
want hardware-honest FLOP counts off-TPU pass ``flops_override`` /
``bytes_override`` from an analytic model or a jnp mirror of the kernel math
(see ``benchmarks/kernel_roofline.py``).
"""
from __future__ import annotations

import time
from dataclasses import asdict, dataclass
from typing import Optional

import jax

from repro.roofline.analysis import peaks


@dataclass
class KernelReport:
    name: str
    flops: float
    bytes_accessed: float
    intensity: float                  # flops per HBM byte
    ridge_intensity: float            # peak_flops / hbm_bw
    compute_s: float
    memory_s: float
    roofline_s: float                 # max(compute_s, memory_s)
    bound: str                        # "compute" | "memory"
    measured_s: Optional[float] = None
    achieved_fraction: Optional[float] = None   # roofline_s / measured_s
    note: str = ""

    def to_dict(self) -> dict:
        return asdict(self)


def _cost_dict(compiled) -> dict:
    """cost_analysis() is a dict, a list of dicts (one per computation), or
    None depending on backend/jax version — normalise to one dict."""
    try:
        cost = compiled.cost_analysis()
    except Exception:  # noqa: BLE001  backends may not implement it
        return {}
    if cost is None:
        return {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    try:
        return dict(cost)
    except TypeError:
        return {}


def kernel_report(fn, args, *, name: str = "", measure: bool = False,
                  iters: int = 3, device_kind: Optional[str] = None,
                  flops_override: Optional[float] = None,
                  bytes_override: Optional[float] = None) -> KernelReport:
    """Compile ``fn(*args)`` and place it on the compute/memory roofline
    of ``device_kind`` (default: the kind of ``jax.devices()[0]``, which
    raises off a chip with published peaks)."""
    chip = peaks(device_kind or jax.devices()[0].device_kind)
    jfn = jax.jit(fn)
    compiled = jfn.lower(*args).compile()
    cost = _cost_dict(compiled)
    note = "" if cost else "cost_analysis unavailable on this backend"
    flops = float(cost.get("flops", 0.0) if flops_override is None
                  else flops_override)
    byts = float(cost.get("bytes accessed", 0.0) if bytes_override is None
                 else bytes_override)
    compute_s = flops / chip.flops_bf16
    memory_s = byts / chip.hbm_bw
    roofline_s = max(compute_s, memory_s)
    intensity = flops / byts if byts > 0 else 0.0
    measured = None
    achieved = None
    if measure:
        out = compiled(*args)
        jax.block_until_ready(out)     # warm-up outside the timer
        t0 = time.perf_counter()
        for _ in range(iters):
            out = compiled(*args)
        jax.block_until_ready(out)
        measured = (time.perf_counter() - t0) / iters
        achieved = roofline_s / measured if measured > 0 else 0.0
        if jax.default_backend() != "tpu":
            note = (note + "; " if note else "") + \
                "measured off-TPU: achieved fraction is not hardware-honest"
    return KernelReport(
        name=name or getattr(fn, "__name__", "kernel"),
        flops=flops, bytes_accessed=byts, intensity=intensity,
        ridge_intensity=chip.ridge_intensity, compute_s=compute_s,
        memory_s=memory_s, roofline_s=roofline_s,
        bound="compute" if compute_s >= memory_s else "memory",
        measured_s=measured, achieved_fraction=achieved, note=note)
