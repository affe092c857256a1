"""§Kernel roofline: place each Pallas kernel on the compute/memory roofline
and gate the two-level histogram speedup.

Off-TPU the Pallas kernels only execute in interpret mode, whose
``cost_analysis()`` prices the python interpreter machinery rather than the
kernel math — so FLOP/byte counts come from jnp *mirror* functions that
spell out exactly the arithmetic the kernel bodies do (DCT matmul + one-hot
binning matmuls), compiled by XLA. On TPU the real kernels are compiled and
additionally wall-timed, giving a hardware-honest achieved fraction.

The two-level gate: the coarse(32) + refine(16) histogram passes must cost
>= 3x fewer FLOPs than the flat 512-bin pass they replaced (ISSUE 10
acceptance). ``run(quick=True)`` asserts it; the full run records the
``kernel_roofline`` section of ``BENCH_runtime.json``.
"""
from __future__ import annotations

import numpy as np

from benchmarks import common

GATE_SPEEDUP = 3.0


# ---------------------------------------------------------------------------
# jnp mirrors of the kernel bodies (same math, XLA-compiled) — used for
# FLOP/byte accounting off-TPU where interpret-mode cost_analysis would
# price the interpreter, not the kernel.
# ---------------------------------------------------------------------------

def _mirrors():
    import jax.numpy as jnp

    from repro.kernels import ref

    def _abs_bins(y):
        a = jnp.abs(y).reshape(-1)
        return a * a, ref.bin_index(a)

    def _onehot(idx, nbins):
        return (idx[:, None] == jnp.arange(nbins)[None, :]).astype(jnp.float32)

    def flat_hist(xb):
        y = ref.dct_blocks(xb)
        a2, idx = _abs_bins(y)
        oh = _onehot(idx, ref.NBINS)
        return y, jnp.sum(oh, axis=0), a2 @ oh

    def coarse_hist(xb):
        y = ref.dct_blocks(xb)
        a2, idx = _abs_bins(y)
        oh = _onehot(idx // ref.NBINS_FINE, ref.NBINS_COARSE)
        return y, jnp.sum(oh, axis=0), a2 @ oh

    def refine_hist(y, coarse):
        a2, idx = _abs_bins(y)
        member = (idx // ref.NBINS_FINE) == coarse
        fine = jnp.where(member, idx - coarse * ref.NBINS_FINE, 0)
        oh = _onehot(fine, ref.NBINS_FINE) * member[:, None]
        return jnp.sum(oh, axis=0), a2 @ oh

    def threshold_quant(y, t):
        return ref.quantize_blocks(y, t)

    def dequant_idct(q, s):
        return ref.idct_blocks(ref.dequantize_blocks(q, s))

    return {"dct_hist": flat_hist, "dct_hist_coarse": coarse_hist,
            "hist_refine": refine_hist, "threshold_quant": threshold_quant,
            "dequant_idct": dequant_idct}


def run(quick: bool = True) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref, spectral_lossy as K
    from repro.kernels import paged_attention as PK
    from repro.roofline.analysis import V5E
    from repro.roofline.kernels import kernel_report

    on_tpu = jax.default_backend() == "tpu"
    # on the chip: its own peaks; off it, the mirrors price a v5e
    kind = None if on_tpu else V5E
    n_blocks = 256 if quick else 4096          # 64K / 1M elements
    rng = np.random.default_rng(0)
    xb = jnp.asarray(rng.standard_normal((n_blocks, ref.BLOCK)), jnp.float32)
    y = ref.dct_blocks(xb)
    t = jnp.full((n_blocks,), 1e-2, jnp.float32)
    q, s = ref.quantize_blocks(y, t)
    coarse = jnp.int32(17)

    reports = {}
    if on_tpu:
        # compiled Pallas kernels: cost_analysis is the real lowered cost
        # and wall time is hardware-honest.
        import functools
        cases = {
            "dct_hist": (functools.partial(K.dct_hist, interpret=False),
                         (xb,)),
            "dct_hist_tiled": (functools.partial(K.dct_hist_tiled,
                                                 interpret=False), (xb,)),
            "dct_hist_coarse": (functools.partial(K.dct_hist_coarse,
                                                  interpret=False), (xb,)),
            "hist_refine": (functools.partial(K.hist_refine,
                                              interpret=False), (y, coarse)),
            "threshold_quant": (functools.partial(K.threshold_quant,
                                                  interpret=False), (y, t)),
            "dequant_idct": (functools.partial(K.dequant_idct,
                                               interpret=False), (q, s)),
        }
        for name, (fn, fargs) in cases.items():
            reports[name] = kernel_report(fn, fargs, name=name, measure=True,
                                          device_kind=kind)
    else:
        mirrors = _mirrors()
        for name, fn in mirrors.items():
            fargs = {"hist_refine": (y, coarse),
                     "threshold_quant": (y, t),
                     "dequant_idct": (q, s)}.get(name, (xb,))
            reports[name] = kernel_report(fn, fargs, name=name, measure=True,
                                          device_kind=kind)
        # tiled flat pass does the same arithmetic per element as the
        # global-accumulation pass; mirror cost is shared.
        import dataclasses
        reports["dct_hist_tiled"] = dataclasses.replace(
            reports["dct_hist"], name="dct_hist_tiled",
            note="mirror cost shared with dct_hist (same per-element math)")

    # paged attention rides along at a decode-like shape; off-TPU this is
    # the interpret-mode artifact (cost note says so).
    b, pps, ps, n_kv, hq, d = 4, 4, 16, 2, 8, 64
    kp = jnp.asarray(rng.standard_normal((b * pps + 1, ps, n_kv, d)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((b * pps + 1, ps, n_kv, d)),
                     jnp.float32)
    qq = jnp.asarray(rng.standard_normal((b, 1, hq, d)), jnp.float32)
    table = jnp.asarray(rng.permutation(b * pps).reshape(b, pps) + 1,
                        jnp.int32)
    lengths = jnp.asarray(rng.integers(1, pps * ps, b), jnp.int32)
    import functools as _ft
    pa = kernel_report(
        _ft.partial(PK.paged_decode_attention, interpret=not on_tpu),
        (qq, kp, vp, table, lengths), name="paged_attention",
        measure=on_tpu, device_kind=kind)
    if not on_tpu:
        pa.note = ((pa.note + "; ") if pa.note else "") + \
            "interpret-mode lowering: cost reflects the emulation, not the kernel"
    reports["paged_attention"] = pa

    # -- two-level gate -----------------------------------------------------
    flat = reports["dct_hist"]
    coarse_r = reports["dct_hist_coarse"]
    refine_r = reports["hist_refine"]
    if on_tpu:
        # wall time on hardware
        speedup = flat.measured_s / (coarse_r.measured_s + refine_r.measured_s)
        basis = "measured_s"
    else:
        speedup = flat.flops / (coarse_r.flops + refine_r.flops)
        basis = "flops"
    elems = n_blocks * ref.BLOCK
    metrics = {
        "backend": jax.default_backend(),
        "quick": quick,
        "n_blocks": n_blocks,
        "kernels": {n: r.to_dict() for n, r in reports.items()},
        "two_level": {
            "basis": basis,
            "flat_cost": flat.measured_s if on_tpu else flat.flops,
            "two_level_cost": ((coarse_r.measured_s + refine_r.measured_s)
                               if on_tpu
                               else coarse_r.flops + refine_r.flops),
            "speedup": speedup,
            "flat_flops_per_elem": flat.flops / elems,
            "two_level_flops_per_elem":
                (coarse_r.flops + refine_r.flops) / elems,
        },
        "tuned_tiles": {repr(k): v for k, v in ops.tuned_tiles().items()},
    }
    for name, r in reports.items():
        common.row(f"kernel_roofline/{name}",
                   (r.measured_s or r.roofline_s) * 1e6,
                   f"bound={r.bound};intensity={r.intensity:.1f};"
                   f"flops={r.flops:.3g};bytes={r.bytes_accessed:.3g}")
    common.row("kernel_roofline/two_level_speedup", 0.0,
               f"{speedup:.2f}x ({basis})")
    assert speedup >= GATE_SPEEDUP, (
        f"two-level histogram pass only {speedup:.2f}x cheaper than the "
        f"flat 512-bin pass (gate: {GATE_SPEEDUP}x, basis: {basis})")
    return metrics


if __name__ == "__main__":
    import argparse
    import json

    from benchmarks import handoff_overlap

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="small payload; gates the two-level speedup only")
    ap.add_argument("--out", default=None,
                    help="merge the kernel_roofline section into this "
                         "artifact (default: BENCH_runtime.json on --full)")
    args = ap.parse_args()
    quick = not args.full
    metrics = run(quick=quick)
    out = args.out or (None if quick else handoff_overlap.ARTIFACT)
    if out:
        try:
            with open(out) as f:
                artifact = json.load(f)
        except (FileNotFoundError, json.JSONDecodeError):
            artifact = {}
        artifact["kernel_roofline"] = metrics
        handoff_overlap.write_artifact(artifact, path=out)
        print(f"# wrote kernel_roofline into {out}")
