"""Model FLOP utilization of serving, in % of the chip's bf16 peak: the
forward FLOPs of the prompts admitted in the window (their own tokens, not
the prefill's padding) and of every token decoded in it, over the window,
over the peak (see work.serve_flops)."""
import work

UNIT = "%"
LAYER = "serving step (serving/pages.py)"
MOVES = "itl_p95_ms"


def read(rec):
    flops = work.serve_flops(rec["config"], [a[2] for a in rec["admits"]],
                             [c for s in rec["steps"] for c in s[3]])
    return 100.0 * flops / rec["window_s"] / rec["peaks"].bf16_flops
