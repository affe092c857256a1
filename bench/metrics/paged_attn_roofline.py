"""Share of its roofline that the paged decode attention reaches, in %:
the HBM time of the bytes it needs (each active row's K and V for its valid
length, plus query and output, over every layer and step in the window;
work.paged_attn_bytes) over the summed device time of the kernel's ops."""
import re

import work

UNIT = "%"
LAYER = "paged attention kernel (kernels/paged_attention.py)"
MOVES = "itl_p95_ms"
OPS = r"paged"          # the Pallas kernel's op name in the trace


def read(rec):
    tr = rec.get("trace")
    if tr is None:
        return None
    rx = re.compile(OPS)
    seconds = sum(v for k, v in tr.op_s.items() if rx.search(k))
    if seconds <= 0:
        return None
    nbytes = sum(work.paged_attn_bytes(rec["config"], s[3]) for s in rec["steps"])
    return 100.0 * nbytes / rec["peaks"].hbm_bytes / seconds
