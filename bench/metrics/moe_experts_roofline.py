"""Share of its roofline that the grouped expert matmuls reach, in %: the
least time the chip could take for the (token, slot) pairs the held experts
computed in the window (the program's ``moe/routed_pairs`` counter;
work_moe.expert_matmul_work: gate, up and down, forward, rematerialised
forward and both gradients), over the summed device time of the grouped
matmul kernels in the trace: XLA lowers ``jax.lax.ragged_dot`` to TPU
kernels whose ops print as ``%ragged-dot-none.<n> = ...`` (and one
``%ragged-dot-metadata``); an op that only takes one as an operand does not
count."""
import re

import work
import work_moe

UNIT = "%"
LAYER = "grouped expert matmuls (models/moe.py)"
MOVES = "train_tokens_per_s"
OPS = r"^%?ragged-dot"


def read(rec):
    tr = rec.get("trace")
    pairs = rec.get("counters", {}).get("moe/routed_pairs")
    if tr is None or not pairs:
        return None
    rx = re.compile(OPS)
    seconds = sum(v for k, v in tr.op_s.items() if rx.search(k))
    if seconds <= 0:
        return None
    cfg = rec["config"]
    layers = (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]) \
        * rec["steps_in_window"]
    flops, nbytes = work_moe.expert_matmul_work(cfg, pairs, layers)
    return 100.0 * work.ideal_seconds(flops, nbytes, rec["peaks"]) / seconds
