"""Model FLOP utilization of a MoE training step, in % of the chip's bf16
peak: the share's model FLOPs (work_moe.step_flops: 6 per parameter per
token outside the routed experts, 6 x 3·d·f per (token, slot) pair the held
experts computed, from the program's ``moe/routed_pairs`` counter over the
window, and causal MLA attention; no recomputation) over the window, over
the peak. Nothing to read from a program that counts no routed pairs."""
import work_moe

UNIT = "%"
LAYER = "train step (launch/train.py)"
MOVES = "train_tokens_per_s"


def read(rec):
    pairs = rec.get("counters", {}).get("moe/routed_pairs")
    if not pairs:
        return None
    cfg, tr, steps = rec["config"], rec["traffic"], rec["steps_in_window"]
    flops = steps * work_moe.step_flops(cfg, seq_len=tr["seq_len"],
                                        batch=tr["batch"],
                                        routed_pairs=pairs / steps)
    return 100.0 * flops / rec["window_s"] / rec["peaks"].bf16_flops
