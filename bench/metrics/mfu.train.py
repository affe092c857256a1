"""Model FLOP utilization of training, in % of the chip's bf16 peak: the
step's model FLOPs (6·N·D plus causal attention, no recomputation; see
work.model_flops) times the steps in the window, over the window, over the
peak."""
import work

UNIT = "%"
LAYER = "train step (launch/train.py)"
MOVES = "train_tokens_per_s"


def read(rec):
    cfg, tr = rec["config"], rec["traffic"]
    flops = work.model_flops(cfg, kind="train", seq_len=tr["seq_len"],
                             batch=tr["batch"])
    return 100.0 * flops * rec["steps_in_window"] / rec["window_s"] \
        / rec["peaks"].bf16_flops
