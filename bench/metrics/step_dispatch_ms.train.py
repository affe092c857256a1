"""Milliseconds per training step that the loop thread spends in the jitted
step call until it returns (``step/dispatch``, inside ``step/compute``),
from the program's spans: the host's share of launching a step."""
UNIT = "ms"
LAYER = "train step host dispatch (launch/train.py train_loop)"
MOVES = "train_tokens_per_s"


def read(rec):
    w0, w1 = rec["window"]
    dts = [min(t1, w1) - max(t0, w0) for name, t0, t1, thread, _ in rec["spans"]
           if thread == rec["main_thread"] and name == "step/dispatch"
           and t0 < w1 and t1 > w0]
    return 1e3 * sum(dts) / rec["steps_in_window"] if dts else None
