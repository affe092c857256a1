"""Milliseconds per training step that the loop thread spends in in-situ
work instead of stepping: the device stage, the hand-off dispatch, any
blocking hand-off and backpressure waits, from the program's spans."""
UNIT = "ms"
LAYER = "in-situ runtime (core/session.py, core/runtime.py)"
MOVES = "train_tokens_per_s"
NAMES = ("insitu-device/", "handoff/dispatch", "step/handoff", "staging/wait")


def read(rec):
    w0, w1 = rec["window"]
    total = sum(max(0.0, min(t1, w1) - max(t0, w0))
                for name, t0, t1, thread, _ in rec["spans"]
                if thread == rec["main_thread"] and name.startswith(NAMES))
    return 1e3 * total / rec["steps_in_window"]
