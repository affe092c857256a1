"""Milliseconds the loop thread spends, inside the window, waiting for the
in-situ work it could not overlap: ``insitu/wait_idle`` (outstanding
firings, such as the last save's host stages) and ``insitu/drain`` (ring
close and worker join), from the program's spans. Nothing to read on a
program that records no ``insitu/wait_idle``."""
UNIT = "ms"
LAYER = "in-situ runtime (core/session.py, core/runtime.py)"
MOVES = "train_tokens_per_s"
NAMES = ("insitu/wait_idle", "insitu/drain")


def read(rec):
    w0, w1 = rec["window"]
    hits = [(name, min(t1, w1) - max(t0, w0))
            for name, t0, t1, thread, _ in rec["spans"]
            if thread == rec["main_thread"] and name in NAMES
            and t0 < w1 and t1 > w0]
    if not any(name == "insitu/wait_idle" for name, _ in hits):
        return None
    return 1e3 * sum(dt for _, dt in hits)
