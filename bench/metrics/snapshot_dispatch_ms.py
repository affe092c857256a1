"""Mean milliseconds the serving loop spends dispatching one snapshot's
device-to-host copy (``handoff/dispatch`` spans in the window, one per
firing that was not shed)."""
UNIT = "ms"
LAYER = "in-situ runtime (core/session.py, core/runtime.py)"
MOVES = "itl_p95_ms"


def read(rec):
    w0, w1 = rec["window"]
    d = [t1 - t0 for name, t0, t1, _, _ in rec["spans"]
         if name == "handoff/dispatch" and w0 < t1 <= w1]
    return 1e3 * sum(d) / len(d) if d else None
