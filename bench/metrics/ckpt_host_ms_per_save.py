"""Milliseconds of host work per checkpoint save: the worker-side
``insitu-async/checkpoint`` spans (host codec stages and the fsynced sink)
that end in the window, over their number."""
UNIT = "ms"
LAYER = "host codecs and sink (core/codecs.py, checkpoint/serialization.py)"
MOVES = "state_age_p95_s"


def read(rec):
    w0, w1 = rec["window"]
    dts = [t1 - t0 for name, t0, t1, _, _ in rec["spans"]
           if name == "insitu-async/checkpoint" and w0 < t1 <= w1]
    return 1e3 * sum(dts) / len(dts) if dts else None
