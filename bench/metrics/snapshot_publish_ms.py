"""Mean milliseconds of host work per published snapshot: the worker-side
``insitu-async/<task>`` spans (delta encode and store) that end in the
window, over their number."""
UNIT = "ms"
LAYER = "snapshot store (serving/snapshot.py)"
MOVES = "state_age_p95_s"


def read(rec):
    w0, w1 = rec["window"]
    name = f"insitu-async/{rec['task']}"
    d = [t1 - t0 for n, t0, t1, _, _ in rec["spans"] if n == name and w0 < t1 <= w1]
    return 1e3 * sum(d) / len(d) if d else None
