"""Milliseconds of sink work per checkpoint save: every ``sink/checkpoint``
attempt (fsynced shard writes, manifest, commit, retention) of each save
whose last attempt ends in the window, summed, over the number of saves."""
UNIT = "ms"
LAYER = "checkpoint sink (checkpoint/manager.py, checkpoint/serialization.py)"
MOVES = "state_age_p95_s"


def read(rec):
    w0, w1 = rec["window"]
    by_step: dict = {}
    for name, t0, t1, _, step in rec["spans"]:
        if name == "sink/checkpoint":
            total, end = by_step.get(step, (0.0, t1))
            by_step[step] = (total + t1 - t0, max(end, t1))
    saves = [total for total, end in by_step.values() if w0 < end <= w1]
    return 1e3 * sum(saves) / len(saves) if saves else None
