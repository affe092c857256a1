"""Share of the training window in which no operation ran on the device,
in %, from the profiler trace (1 - union of device-op intervals / window)."""
UNIT = "%"
LAYER = "device"
MOVES = "train_tokens_per_s"


def read(rec):
    tr = rec.get("trace")
    return None if tr is None else 100.0 * tr.idle_share
