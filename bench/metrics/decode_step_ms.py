"""Mean milliseconds of one ``PagedServingEngine.step`` (the decode
program, its host sync and the host row loop), from the harness's own
span around each call that ends in the window."""
UNIT = "ms"
LAYER = "engine step (serving/pages.py step)"
MOVES = "itl_p95_ms"


def read(rec):
    s = rec["steps"]
    return 1e3 * sum(t1 - t0 for _, t0, t1, _ in s) / len(s) if s else None
