"""Mean milliseconds of one successful ``PagedServingEngine.admit``
(padded prefill, insert, first token to the host), from the harness's own
span around each call that ends in the window."""
UNIT = "ms"
LAYER = "engine admission and prefill (serving/pages.py admit)"
MOVES = "ttft_p90_ms"


def read(rec):
    a = rec["admits"]
    return 1e3 * sum(t1 - t0 for t0, t1, _ in a) / len(a) if a else None
