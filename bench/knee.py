"""Find the highest request rate a serving cell sustains: a sweep of
offered rates on the chip, in one process, with one engine.

    python3 bench/knee.py --config bench/configs/<c>.json --traffic <t>.json \
        --rates 2,4,6 --seconds 20

For each rate it offers the traffic (open loop, the file's lengths and
plan) for ``lead_in_s + seconds`` and fits a line to the number of
requests in flight (due and not finished) at each engine step of the
window. The knee is the highest rate whose in-flight count does not grow
through the window. The engine is drained between rates, untimed. A
serving cell's runs never run this: their rate is fixed in the traffic
file, from this sweep.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))


def in_flight_slope(served, unadmitted, t0, steps, t_open, t_close):
    """Least-squares slope (requests per second) of the in-flight count
    sampled at the end of every step in the window, and its mean."""
    spans = []
    for s in list(served) + list(unadmitted):
        end = s.times[-1] if s.req.done else np.inf
        spans.append((t0 + s.arrival.due, end))
    ts = np.array([st[2] for st in steps if t_open < st[2] <= t_close])
    if len(ts) < 2:
        return float("nan"), float("nan")
    n = np.array([sum(1 for a, b in spans if a <= t < b) for t in ts], float)
    return float(np.polyfit(ts - ts[0], n, 1)[0]), float(n.mean())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH.parent / "src"))
    import harness
    from repro.core import Session
    from repro.launch.compile_cache import enable_compile_cache

    cell = harness.Cell(
        name="knee", chips=1, config_name=Path(args.config).stem,
        config=json.loads(Path(args.config).read_text()),
        traffic_name=Path(args.traffic).stem,
        traffic=json.loads(Path(args.traffic).read_text()),
        end_to_end=[], per_layer=[])
    harness.check_device(cell.chips)
    enable_compile_cache()
    drv = harness.load_generator(cell.traffic["kind"])
    tr = dict(cell.traffic)
    task, stream, every = drv.plan_task(tr["plan"])
    cfg, e, params, engine = drv.build(cell, args.seed, False)
    with Session(tr["plan"], raise_on_error=True) as session:
        rates = [float(r) for r in args.rates.split(",")]
        arrivals = [a for rate in rates for a in drv.draw_arrivals(
            dict(tr, rate_per_s=rate), args.seed, args.seconds, cfg["vocab_size"])]
        drv.warm_up(engine, session, stream, arrivals)
        log = {"admit": [], "step": [], "emit": []}
        for rate in rates:
            tr["rate_per_s"] = rate
            arrivals = drv.draw_arrivals(tr, args.seed, args.seconds, cfg["vocab_size"])
            log.update(admit=[], step=[], emit=[])
            t0 = time.perf_counter()
            t_open = t0 + tr["lead_in_s"]
            t_close = t_open + args.seconds
            served = drv.serve(engine, session, stream, arrivals, t0, t_close, log)
            lat = drv.latency(served, log["unadmitted"], t0, t_open, t_close)
            slope, mean = in_flight_slope(served, log["unadmitted"], t0,
                                          log["step"], t_open, t_close)
            steps = [s for s in log["step"] if t_open < s[2] <= t_close]
            toks = sum(len(s[3]) for s in steps)
            print(json.dumps({
                "rate_per_s": rate, "in_flight_slope_per_s": slope,
                "in_flight_mean": mean,
                "ttft_p90_ms": 1e3 * float(np.percentile(lat["ttft"], 90)),
                "itl_p95_ms": 1e3 * float(np.percentile(lat["itl"], 95)),
                "decode_tokens_per_s": toks / args.seconds,
                "steps": len(steps),
                "step_ms_mean": 1e3 * float(np.mean([s[2] - s[1] for s in steps])),
                "admit_ms_mean": 1e3 * float(np.mean([a[1] - a[0] for a in log["admit"]])),
                "rows_mean": float(np.mean([len(s[3]) for s in steps])),
                "unadmitted_at_close": len(log["unadmitted"]),
                "memory_peak_bytes": harness.memory_peak_bytes(1)}), flush=True)
            # drain, untimed: finish every admitted request
            while any(a is not None for a in engine.active):
                engine.step()
            session.wait_idle()
    return 0


if __name__ == "__main__":
    sys.exit(main())
