"""Run one cell of the benchmark and print its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for. The cell, its configuration, traffic and metrics are read by
name from BENCHMARK.json and bench/ (see bench/harness.py). The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, last, ``checks``:
each number that decided ``correct`` beside its limit, which are also the
last lines of standard error. With no TPU, too few chips, or a device kind
missing from the peaks table, it exits non-zero and prints no result.

JAX's persistent compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` if
set, else ``.jax_cache`` in the checkout; run-scoped files (checkpoints,
traces) go to ``.bench_run/`` in the checkout and are removed at exit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    try:
        import jax
        from repro.launch.compile_cache import enable_compile_cache
        cell = harness.load_cell(args.workload, ROOT)
        harness.check_device(cell.chips)
        enable_compile_cache()
        # every program goes to the cache, however quick its compile, so
        # that a run after the first compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), T_START, root=ROOT, cell=cell)
    except (harness.BenchError, ImportError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
