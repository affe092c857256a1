"""The control of ``correct``: the reference in a lower precision, put in
the program's place, must come out as not correct.

    python3 bench/control.py --workload <name> --seeds 11,12,13 [--variant half_batch]

The configurations state bfloat16, so the control rounds every matrix
operand to float8 (e4m3) and is then compared by the same function and
against the same limits as a run of the program. It prints one JSON line
per seed with the numbers compared, and exits non-zero if any seed would
pass. The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

LOW = "float8_e4m3fn"


def training_control(cell, seed: int, *, variant: str = "float8") -> dict:
    """The numbers a training cell compares, with the control (or a planted
    fault) in the program's place, and whether they pass the cell's limits.

    ``float8``: the reference with every matrix operand rounded to float8
    (e4m3). ``half_batch``: the float32 reference fed the first half of
    each batch twice, so that its mean is taken over half the rows."""
    import jax.numpy as jnp

    import checks
    import reference

    cfg, tr = cell.config, cell.traffic
    batches = [reference.synth_batch(tr["batch"], tr["seq_len"],
                                     cfg["vocab_size"], i, tr["data_seed"])
               for i in range(3)]
    ref = checks.reference_readings(
        reference.train_steps(cfg, tr["optimizer"], seed, batches))
    if variant == "float8":
        ctl = reference.train_steps(cfg, tr["optimizer"], seed, batches,
                                    low=jnp.dtype(LOW))
    elif variant == "half_batch":
        half = tr["batch"] // 2
        halved = [tuple(np.concatenate([x[:half], x[:half]]) for x in b)
                  for b in batches]
        ctl = reference.train_steps(cfg, tr["optimizer"], seed, halved)
    else:
        raise ValueError(f"unknown control {variant!r}")
    ctl = checks.reference_readings(ctl)
    gaps = checks.training_gaps(ctl["losses"], ctl, ref)
    passed = all(v <= tr["limits"][k] for k, v in gaps.items())
    return {"seed": seed, "variant": variant, "gaps": gaps,
            "limits": tr["limits"], "control_passes": passed}


def serving_control(cell, seed: int, seconds: float) -> dict:
    """A short run of a serving cell at its own load, reading on the same
    prompts and served tokens both the program's widest logit gap and the
    control's (the reference in float8 choosing each token)."""
    import time
    import harness
    out = harness.run(cell.name, seed, seconds, False, time.perf_counter(),
                      cell=cell, control=True)
    return {"seed": seed, "variant": "float8",
            "program_gap": out["checks"]["logit_gap"]["value"],
            "control_gap": out["control"],
            "limit": out["checks"]["logit_gap"]["limit"],
            "control_passes": out["control"] <= out["checks"]["logit_gap"]["limit"],
            "correct": out["correct"], "metrics": out["metrics"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variant", default="float8",
                    choices=("float8", "half_batch"))
    ap.add_argument("--seconds", type=float, default=10.0,
                    help="window of a serving cell's run")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH.parent / "src"))
    import harness
    cell = harness.load_cell(args.workload)
    harness.check_device(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    bad = False
    for seed in (int(s) for s in args.seeds.split(",")):
        if cell.traffic["kind"] == "train_loop":
            out = training_control(cell, seed, variant=args.variant)
        else:
            out = serving_control(cell, seed, args.seconds)
        bad |= out["control_passes"]
        print(json.dumps(out), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
