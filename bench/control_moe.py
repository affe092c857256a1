"""The control of ``correct`` for the MoE training cells: the reference in a
lower precision (or with a planted fault), put in the program's place, must
come out as not correct.

    python3 bench/control_moe.py --workload <name> --seeds 11,12,13 [--variant half_batch]

The configurations state bfloat16, so the control rounds every matrix
operand to float8 (e4m3) (``float8``); ``half_batch`` feeds the float32
reference the first half of each batch twice. Each is compared with the
float32 reference by the cell's own function and limits. One JSON line per
seed: the numbers compared, the share of top-k selections at step 1 that
differ from the reference's, and the ``bias_gap`` of a router bias that
never moves. Exits non-zero if any seed would pass.
The benchmark's own runs never run it.
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


def training_control(cell, seed: int, *, variant: str = "float8",
                     smoke: bool = False) -> dict:
    import jax.numpy as jnp

    import checks
    import harness
    import reference
    import reference_moe

    gen = harness.load_generator("train_loop_moe")
    _, _, cfg = gen.program_config(cell.config, smoke)
    tr = cell.traffic
    batches = [reference.synth_batch(tr["batch"], tr["seq_len"],
                                     cfg["vocab_size"], i, tr["data_seed"])
               for i in range(3)]
    ref = reference_moe.train_steps(cfg, tr["optimizer"], seed, batches)
    if variant == "float8":
        ctl = reference_moe.train_steps(cfg, tr["optimizer"], seed, batches,
                                        low=jnp.dtype(LOW))
    elif variant == "half_batch":
        half = tr["batch"] // 2
        halved = [tuple(np.concatenate([x[:half], x[:half]]) for x in b)
                  for b in batches]
        ctl = reference_moe.train_steps(cfg, tr["optimizer"], seed, halved)
    else:
        raise ValueError(f"unknown control {variant!r}")
    gaps = checks.training_gaps(ctl["losses"], ctl, ref)
    gamma = cfg["assumed"]["bias_update_speed"]
    gaps["bias_gap"] = gen.bias_gap(ctl["bias"], ref["bias"], gamma)
    passed = all(v <= tr["limits"][k] for k, v in gaps.items())
    return {"seed": seed, "variant": variant, "gaps": gaps,
            "flip_share": gen.flip_share(ctl["top_e"], ref["top_e"]),
            # a planted fault: a program whose bias never moves
            "frozen_bias_gap": gen.bias_gap(np.zeros_like(ref["bias"]),
                                            ref["bias"], gamma),
            "limits": tr["limits"], "control_passes": passed}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variant", default="float8",
                    choices=("float8", "half_batch"))
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH.parent / "src"))
    import harness
    cell = harness.load_cell(args.workload)
    harness.check_device(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    bad = False
    for seed in (int(s) for s in args.seeds.split(",")):
        out = training_control(cell, seed, variant=args.variant)
        bad |= out["control_passes"]
        print(json.dumps(out), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
