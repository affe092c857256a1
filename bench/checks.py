"""The comparisons that decide ``correct``, and the readings they compare.

Every number goes into the result line as ``{"value": v, "limit": l}`` and
passes when ``v <= l``. Limits live in the traffic files, with the
readings they were set from in PERF.md.
"""
from __future__ import annotations

import math
from typing import Any, Mapping

import numpy as np

import reference

# leaves whose reference gradient norm is under this share of the median
# leaf's move by round-off alone under Adam: their change is not compared
TINY_GRAD = 1e-3
CODEC_BLOCK = 256


def codec_bound(eps: float) -> float:
    """The spectral codec's stated relative-L2 guarantee: the threshold's
    ``eps`` plus int8 quantization, sqrt(BLOCK) / 254 per block."""
    return eps + math.sqrt(CODEC_BLOCK) / 254.0


def hf_config_of(prog_cfg, file_cfg: Mapping) -> dict:
    """The configuration file's keys with the sizes of ``prog_cfg`` (the
    registry's reduced configuration, for tests on the CPU)."""
    out = dict(file_cfg)
    for hf_key, attr in file_cfg["program"]["fields"].items():
        out[hf_key] = getattr(prog_cfg, attr)
    return out


def _norms(tree: Mapping, fn=lambda x: x) -> dict:
    import jax.numpy as jnp
    return {k: float(jnp.linalg.norm(fn(v).astype(jnp.float32).reshape(-1)))
            for k, v in reference.flatten(tree).items()}


def program_readings(state1: Mapping, state3: Mapping, opt: Mapping) -> dict:
    """Per-leaf norms of the first clipped gradient, read from the first
    moment after one update (mu = (1 - b1) g), and of the parameters'
    change from the state after one update to the state after three."""
    import jax.numpy as jnp
    b1 = opt["b1"]
    grad = _norms(state1["mu"], lambda m: m.astype(jnp.float32) / (1.0 - b1))
    p1 = reference.flatten(state1["params"])
    p3 = reference.flatten(state3["params"])
    upd = {k: float(jnp.linalg.norm((p3[k].astype(jnp.float32)
                                     - p1[k].astype(jnp.float32)).reshape(-1)))
           for k in p1}
    return {"grad": grad, "update": upd}


def reference_readings(ref: Mapping) -> dict:
    import jax.numpy as jnp
    p1 = reference.flatten(ref["params1"])
    p3 = reference.flatten(ref["params_last"])
    upd = {k: float(jnp.linalg.norm((p3[k].astype(jnp.float32)
                                     - p1[k].astype(jnp.float32)).reshape(-1)))
           for k in p1}
    return {"losses": list(ref["losses"]), "grad": _norms(ref["grad1"]),
            "update": upd}


def _worst_gap(prog: Mapping, ref: Mapping, keys) -> float:
    keys = list(keys)
    med = float(np.median([ref[k] for k in keys]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keys)


def training_gaps(losses: list, prog: Mapping, ref: Mapping) -> dict:
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses, ref["losses"]))
    if any(not math.isfinite(x) for x in losses):
        loss_gap = math.inf
    med = float(np.median(list(ref["grad"].values())))
    moved = [k for k, g in ref["grad"].items() if g >= TINY_GRAD * med]
    return {"loss_gap": loss_gap,
            "grad_gap": _worst_gap(prog["grad"], ref["grad"], ref["grad"]),
            "update_gap": _worst_gap(prog["update"], ref["update"], moved)}


def compare_training(losses: list, prog: Mapping, ref: Mapping,
                     limits: Mapping) -> dict:
    gaps = training_gaps(losses, prog, ref)
    return {k: {"value": v, "limit": limits[k]} for k, v in gaps.items()}


def _lossy(path: tuple) -> bool:
    """The configuration's lossy leaves: the optimizer moments."""
    return path[0] in ("mu", "nu")


def checkpoint_checks(task: Mapping, states: Mapping[int, Any], steps) -> dict:
    """Restore each retained checkpoint of the window through the program's
    checkpoint manager (the plan's checkpoint ``task``) and hold it against
    the state it was taken from."""
    from repro.core import Session

    eps = task["options"]["lossy_eps"]
    restore_plan = {"streams": [task["stream"]], "tasks": {"checkpoint": {
        "stream": task["stream"], "preset": "checkpoint",
        "options": dict(task["options"])}}}
    changed, worst, missing = 0, 0.0, 0
    with Session(restore_plan) as session:
        on_disk = set(session.checkpoint.list_steps())
        for step in steps:
            if step not in on_disk or step not in states:
                missing += 1
                continue
            _, restored = session.restore(states[step], step)
            want = reference.flatten(states[step])
            got = reference.flatten(restored)
            for path, a in want.items():
                a32 = np.asarray(a, np.float32)
                b32 = np.asarray(got[path], np.float32)
                if _lossy(path) and a32.size > 1:
                    den = max(float(np.linalg.norm(a32)), 1e-30)
                    worst = max(worst, float(np.linalg.norm(a32 - b32)) / den)
                elif not np.array_equal(a32, b32):
                    changed += 1
            del restored, want, got
    return {"ckpt_missing": {"value": missing, "limit": 0},
            "ckpt_lossless_changed": {"value": changed, "limit": 0},
            "ckpt_lossy_rel_l2": {"value": worst, "limit": codec_bound(eps)}}
