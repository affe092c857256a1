"""Generator for training jobs: drives the program's ``train_loop`` itself.

The traffic file gives the shape (``seq_len`` x ``batch``), the in-situ
plan in its plain-dict form (``{checkpoint_dir}`` is filled with a
run-scoped directory), the optimizer as the configuration states it, and
``steps_per_second``: the step count of the timed call is
``1 + ceil(seconds x steps_per_second)``, rounded up so that the last step
saves when the plan checkpoints. The work is then fixed by the traffic
file and ``--seconds``, not by how fast the program ran.

Set-up is everything before step 1 of the timed call: imports, the seeded
state, tracing and compiling (or loading from the persistent cache) the
step, step 0 and its in-situ firings. The window opens when step 1 starts
and closes when ``train_loop`` returns, after its session has drained, so a
save pushed past the last step still counts. One call, one object: the
state and compiled step that set-up built are those the window drives.

``correct`` compares what that call produced with ``reference.py``:

- ``loss_gap``: the largest relative gap of the first three losses;
- ``grad_gap``: the first clipped gradient, read from the first moment
  after step 0, by the worst leaf's gap of norms;
- ``update_gap``: the parameters' change over steps 1 and 2 (the warm-up
  learning rate is 0 at step 0), by the worst leaf's gap of norms;
- ``ckpt_*``: every checkpoint saved in the window and still retained,
  restored through the checkpoint manager, against the state it was taken
  from: lossless leaves bit for bit, lossy leaves within the codec's stated
  relative-L2 bound;
- ``nonfinite_losses``: every loss of the call is finite.
"""
from __future__ import annotations

import gc
import json
import math
import time
from typing import Any

import numpy as np

import acks
import checks as checks_lib
import reference


def _fill(obj: Any, subs: dict) -> Any:
    text = json.dumps(obj)
    for k, v in subs.items():
        text = text.replace(k, v)
    return json.loads(text)


def checkpoint_task(plan: dict) -> dict:
    """The plan's checkpoint task, or an empty dict."""
    return next((t for t in plan["tasks"].values()
                 if t.get("preset") == "checkpoint"), {})


def steps_for(traffic: dict, seconds: float) -> int:
    steps = 1 + math.ceil(seconds * traffic["steps_per_second"])
    every = checkpoint_task(traffic["plan"]).get("every")
    if every:
        steps = 1 + every * math.ceil((steps - 1) / every)
    return steps


def check_program(cfg_file: dict, prog_cfg, step_cfg, traffic: dict) -> None:
    """The program runs the configuration and optimizer the files state."""
    mismatches = []
    for hf_key, attr in cfg_file["program"]["fields"].items():
        if getattr(prog_cfg, attr) != cfg_file[hf_key]:
            mismatches.append(f"{hf_key}={cfg_file[hf_key]!r} vs "
                              f"{attr}={getattr(prog_cfg, attr)!r}")
    if str(prog_cfg.dtype) != cfg_file["torch_dtype"]:
        mismatches.append(f"dtype {prog_cfg.dtype} vs {cfg_file['torch_dtype']}")
    opt = traffic["optimizer"]
    prog_opt = {"lr_peak": step_cfg.lr_peak, "lr_warmup": step_cfg.lr_warmup,
                "lr_total": step_cfg.lr_total, "b1": step_cfg.opt.b1,
                "b2": step_cfg.opt.b2, "eps": step_cfg.opt.eps,
                "weight_decay": step_cfg.opt.weight_decay,
                "grad_clip": step_cfg.opt.grad_clip,
                "moment_dtype": "bfloat16" if step_cfg.opt.bf16_moments
                else "float32"}
    for k, v in prog_opt.items():
        if opt[k] != v:
            mismatches.append(f"optimizer {k}={opt[k]!r} vs program {v!r}")
    if step_cfg.opt.master_weights:
        mismatches.append("the program keeps f32 master weights")
    if mismatches:
        import harness
        raise harness.BenchError("the program does not run the stated "
                                 "configuration: " + "; ".join(mismatches))


def run(ctx) -> dict:
    import jax
    from repro.configs import base as configs
    from repro.launch import train as train_lib

    import devtrace
    import harness

    cell, tr = ctx.cell, ctx.cell.traffic
    cfg = cell.config
    prog_cfg = configs.get(cfg["program"]["registry"], smoke=ctx.smoke)
    if ctx.smoke:
        cfg = checks_lib.hf_config_of(prog_cfg, cfg)
    step_cfg = train_lib.StepConfig()
    check_program(cfg, prog_cfg, step_cfg, tr)
    shape = configs.ShapeConfig("bench", "train", tr["seq_len"], tr["batch"])
    steps = steps_for(tr, ctx.seconds)
    ckpt_dir = ctx.workdir / "ckpt"
    plan = _fill(tr["plan"], {"{checkpoint_dir}": str(ckpt_dir)})
    ckpt = checkpoint_task(plan)
    every, keep = ckpt.get("every"), ckpt.get("options", {}).get("keep", 0)
    save_steps = list(range(0, steps, every)) if every else []
    retained = set(save_steps[-keep:]) if keep else set()
    want = {0, 2} | {s for s in retained if s > 0}
    captured: dict[int, Any] = {}
    held: dict[str, Any] = {}
    step_end: dict[int, float] = {}     # host clock after each step's emits
    emits: list = []                    # (name, t0, t1) of every emit call

    def on_session(session):
        orig = session.emit
        held["t_start"] = time.perf_counter()

        def emit(stream, step, payload):
            t0 = time.perf_counter()
            if stream == tr["state_stream"] and step in want:
                payload = payload() if callable(payload) else payload
                captured[step] = payload
            orig(stream, step, payload)
            emits.append((f"emit/{stream}", t0, time.perf_counter()))
            if stream != tr["state_stream"]:
                return
            if ctx.trace and step == 0:
                jax.profiler.start_trace(str(ctx.workdir / "trace"))
                with jax.profiler.TraceAnnotation(devtrace.CLOCK_MARK):
                    held["t_sync"] = time.perf_counter()
            step_end[step] = time.perf_counter()

        session.emit = emit

    watcher = acks.AckWatcher(acks.committed_checkpoints(ckpt_dir)) if every else None
    try:
        with jax.profiler.TraceAnnotation("bench/train_loop"):
            out = train_lib.train_loop(
                cfg["program"]["registry"], smoke=ctx.smoke, shape=shape,
                steps=steps, seed=ctx.seed, plan=plan, on_session=on_session,
                log=lambda _msg: None)
        t_close = time.perf_counter()
    finally:
        if watcher is not None:
            watcher.close()
        if ctx.trace and "t_sync" in held:
            jax.profiler.stop_trace()
    tm = out["telemetry"]
    spans = tm.spans()
    t_open = step_end[0]
    window = t_close - t_open
    losses = out["losses"]
    tokens = (steps - 1) * tr["batch"] * tr["seq_len"]
    rec: dict = {
        "window": (t_open, t_close), "window_s": window,
        "steps_in_window": steps - 1,
        "spans": [(s.name, s.t0, s.t1, s.thread, s.step) for s in spans],
        "config": cfg, "traffic": tr,
        "peaks": ctx.peaks, "main_thread": "MainThread",
        "compress_leaf_sizes": [int(np.prod(x.shape)) for k in ("mu", "nu")
                                for x in jax.tree.leaves(captured[0][k])],
    }
    end_to_end = {"train_tokens_per_s": tokens / window,
                  "setup_s": t_open - ctx.t_start}
    if every:
        end_to_end["state_age_p95_s"] = acks.state_age_p95(
            step_end, watcher.seen, held["t_start"], t_open, t_close)
    if ctx.trace:
        rec["trace"] = _reduce_trace(ctx, held["t_sync"], rec, spans, emits)
    mem = harness.memory_peak_bytes(ctx.cell.chips)

    # --- what the window produced, against the reference ------------------
    checks: dict = {}
    nonfinite = sum(not math.isfinite(x) for x in losses)
    checks["nonfinite_losses"] = {"value": nonfinite, "limit": 0}
    final = out.pop("state")
    del out
    if every:
        captured[steps - 1] = final
        checks.update(checks_lib.checkpoint_checks(
            ckpt, captured, sorted(retained - {0})))
    del final
    prog = checks_lib.program_readings(captured[0], captured[2], tr["optimizer"])
    captured.clear()
    gc.collect()
    batches = [reference.synth_batch(tr["batch"], tr["seq_len"],
                                     cfg["vocab_size"], i, tr["data_seed"])
               for i in range(3)]
    ref = reference.train_steps(cfg, tr["optimizer"], ctx.seed, batches)
    ref_read = checks_lib.reference_readings(ref)
    del ref
    checks.update(checks_lib.compare_training(
        losses[:3], prog, ref_read, tr["limits"]))
    return {"window": (t_open, t_close), "end_to_end": end_to_end,
            "record": rec, "checks": checks, "memory_peak_bytes": mem,
            "attempted": steps - 1, "failed": nonfinite}


def _reduce_trace(ctx, t_sync: float, rec: dict, spans, emits) -> Any:
    import devtrace
    path = devtrace.find_xplane(ctx.workdir / "trace")
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    off = devtrace.clock_offset_ns(pd) - t_sync * 1e9
    to_ns = lambda t: t * 1e9 + off
    host = [(s.name, to_ns(s.t0), to_ns(s.t1)) for s in spans
            if s.thread == rec["main_thread"]]
    host += [(name, to_ns(a), to_ns(b)) for name, a, b in emits]
    w0, w1 = rec["window"]
    return devtrace.reduce_profile(
        pd, (to_ns(w0), to_ns(w1)), host,
        device_plane=lambda n: n.startswith("/device:TPU"),
        ops_line=lambda n: n == "XLA Ops",
        modules_line=lambda n: n == "XLA Modules")
