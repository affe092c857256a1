"""Generator for MoE training jobs (one chip's expert-parallel share):
drives the program's ``train_loop`` itself, like ``train_loop.py``, whose
helpers it imports.

The configuration file states the published model (``published``) and the
share this chip holds: its layers (``num_hidden_layers``), its routed
experts (``n_routed_experts``) and its vocabulary slice (``vocab_size``).
The program's configuration is ``configs.chip_share`` of the registry
entry, with those three numbers; a run fails if any field, the published
numbers or the optimizer differ from the files.

Window and end-to-end metrics are ``train_loop.py``'s, except that set-up
also waits for step 0's save to be durable: a first save of several GB
(first disk writes, codec threads starting) takes longer than a step, and
while none is durable the state age counts from the session's start, so
the window's state age would read set-up's compile time. The record adds
the program's routing and compress counters over the window (telemetry
counters at window close less those at window open: the steps in the
window and the saves whose device stage ran in it).

``correct`` compares what the timed call produced with
``reference_moe.py``, by ``train_loop.py``'s numbers and limits, without
holding whole states on the device (each is several GB here):

- the state after step 1 (parameters, first moment) and the parameters
  after step 3 are copied to the host, asynchronously, when the loop
  offers them (the copy of step 1's finishes during set-up);
- of every checkpoint saved in the window and still retained: the moments
  are copied to the host the same way, and each lossless leaf (parameters,
  step count) is reduced on the device to a bitwise digest; after the
  window each save is restored through the checkpoint manager and held
  against them (lossless leaves digest for digest, moments within the
  codec's stated bound);
- the router's correction bias after step 3 (``bias_gap``): the share of
  its (layer, expert) entries that moved otherwise than the reference's
  (the bias takes no gradient, so ``update_gap`` leaves it out);
- the share of top-k selections at step 1 that differ between the program
  (bfloat16, from the seeded weights) and the reference (float32) is
  reported on standard error and in the record; it decides nothing.

The reference runs after the window, after the program's state is freed
and ``memory_peak_bytes`` is read. Each phase's end is logged on standard
error with the seconds since the process started.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import sys
import time

import numpy as np

import acks
import checks as checks_lib
import harness
import reference
import reference_moe

train_loop = harness.load_generator("train_loop")

# configuration-file key -> attribute of the program's configuration
# (dotted: nested); every one is compared
FIELDS = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
    "vocab_size": "vocab_size",
    "attention_bias": "qkv_bias",
    "intermediate_size": "moe.dense_d_ff",
    "moe_intermediate_size": "moe.d_expert",
    "first_k_dense_replace": "moe.first_dense",
    "n_routed_experts": "moe.held",
    "n_shared_experts": "moe.n_shared_experts",
    "num_experts_per_tok": "moe.top_k",
    "routed_scaling_factor": "moe.routed_scale",
    "kv_lora_rank": "mla.kv_lora",
    "q_lora_rank": "mla.q_lora",
    "qk_nope_head_dim": "mla.qk_nope",
    "qk_rope_head_dim": "mla.qk_rope",
    "v_head_dim": "mla.v_head",
}
# what the configuration's flags mean in the program
FLAGS = {
    "hidden_act": ("silu", lambda c: True),
    "scoring_func": ("sigmoid", lambda c: c.moe.capacity_factor is None),
    "norm_topk_prob": (True, lambda c: c.moe.capacity_factor is None),
    "topk_method": ("noaux_tc", lambda c: c.moe.bias_update_rate > 0),
    "seq_aux": (True, lambda c: c.moe.seq_aux_weight > 0),
    "n_group": (1, lambda c: True),
    "topk_group": (1, lambda c: True),
    "num_nextn_predict_layers": (0, lambda c: c.mtp_weight == 0),
    "moe_layer_freq": (1, lambda c: True),
}


def _attr(cfg, dotted: str):
    for part in dotted.split("."):
        cfg = getattr(cfg, part)
    return cfg


def program_config(cfg_file: dict, smoke: bool = False):
    """The program's configuration of the file's share: ``chip_share`` of
    the registry entry (for tests on the CPU: of its reduced entry, two
    experts of every four held, with the file's keys set to its sizes)."""
    from repro.configs import base as configs
    reg = cfg_file["program"]["registry"]
    if reg not in getattr(configs, "ARCH_IDS", ()):
        raise harness.BenchError(f"the program has no registry entry {reg!r}")
    whole = configs.get(reg, smoke=smoke)
    if not smoke:
        return whole, configs.chip_share(
            whole, n_layers=cfg_file["num_hidden_layers"],
            n_held=cfg_file["n_routed_experts"],
            vocab_size=cfg_file["vocab_size"]), cfg_file
    share = configs.chip_share(whole, n_layers=whole.n_layers,
                               n_held=whole.moe.n_experts // 2,
                               vocab_size=whole.vocab_size)
    out = dict(cfg_file, published=dict(cfg_file["published"],
                                        n_routed_experts=whole.moe.n_experts,
                                        num_hidden_layers=whole.n_layers,
                                        vocab_size=whole.vocab_size))
    for key, attr in FIELDS.items():
        out[key] = _attr(share, attr)
    out["assumed"] = dict(cfg_file["assumed"],
                          bias_update_speed=whole.moe.bias_update_rate,
                          seq_aux_alpha=whole.moe.seq_aux_weight)
    return whole, share, out


def check_program(cfg_file: dict, whole, share, step_cfg, traffic) -> None:
    """The program runs the configuration and optimizer the files state:
    the share's every field, the whole model's published numbers, the
    flags' meanings and the assumed routing constants."""
    bad = []
    for key, attr in FIELDS.items():
        if _attr(share, attr) != cfg_file[key]:
            bad.append(f"{key}={cfg_file[key]!r} vs {attr}="
                       f"{_attr(share, attr)!r}")
    pub = cfg_file["published"]
    for key, attr in (("num_hidden_layers", "n_layers"),
                      ("n_routed_experts", "moe.n_experts"),
                      ("vocab_size", "vocab_size")):
        if _attr(whole, attr) != pub[key]:
            bad.append(f"published {key}={pub[key]!r} vs {attr}="
                       f"{_attr(whole, attr)!r}")
    if share.moe.n_experts != pub["n_routed_experts"]:
        bad.append("the share's router does not keep every expert")
    for key, (want, holds) in FLAGS.items():
        if cfg_file[key] != want or not holds(share):
            bad.append(f"{key}={cfg_file[key]!r}")
    assumed = cfg_file["assumed"]
    if (share.moe.bias_update_rate, share.moe.seq_aux_weight) != (
            assumed["bias_update_speed"], assumed["seq_aux_alpha"]):
        bad.append("bias update speed / balance alpha differ from assumed")
    try:    # the dtype and the optimizer, as for the dense cells
        train_loop.check_program({"program": {"fields": {}}, "torch_dtype":
                                  cfg_file["torch_dtype"]}, share, step_cfg,
                                 traffic)
    except harness.BenchError as e:
        bad.append(str(e))
    if bad:
        raise harness.BenchError("the program does not run the stated "
                                 "configuration: " + "; ".join(bad))


# ---------------------------------------------------------------------------
# capturing what the checks read, without holding states on the device
# ---------------------------------------------------------------------------

def _digest_fn():
    import jax
    import jax.numpy as jnp

    def one(x):
        width = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
        u = jax.lax.bitcast_convert_type(x, width).astype(jnp.uint32)
        u = u.reshape(-1)
        i = jnp.arange(u.size, dtype=jnp.uint32)
        return jnp.stack([jnp.sum(u), jnp.sum(u * (i * jnp.uint32(2654435761)
                                                   + jnp.uint32(1)))])
    return jax.jit(lambda tree: {k: one(v) for k, v in tree.items()})


def _lossless(path: tuple) -> bool:
    return not checks_lib._lossy(path)


class HostCopies:
    """Leaves copied to the host without blocking the loop: ``start``
    issues each leaf's device-to-host copy; ``finish`` (at the next offer,
    a step later, or after the window) takes the host arrays and lets go
    of the device ones."""

    def __init__(self):
        self.pending: list = []
        self.host: dict = {}

    def start(self, step: int, leaves: dict) -> None:
        for x in leaves.values():
            x.copy_to_host_async()
        self.pending.append((step, leaves))

    def finish(self) -> None:
        for step, leaves in self.pending:
            self.host.setdefault(step, {}).update(
                {k: np.asarray(v) for k, v in leaves.items()})
        self.pending.clear()


def program_top_e(share, seed: int, tokens) -> np.ndarray:
    """The program's top-k experts per MoE layer (n_moe, batch, S, k) for
    ``tokens`` from the seeded weights, by its own forward in bfloat16."""
    import jax
    import jax.numpy as jnp
    from repro.models import moe as moe_lib, params as P, transformer
    cfg = dataclasses.replace(share, scan_layers=False, remat=False)
    picked: list = []
    select = moe_lib.select

    def spy(*args):
        out = select(*args)
        picked.append(out[1])
        return out

    @jax.jit
    def run(key, toks):
        picked.clear()
        params = P.materialize(key, transformer.param_spec(cfg))
        transformer.forward(params, cfg, toks)
        return jnp.stack(picked)

    moe_lib.select = spy
    try:
        return np.asarray(run(jax.random.PRNGKey(seed), jnp.asarray(tokens)))
    finally:
        moe_lib.select = select


def flip_share(prog: np.ndarray, ref: np.ndarray) -> float:
    """Share of (token, slot) selections of ``prog`` not among ``ref``'s
    top-k for the same token and layer."""
    hit = (prog[..., :, None] == ref[..., None, :]).any(-1)
    return float(1.0 - hit.sum() / hit.size)


BIAS = ("params", "moe_blocks", "moe", "e_bias")


def bias_gap(prog: np.ndarray, ref: np.ndarray, gamma: float) -> float:
    """Share of the router's correction-bias entries (layer, expert) that
    differ from the reference's by more than ``gamma`` / 2. Each update
    moves an entry by 0 or +-gamma, so two runs that moved it the same way
    at every step agree to rounding, and one disagreeing step puts them
    at least gamma apart."""
    diff = np.abs(np.asarray(prog, np.float64) - np.asarray(ref, np.float64))
    return float(np.mean(diff > gamma / 2))


def checkpoint_checks(task: dict, template, digests: dict, moments: dict,
                      steps) -> dict:
    """Restore each retained save through the program's checkpoint manager
    and hold it against what was captured when it was offered."""
    import jax
    from repro.core import Session

    eps = task["options"]["lossy_eps"]
    plan = {"streams": [task["stream"]], "tasks": {"checkpoint": {
        "stream": task["stream"], "preset": "checkpoint",
        "options": dict(task["options"])}}}
    digest = _digest_fn()
    changed, worst, missing = 0, 0.0, 0
    with Session(plan) as session:
        on_disk = set(session.checkpoint.list_steps())
        for step in steps:
            if step not in on_disk or step not in digests:
                missing += 1
                continue
            _, restored = session.restore(template, step)
            got = reference.flatten(restored)
            del restored
            want = {k: v for k, v in got.items() if _lossless(k)}
            same = jax.device_get(digest(want))
            changed += sum(not np.array_equal(same[k], digests[step][k])
                           for k in want)
            for k, a in moments[step].items():
                a32 = np.asarray(a, np.float32)
                b32 = np.asarray(got[k], np.float32)
                den = max(float(np.linalg.norm(a32)), 1e-30)
                worst = max(worst, float(np.linalg.norm(a32 - b32)) / den)
            del got, want
            gc.collect()
    return {"ckpt_missing": {"value": missing, "limit": 0},
            "ckpt_lossless_changed": {"value": changed, "limit": 0},
            "ckpt_lossy_rel_l2": {"value": worst,
                                  "limit": checks_lib.codec_bound(eps)}}


def _readings(host: dict, opt: dict) -> dict:
    """Per-leaf norms of the first clipped gradient (from the first moment
    after one update) and of the parameters' change over steps 2-3."""
    b1 = opt["b1"]
    grad = {k[1:]: float(np.linalg.norm(np.asarray(v, np.float32).reshape(-1)
                                        / (1.0 - b1)))
            for k, v in host[0].items() if k[0] == "mu"}
    upd = {k[1:]: float(np.linalg.norm(
        (np.asarray(host[2][k], np.float32)
         - np.asarray(v, np.float32)).reshape(-1)))
        for k, v in host[0].items() if k[0] == "params"}
    return {"grad": grad, "update": upd}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def save_breakdown(spans, step_end: dict, seen: dict, steps) -> list:
    """Per save: the seconds of each phase of its checkpoint firing, by the
    program's spans (loop: device stage, hand-off dispatch; worker: the
    transfer's drain, each host stage, the sink), and from the step's end
    to the harness seeing it committed."""
    out = []
    for step in steps:
        row = {"step": step}
        for sp in spans:
            if sp.step != step or sp.meta.get("task", "checkpoint") != \
                    "checkpoint":
                continue
            name = {"insitu-device/checkpoint": "device",
                    "handoff/dispatch": "dispatch",
                    "handoff/materialize": "d2h",
                    "sink/checkpoint": "sink"}.get(sp.name)
            if name is None and sp.name.startswith("stage/checkpoint/") \
                    and not sp.name.endswith("/item"):
                name = sp.name.rsplit("/", 1)[1]
            if name is not None:
                row[name] = row.get(name, 0.0) + sp.t1 - sp.t0
        if step in seen and step in step_end:
            row["durable"] = seen[step] - step_end[step]
        out.append(row)
    return out


def _wait_durable(watcher, step: int, timeout_s: float = 600.0) -> None:
    t0 = time.perf_counter()
    while step not in watcher.seen:
        if time.perf_counter() - t0 > timeout_s:
            raise harness.BenchError(f"the save of step {step} was not "
                                     f"durable within {timeout_s} s")
        time.sleep(0.01)


def _say(ctx, msg: str) -> None:
    print(f"bench: {time.perf_counter() - ctx.t_start:8.1f} s  {msg}",
          file=sys.stderr, flush=True)


def run(ctx) -> dict:
    import jax
    from repro.configs import base as configs
    from repro.launch import train as train_lib

    import devtrace

    cell, tr = ctx.cell, ctx.cell.traffic
    whole, share, cfg = program_config(cell.config, ctx.smoke)
    step_cfg = train_lib.StepConfig()
    check_program(cfg, whole, share, step_cfg, tr)
    _say(ctx, "configuration checked")
    shape = configs.ShapeConfig("bench", "train", tr["seq_len"], tr["batch"])
    steps = train_loop.steps_for(tr, ctx.seconds)
    ckpt_dir = ctx.workdir / "ckpt"
    plan = train_loop._fill(tr["plan"], {"{checkpoint_dir}": str(ckpt_dir)})
    ckpt = train_loop.checkpoint_task(plan)
    every, keep = ckpt["every"], ckpt["options"]["keep"]
    save_steps = list(range(0, steps, every))
    retained = sorted(set(save_steps[-keep:]) - {0})
    copies = HostCopies()
    digest = _digest_fn()
    digests: dict = {}
    held: dict = {}
    step_end: dict = {}
    emits: list = []

    def capture(step: int, state) -> None:
        flat = reference.flatten(state)
        if step == 0:
            copies.start(0, {k: v for k, v in flat.items()
                             if k[0] in ("params", "mu")})
        if step == 2:
            copies.start(2, {k: v for k, v in flat.items()
                             if k[0] == "params"})
        if step in retained or step == 0:    # step 0: compile in set-up
            digests[step] = digest({k: v for k, v in flat.items()
                                    if _lossless(k)})
        if step in retained:
            copies.start(-step, {k: v for k, v in flat.items()
                                 if not _lossless(k)})

    def on_session(session):
        orig = session.emit
        held["t_start"] = time.perf_counter()
        held["telemetry"] = session.runtime.telemetry

        def emit(stream, step, payload):
            t0 = time.perf_counter()
            if stream == tr["state_stream"]:
                copies.finish()
                t1 = time.perf_counter()
                payload = payload() if callable(payload) else payload
                capture(step, payload)
                emits.append(("bench/host_copies", t0, t1))
                emits.append(("bench/capture", t1, time.perf_counter()))
            orig(stream, step, payload)
            emits.append((f"emit/{stream}", t0, time.perf_counter()))
            if stream != tr["state_stream"]:
                return
            _say(ctx, f"step {step} offered")
            if step == 0:
                copies.finish()
                _wait_durable(watcher, 0)
                held["counters0"] = held["telemetry"].counters()
                if ctx.trace:
                    jax.profiler.start_trace(str(ctx.workdir / "trace"))
                    with jax.profiler.TraceAnnotation(devtrace.CLOCK_MARK):
                        held["t_sync"] = time.perf_counter()
            step_end[step] = time.perf_counter()

        session.emit = emit

    watcher = acks.AckWatcher(acks.committed_checkpoints(ckpt_dir))
    try:
        with jax.profiler.TraceAnnotation("bench/train_loop"):
            out = train_lib.train_loop(
                share, shape=shape, steps=steps, seed=ctx.seed, plan=plan,
                on_session=on_session, log=lambda _msg: None)
        t_close = time.perf_counter()
    finally:
        watcher.close()
        if ctx.trace and "t_sync" in held:
            jax.profiler.stop_trace()
    copies.finish()
    _say(ctx, f"window closed: {steps - 1} steps")
    tm = out["telemetry"]
    spans = tm.spans()
    saves = save_breakdown(spans, step_end, watcher.seen,
                           [s for s in save_steps if s > 0])
    harness_s = sum(b - a for name, a, b in emits
                    if name.startswith("bench/") and a >= step_end[0])
    _say(ctx, f"capture for correct: {harness_s:.3f} s on the loop thread "
         f"in the window")
    for row in saves:
        _say(ctx, "save " + ", ".join(
            f"{k} {v:.3f}" if isinstance(v, float) else f"{k} {v}"
            for k, v in row.items()))
    t_open = step_end[0]
    window = t_close - t_open
    losses = out["losses"]
    c0, c1 = held["counters0"], tm.counters()
    counters = {k: v - c0.get(k, 0.0) for k, v in c1.items()}
    rec: dict = {
        "window": (t_open, t_close), "window_s": window,
        "steps_in_window": steps - 1,
        "saves_in_window": sum(1 for s in save_steps if s > 0),
        "spans": [(s.name, s.t0, s.t1, s.thread, s.step) for s in spans],
        "saves": saves,
        "config": cfg, "traffic": tr, "counters": counters,
        "peaks": ctx.peaks, "main_thread": "MainThread",
        "compress_leaf_sizes": [int(np.prod(v.shape))
                                for k, v in copies.host[0].items()
                                if k[0] == "mu"] * 2,
    }
    end_to_end = {
        "train_tokens_per_s": (steps - 1) * tr["batch"] * tr["seq_len"] / window,
        "setup_s": t_open - ctx.t_start,
        "state_age_p95_s": acks.state_age_p95(
            step_end, watcher.seen, held["t_start"], t_open, t_close)}
    if ctx.trace:
        rec["trace"] = train_loop._reduce_trace(ctx, held["t_sync"], rec,
                                                spans, emits)
    mem = harness.memory_peak_bytes(ctx.cell.chips)

    # --- what the window produced, against the reference ------------------
    checks: dict = {}
    nonfinite = sum(not math.isfinite(x) for x in losses)
    checks["nonfinite_losses"] = {"value": nonfinite, "limit": 0}
    template = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            out.pop("state"))
    del out
    gc.collect()
    digests = {s: jax.device_get(d) for s, d in digests.items()}
    moments = {s: copies.host.pop(-s) for s in retained if -s in copies.host}
    checks.update(checkpoint_checks(ckpt, template, digests, moments,
                                    retained))
    _say(ctx, f"{len(retained)} checkpoints restored and compared")
    del moments
    prog = _readings(copies.host, tr["optimizer"])
    prog_bias = copies.host[2][BIAS]
    copies.host.clear()
    gc.collect()
    batches = [reference.synth_batch(tr["batch"], tr["seq_len"],
                                     cfg["vocab_size"], i, tr["data_seed"])
               for i in range(3)]
    prog_top = program_top_e(share, ctx.seed, batches[0][0])
    _say(ctx, "program's routing read")
    ref = reference_moe.train_steps(cfg, tr["optimizer"], ctx.seed, batches)
    _say(ctx, "reference done")
    rec["flip_share"] = flip_share(prog_top, ref["top_e"])
    print(f"moe top-{cfg['num_experts_per_tok']} flip share at step 1: "
          f"{rec['flip_share']!r}", file=sys.stderr)
    checks.update(checks_lib.compare_training(losses[:3], prog, ref,
                                              tr["limits"]))
    checks["bias_gap"] = {
        "value": bias_gap(prog_bias, ref["bias"],
                          cfg["assumed"]["bias_update_speed"]),
        "limit": tr["limits"]["bias_gap"]}
    return {"window": (t_open, t_close), "end_to_end": end_to_end,
            "record": rec, "checks": checks, "memory_peak_bytes": mem,
            "attempted": steps - 1, "failed": nonfinite}
