"""Generator for served traffic: open-loop arrivals on the wall clock.

Requests arrive as a Poisson process at the traffic file's fixed
``rate_per_s``, whatever the server does; each is timed from the moment it
was due. Prompt and output lengths are lognormal, clipped. The multiset of
lengths and of inter-arrival gaps is drawn once from the file's
``shape_seed``, and ``--seed`` only orders them and draws the token ids, so
every seed offers the same work. A ``lead_in_s`` of arrivals before the
window fills the engine to its steady in-flight count.

The window drives the program itself, in ``serve_loop``'s order: admit
every due request that fits (``PagedServingEngine.admit``), one
``PagedServingEngine.step``, then ``Session.emit`` of the engine's
snapshot payload on the plan's stream. ``serve_loop`` serves a closed
batch, so the loop is written here.

Set-up: the weights, made on the device from the seed in one jitted call
in the type they are served in; the engine; one warm-up request through
admit, step and emit, which compiles every program the window uses.

``correct``:

- ``logit_gap``: a sample of finished requests drawn from the seed, with
  the longest among them. The float32 reference runs once over each prompt
  and its served tokens; the number is the widest gap by which a served
  (greedy) token's logit lies below the reference's best at its position.
- ``snapshot_mismatch``: after the window, one more snapshot is emitted and
  the store drained; the newest published snapshot, restored through the
  ``SnapshotStore``, must equal the engine state it captured, leaf for leaf.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time
from collections import deque
from typing import Any, Optional

import numpy as np

import acks
import checks as checks_lib
import reference


@dataclasses.dataclass
class Arrival:
    due: float            # seconds after the loop's start
    prompt: np.ndarray
    max_new: int


def _lognormal_lengths(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(int)


def draw_arrivals(tr: dict, seed: int, seconds: float, vocab: int) -> list[Arrival]:
    n = math.ceil(tr["rate_per_s"] * (tr["lead_in_s"] + seconds) * 1.25) + 16
    shape = np.random.default_rng(tr["shape_seed"])
    gaps = shape.exponential(1.0 / tr["rate_per_s"], n)
    plen = _lognormal_lengths(shape, tr["prompt_len"], n)
    olen = _lognormal_lengths(shape, tr["output_len"], n)
    rng = np.random.default_rng(seed & (2**64 - 1))
    gaps = gaps[rng.permutation(n)]
    order = rng.permutation(n)
    due = np.cumsum(gaps)
    return [Arrival(float(due[i]), rng.integers(0, vocab, plen[j]).astype(np.int32),
                    int(olen[j]))
            for i, j in enumerate(order)]


def make_engine_config(cfg_file: dict, smoke: bool):
    """The program's configuration, with the file's stated overrides."""
    from repro.configs import base as configs
    prog = configs.get(cfg_file["program"]["registry"], smoke=smoke)
    return dataclasses.replace(prog, **cfg_file["program"].get("overrides", {}))


@dataclasses.dataclass
class Served:
    arrival: Arrival
    req: Any
    times: list = dataclasses.field(default_factory=list)  # token host times


def serve(engine, session, stream: Optional[str],
          arrivals: list[Arrival], t0: float, t_close: float,
          log: dict) -> list[Served]:
    """Offer ``arrivals`` (due at ``t0 + due``) until ``t_close``."""
    import jax
    from repro.serving.engine import Request

    pending = deque(arrivals)
    queue: deque = deque()
    served: list[Served] = []
    by_rid: dict[int, Served] = {}
    step = log.setdefault("next_step", 1)
    while True:
        now = time.perf_counter()
        if now >= t_close:
            break
        while pending and t0 + pending[0].due <= now:
            a = pending.popleft()
            queue.append(Served(a, Request(len(served) + len(queue), a.prompt,
                                           max_new=a.max_new)))
            by_rid[queue[-1].req.rid] = queue[-1]
        while queue:
            s = queue[0]
            a0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench/admit"):
                ok = engine.admit(s.req)
            a1 = time.perf_counter()
            if not ok:
                break
            log["admit"].append((a0, a1, len(s.arrival.prompt)))
            s.times.append(a1)
            served.append(queue.popleft())
        before = [r for r in engine.active if r is not None]
        if before:
            ctx = [len(r.prompt) + len(r.out) for r in before]
            s0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench/step"), \
                    session.step_span(step):
                engine.step()
            s1 = time.perf_counter()
            log["step"].append((step, s0, s1, ctx))
            for r in before:
                by_rid[r.rid].times.append(s1)
            if stream is not None:
                e0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench/emit"):
                    session.emit(stream, step, engine.snapshot_payload)
                log["emit"].append((e0, time.perf_counter()))
            step += 1
        else:
            nxt = t0 + pending[0].due if pending else t_close
            time.sleep(max(0.0, min(nxt, t_close) - time.perf_counter()))
    log["next_step"] = step
    log["unadmitted"] = list(queue)
    return served


def _percentile(xs, q) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


def latency(served: list[Served], unadmitted: list[Served], t0: float,
            t_open: float, t_close: float) -> dict:
    """TTFT over every request due in the window (one with no first token
    by the window's end counts at its wait so far) and every gap between
    consecutive tokens that ends in the window."""
    ttft, itl = [], []
    for s in list(served) + list(unadmitted):
        due = t0 + s.arrival.due
        if t_open <= due < t_close:
            first = s.times[0] if s.times and s.times[0] <= t_close else t_close
            ttft.append(first - due)
        itl += [b - a for a, b in zip(s.times, s.times[1:]) if t_open < b <= t_close]
    return {"ttft": ttft, "itl": itl}


def logit_gaps(params, cfg: dict, sample: list, max_len: int, *, low=None) -> list:
    """Widest gap, per request, by which a served token's logit lies below
    the reference's best (``low``: the control, reading the reference's gap
    of the token the lower precision puts first). Sequences are padded at
    the end to ``max_len`` so one compiled program serves them all."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def both(params, tokens):
        with jax.default_matmul_precision("highest"):
            hi = reference.logits(params, cfg, tokens)
            lo = reference.logits(params, cfg, tokens, low=low) if low else hi
        return hi, lo

    out = []
    for prompt, toks in sample:
        seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
        padded = np.zeros(max_len, np.int32)
        padded[:len(seq)] = seq
        hi, lo = both(params, jnp.asarray(padded))
        pos = np.arange(len(prompt) - 1, len(seq))
        hi = np.asarray(hi[pos[0]:pos[-1] + 1])
        chosen = (np.asarray(toks) if low is None
                  else np.asarray(jnp.argmax(lo[pos[0]:pos[-1] + 1], -1)))
        gap = hi.max(-1) - hi[np.arange(len(pos)), chosen]
        out.append(float(gap.max()))
    return out


def build(cell, seed: int, smoke: bool):
    """The configuration as run, the seeded weights and a fresh engine."""
    from repro.serving.pages import PagedServingEngine

    cfg = cell.config
    eng_cfg = make_engine_config(cfg, smoke)
    if smoke:
        cfg = checks_lib.hf_config_of(eng_cfg, cfg)
    e = dict(cfg["serving"], **cell.traffic.get("engine", {}))
    params = reference.init_params(cfg, seed)
    engine = PagedServingEngine(eng_cfg, params, num_pages=e["num_pages"],
                                page_size=e["page_size"], max_reqs=e["max_reqs"],
                                prompt_len=e["prompt_len"], max_len=e["max_len"])
    return cfg, e, params, engine


def warm_up(engine, session, stream: Optional[str], arrivals) -> None:
    """Compile (or load from the cache) every program the window drives:
    the engine's insert is one program per page-chain length, so one
    request of each length the arrivals will need is admitted and released;
    then one request goes through admit, step and emit to the end."""
    import jax
    from repro.serving.engine import Request

    import harness
    ps = engine.page_size
    head = np.asarray(arrivals[0].prompt[:ps])
    for n in sorted({-(-(len(a.prompt) + a.max_new) // ps) for a in arrivals}):
        r = Request(-1, head, max_new=n * ps - len(head))
        if not engine.admit(r):
            raise harness.BenchError(f"a {n}-page request does not fit the pool")
        engine.free_resource(engine.active.index(r))
    warm = Request(-1, arrivals[0].prompt, max_new=2)
    if not engine.admit(warm):
        raise harness.BenchError("the warm-up request does not fit the pool")
    while not warm.done:
        engine.step()
        if stream is not None:
            session.emit(stream, 0, engine.snapshot_payload)
    session.wait_idle()
    jax.block_until_ready(engine.pool)


def plan_task(plan: dict) -> tuple[Optional[str], Optional[str], int]:
    task = next(iter(plan["tasks"]), None)
    if task is None:
        return None, None, 1
    return task, plan["tasks"][task]["stream"], plan["tasks"][task]["every"]


def run(ctx) -> dict:
    import jax
    from repro.core import Session

    import harness

    cell, tr = ctx.cell, ctx.cell.traffic
    plan = tr["plan"]
    task, stream, every = plan_task(plan)
    cfg, e, params, engine = build(cell, ctx.seed, ctx.smoke)
    arrivals = draw_arrivals(tr, ctx.seed, ctx.seconds, cfg["vocab_size"])
    log: dict = {"admit": [], "step": [], "emit": []}
    session = Session(plan, raise_on_error=True)
    watcher = None
    try:
        warm_up(engine, session, stream, arrivals)
        if stream is not None:
            # a publish is acknowledged when its task result appears
            watcher = acks.AckWatcher(lambda: [
                r.step for r in list(session.results) if r.task == task])

        t0 = time.perf_counter()
        t_open = t0 + tr["lead_in_s"]
        t_close = t_open + ctx.seconds
        if ctx.trace:
            import devtrace
            jax.profiler.start_trace(str(ctx.workdir / "trace"))
            with jax.profiler.TraceAnnotation(devtrace.CLOCK_MARK):
                t_sync = time.perf_counter()
        served = serve(engine, session, stream, arrivals, t0, t_close, log)
        if ctx.trace:
            jax.profiler.stop_trace()
        mem = harness.memory_peak_bytes(cell.chips)
        checks: dict = {}
        if stream is not None:
            checks["snapshot_mismatch"] = {
                "value": _snapshot_mismatch(engine, session, task, stream,
                                            every, log),
                "limit": 0}
    finally:
        if watcher is not None:
            watcher.close()
        session.finish()

    spans = session.telemetry.spans()
    lat = latency(served, log["unadmitted"], t0, t_open, t_close)
    end_to_end = {"ttft_p90_ms": 1e3 * _percentile(lat["ttft"], 90),
                  "itl_p95_ms": 1e3 * _percentile(lat["itl"], 95),
                  "setup_s": t0 - ctx.t_start}
    if stream is not None:
        step_end = {st: s1 for st, _, s1, _ in log["step"]}
        end_to_end["state_age_p95_s"] = acks.state_age_p95(
            step_end, watcher.seen, t0, t_open, t_close)
    rec = {"window": (t_open, t_close), "window_s": t_close - t_open,
           "config": cfg, "traffic": tr, "peaks": ctx.peaks,
           "main_thread": "MainThread", "task": task,
           "spans": [(s.name, s.t0, s.t1, s.thread, s.step) for s in spans],
           "admits": [a for a in log["admit"] if t_open <= a[1] <= t_close],
           "steps": [s for s in log["step"] if t_open <= s[2] <= t_close],
           "emits": [e for e in log["emit"] if t_open <= e[1] <= t_close]}
    if ctx.trace:
        rec["trace"] = _reduce_trace(ctx, t_sync, rec)

    # a sample of finished requests: the longest, and more drawn from the seed
    done = [s for s in served if s.req.done]
    if not done:
        raise harness.BenchError("no request finished in the window")
    rng = np.random.default_rng((ctx.seed + 1) & (2**64 - 1))
    longest = max(done, key=lambda s: len(s.req.prompt) + len(s.req.out))
    rest = [s for s in done if s is not longest]
    k = min(tr["check_requests"] - 1, len(rest))
    picked = [longest] + [rest[i] for i in rng.choice(len(rest), k, replace=False)]
    sample = [(np.asarray(s.req.prompt), list(s.req.out)) for s in picked]
    del engine, served, session, picked, done, rest, longest
    gc.collect()
    gaps = logit_gaps(params, cfg, sample, e["max_len"])
    checks["logit_gap"] = {"value": max(gaps), "limit": tr["limits"]["logit_gap"]}
    if ctx.control:
        rec["control_gap"] = max(logit_gaps(params, cfg, sample, e["max_len"],
                                            low=np.dtype("float8_e4m3fn")))
    return {"window": (t_open, t_close), "end_to_end": end_to_end,
            "record": rec, "checks": checks, "memory_peak_bytes": mem,
            "attempted": len(lat["ttft"]), "failed": 0}


def _snapshot_mismatch(engine, session, task: str, stream: str, every: int,
                       log: dict) -> int:
    """Publish the engine's state once more, drained, and hold the newest
    snapshot, restored through the store, against it leaf for leaf."""
    import jax
    session.wait_idle()
    step = every * (log["next_step"] // every + 1)
    session.emit(stream, step, engine.snapshot_payload)
    session.wait_idle()
    got_step, leaves = session.snapshot_store(task).restore(stream)
    want = {jax.tree_util.keystr(p): np.asarray(v) for p, v in
            jax.tree_util.tree_flatten_with_path(
                engine.snapshot_payload()["cache"])[0]}
    return int(got_step != step) + sum(
        1 for k, v in want.items()
        if k not in leaves or not np.array_equal(np.asarray(leaves[k]), v))


def _reduce_trace(ctx, t_sync: float, rec: dict):
    import devtrace
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(devtrace.find_xplane(ctx.workdir / "trace")))
    off = devtrace.clock_offset_ns(pd) - t_sync * 1e9
    to_ns = lambda t: t * 1e9 + off
    host = [(n, to_ns(a), to_ns(b)) for n, a, b, th, _ in rec["spans"]
            if th == rec["main_thread"]]
    host += [("bench/admit", to_ns(a), to_ns(b)) for a, b, _ in rec["admits"]]
    host += [("bench/step", to_ns(a), to_ns(b)) for _, a, b, _ in rec["steps"]]
    host += [("bench/emit", to_ns(a), to_ns(b)) for a, b in rec["emits"]]
    w0, w1 = rec["window"]
    return devtrace.reduce_profile(
        pd, (to_ns(w0), to_ns(w1)), host,
        device_plane=lambda n: n.startswith("/device:TPU"),
        ops_line=lambda n: n == "XLA Ops",
        modules_line=lambda n: n == "XLA Modules")
