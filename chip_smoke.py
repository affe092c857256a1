"""Prove on a TPU that training and serving run end to end at published widths.

    python chip_smoke.py               # one chip: train, resume, serve
    python chip_smoke.py --four-chips  # 2x2 train, elastic 1x2 resume

One process drives the chip; nothing here starts a child process. The
one-chip run drives smollm-135m (30 layers, d_model 576, vocab 49152) with
random weights from ``--seed`` through the repository's entry points:

  train   ``train_loop`` with analytics and a hybrid checkpoint (the Pallas
          spectral-lossy stage shrinks the moments on the device), then a
          second ``train_loop`` that resumes from that checkpoint. Checks:
          finite losses, "resumed from step N", the checkpoint's lossy
          leaves round-trip within ``ref.error_bound(eps)`` and its other
          leaves exactly, and the compress program holds Pallas kernels.
  serve   ``serve_loop`` on the paged engine with the ``serve_snapshot``
          task attached (checks: every request served, page pool back to 0
          pages in use, at least one snapshot published); the Pallas paged
          attention kernel against the gather path at smollm-135m's heads
          (tolerance of ``tests/test_paged_serving.py``); and the paged
          engine's greedy tokens with the kernel (present in its decode
          program) against the same engine on the gather path, both
          computing in f32.

``--four-chips`` runs only the sharded path: a 2x2 ('data', 'model') mesh
trains with checkpoints, restores elastically onto a 1x2 mesh of two
survivors and continues; the resumed losses must stay within
max(0.05, 2%) of an uninterrupted 2x2 run of the same seed and batches.

Per-phase lines go to stdout; the last line is one JSON object,
``{"ok": true, "device": {...}}``. Any failed check, a missing TPU, or a
directory without the rest of the repository exits non-zero with no result.
Everything written goes under ``--out``. ``JAX_COMPILATION_CACHE_DIR``, if
set, holds the compile cache (else ``<repo>/.jax_cache``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "smollm-135m"
EPS = 1e-2                     # the checkpoint preset's lossy_eps


class SmokeError(RuntimeError):
    """A check of the smoke failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeError(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(count: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeError(f"no TPU found: JAX sees {devs[0].platform} "
                         f"devices only ({len(devs)} x {devs[0].device_kind})")
    if len(devs) < count:
        raise SmokeError(f"needs {count} TPU chips, JAX sees {len(devs)}")
    return devs


# ---------------------------------------------------------------------------
# train: hybrid checkpoint, resume, round trip, kernels in the compress program
# ---------------------------------------------------------------------------

def train_phase(out: Path, *, smoke: bool, shape, steps: int, ckpt_every: int,
                seed: int) -> None:
    import jax
    import numpy as np
    from repro.checkpoint.manager import default_lossy_policy
    from repro.core import Session
    from repro.kernels import ops, ref
    from repro.launch.train import train_loop

    ckpt = out / "ckpt"
    shutil.rmtree(ckpt, ignore_errors=True)
    check(steps > ckpt_every and (steps - 1) % ckpt_every == 0,
          "the last step must be a checkpoint step")
    lines: list[str] = []

    def tee(msg: str) -> None:
        lines.append(msg)
        log(f"  {msg}")

    t0 = time.perf_counter()
    r1 = train_loop(ARCH, smoke=smoke, shape=shape, steps=steps, seed=seed,
                    insitu_mode="hybrid", ckpt_dir=str(ckpt),
                    ckpt_every=ckpt_every, analytics_every=ckpt_every,
                    log=tee)
    wall = time.perf_counter() - t0
    losses, step_s = r1["losses"], r1["step_s"]
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    ck = r1["session_report"]["checkpoint"]
    log(f"train: {steps} steps of {ARCH} at seq {shape.seq_len} x batch "
        f"{shape.global_batch} in {wall:.1f}s; first step (compile) "
        f"{step_s[0]:.2f}s, steady step median "
        f"{float(np.median(step_s[1:])):.4f}s (host clock, ends in the "
        f"loss read); losses {[round(x, 4) for x in losses]}")
    log(f"train: checkpoints saved {ck['saves']}, last step "
        f"{ck['last_step']}, {ck['raw_bytes']} raw -> {ck['stored_bytes']} "
        f"stored bytes; analytics results {r1['insitu_results']}")
    check(ck["last_step"] == steps - 1,
          f"last checkpoint at step {ck['last_step']}, wanted {steps - 1}")
    check(not ops._interpret(), "the kernels would run in interpret mode")

    # the checkpoint on disk against the state it was taken from
    final = r1["state"]
    plan = {"streams": ["train_state"], "tasks": {"checkpoint": {
        "stream": "train_state", "preset": "checkpoint",
        "options": {"directory": str(ckpt)}}}}
    with Session(plan) as s:
        step, restored = s.restore(final)
    check(step == steps - 1, f"restored step {step}, wanted {steps - 1}")
    worst, n_lossy = 0.0, 0
    flat_a = jax.tree_util.tree_flatten_with_path(final)[0]
    flat_b = jax.tree_util.tree_leaves(restored)
    for (path, a), b in zip(flat_a, flat_b):
        key = jax.tree_util.keystr(path)
        a32 = np.asarray(a, np.float32)
        b32 = np.asarray(b, np.float32)
        if default_lossy_policy(key) and a.size > 1:
            err = ref.rel_l2_error(a32, b32)
            worst, n_lossy = max(worst, err), n_lossy + 1
            check(err <= ref.error_bound(EPS),
                  f"{key}: round-trip error {err} > {ref.error_bound(EPS)}")
        else:
            check(np.array_equal(a32, b32), f"{key}: lossless leaf changed")
    check(n_lossy > 0, "no lossy leaf in the checkpoint")
    log(f"train: checkpoint round trip ok: {n_lossy} lossy leaves, worst "
        f"rel-L2 {worst:.3e} <= bound {ref.error_bound(EPS):.3e}; "
        f"lossless leaves bit-exact")

    t0 = time.perf_counter()
    hlo = ops.lower_compress_tree(final, EPS, default_lossy_policy
                                  ).compile().as_text()
    n_calls = hlo.count("tpu_custom_call")
    log(f"train: compress program holds {n_calls} tpu_custom_call(s) "
        f"(recompiled in {time.perf_counter() - t0:.1f}s); tuned tiles "
        f"{ops.tuned_tiles()}")
    check(n_calls > 0, "the compress program holds no Pallas kernel")

    lines.clear()
    r2 = train_loop(ARCH, smoke=smoke, shape=shape, steps=2, seed=seed,
                    insitu_mode="hybrid", ckpt_dir=str(ckpt),
                    ckpt_every=ckpt_every, analytics_every=ckpt_every,
                    log=tee)
    check(f"resumed from step {steps - 1}" in lines,
          f"second run did not resume from step {steps - 1}: {lines}")
    check(all(math.isfinite(x) for x in r2["losses"]),
          f"non-finite loss after resume {r2['losses']}")
    log(f"train: resumed from step {steps - 1}, losses "
        f"{[round(x, 4) for x in r2['losses']]}")


# ---------------------------------------------------------------------------
# serve: entry point with snapshots, kernel vs gather attention and tokens
# ---------------------------------------------------------------------------

def serve_phase(out: Path, *, smoke: bool, n_requests: int, prompt_len: int,
                max_new: int, max_len: int, num_pages: int,
                seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import base as configs
    from repro.kernels import paged_attention as PK
    from repro.launch.serve import default_serve_plan, serve_loop
    from repro.models import attention as attn_lib
    from repro.models import params as P_lib
    from repro.models import transformer
    from repro.serving.engine import Request
    from repro.serving.pages import PagedServingEngine

    page_size = 16
    t0 = time.perf_counter()
    plan = default_serve_plan(insitu_mode="sync", snapshot_every=4,
                              snapshot_dir=str(out / "snapshots"))
    shutil.rmtree(out / "snapshots", ignore_errors=True)
    res = serve_loop(ARCH, smoke=smoke, n_requests=n_requests,
                     max_new=max_new, prompt_len=prompt_len, max_len=max_len,
                     num_pages=num_pages, page_size=page_size, seed=seed,
                     plan=plan, log=lambda m: log(f"  {m}"))
    wall = time.perf_counter() - t0
    reqs = res["requests"]
    toks = sum(len(r.out) for r in reqs)
    snap = res["session_report"]["tasks"].get("kv_snapshot", {})
    log(f"serve: {len(reqs)} requests, prompt {prompt_len}, {toks} tokens, "
        f"{res['steps']} engine steps in {wall:.1f}s including compile "
        f"({res['tok_per_s']:.1f} tok/s host clock, not a benchmark); "
        f"snapshots published {snap.get('publishes', 0)}")
    check(all(r.done and len(r.out) == max_new for r in reqs),
          "not every request finished")
    check(res["page_stats"]["used_pages"] == 0,
          f"{res['page_stats']['used_pages']} pages still in use at exit")
    check(snap.get("publishes", 0) >= 1, "no snapshot published")

    # the kernel against the gather path, f32, smollm-135m's heads
    cfg = configs.get(ARCH, smoke=smoke)
    rng = np.random.default_rng(seed)
    b, pps = 8, max_len // page_size
    n_kv, hq, d = cfg.n_kv_heads, cfg.n_heads, cfg.resolved_head_dim
    kp = jnp.asarray(rng.standard_normal((num_pages, page_size, n_kv, d)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((num_pages, page_size, n_kv, d)),
                     jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, 1, hq, d)), jnp.float32)
    table = jnp.asarray(rng.integers(1, num_pages, (b, pps)), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, pps * page_size + 1, b), jnp.int32)
    with jax.default_matmul_precision("highest"):
        o_k = PK.paged_decode_attention(q, kp, vp, table, lengths,
                                        interpret=False)
        o_g = attn_lib.paged_decode_attention(q, kp, vp, table, lengths,
                                              use_kernel=False)
    diff = float(jnp.max(jnp.abs(o_k - o_g)))
    log(f"serve: paged attention kernel vs gather (f32, {hq} q / {n_kv} kv "
        f"heads, head_dim {d}): max |diff| {diff:.3e}")
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_g),
                               rtol=1e-5, atol=1e-5)

    # greedy tokens: the same paged engine, kernel vs gather, in f32 (random
    # weights make near-ties common, so bf16 rounding alone reorders them)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        P_lib.materialize(jax.random.PRNGKey(seed),
                          transformer.param_spec(cfg32)))
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len)
               for _ in range(n_requests)]
    outs = []
    with jax.default_matmul_precision("highest"):
        for use_kernel in (True, False):
            eng = PagedServingEngine(cfg32, params, num_pages=num_pages,
                                     page_size=page_size, max_reqs=8,
                                     prompt_len=prompt_len, max_len=max_len,
                                     use_kernel=use_kernel)
            rs = [Request(i, p, max_new=max_new)
                  for i, p in enumerate(prompts)]
            eng.run(rs)
            check(eng.page_stats()["used_pages"] == 0, "f32 engine leaked")
            outs.append([r.out for r in rs])
            if use_kernel:
                hlo = eng._decode.lower(
                    eng.params, eng.pool, eng.state, eng.page_table,
                    eng.tokens, eng.lengths).compile().as_text()
                check("tpu_custom_call" in hlo,
                      "the paged decode program holds no Pallas kernel")
    same = sum(a == b for a, b in zip(*outs))
    log(f"serve: f32 greedy tokens, kernel vs gather engine: {same}/"
        f"{n_requests} requests identical")
    check(same == n_requests, f"kernel tokens {outs[0]} != gather {outs[1]}")


# ---------------------------------------------------------------------------
# four chips: 2x2 train with checkpoints, elastic restore onto 1x2
# ---------------------------------------------------------------------------

def four_chip_phase(out: Path, devices, *, smoke: bool, shape, steps: int,
                    fail_at: int, ckpt_every: int, seed: int) -> None:
    import jax
    from repro.configs import base as configs
    from repro.core import Session
    from repro.data.pipeline import batch_spec_for, synth_batch
    from repro.distributed import sharding
    from repro.launch import train
    from repro.launch.mesh import make_mesh

    cfg = configs.get(ARCH, smoke=smoke)
    step_cfg = train.StepConfig()
    spec = batch_spec_for(cfg, shape)
    ckpt = out / "ckpt_4chip"
    shutil.rmtree(ckpt, ignore_errors=True)

    def spread(state, devs, what: str) -> None:
        leaves = jax.tree.leaves(state["params"])
        for leaf in leaves:
            check(leaf.sharding.device_set == set(devs),
                  f"{what}: a leaf is not on all of {len(devs)} devices")
        split = sum(not leaf.sharding.is_fully_replicated for leaf in leaves)
        check(split > 0, f"{what}: every parameter is replicated")
        log(f"four: {what}: {split}/{len(leaves)} parameter leaves split "
            f"over {len(devs)} devices")

    def run(jitted, state, b_sh, lo, hi) -> tuple[dict, dict]:
        losses = {}
        for i in range(lo, hi):
            batch = jax.device_put(synth_batch(spec, i, seed), b_sh)
            state, metrics = jitted(state, batch)
            losses[i] = float(metrics["loss"])
        return state, losses

    mesh_full = make_mesh((2, 2), ("data", "model"), devices=devices[:4])
    jit_full, st_full, b_full, _ = train.jit_train_step(
        cfg, mesh_full, step_cfg, shape, donate=False)

    def fresh():
        with sharding.mesh_context(mesh_full):
            state = train.init_state(cfg, jax.random.PRNGKey(seed),
                                     step_cfg.opt)
        return jax.device_put(state, st_full)

    t0 = time.perf_counter()
    with sharding.mesh_context(mesh_full):
        state = fresh()
        spread(state, devices[:4], "2x2 mesh")
        _, golden = run(jit_full, state, b_full, 0, steps)
    log(f"four: uninterrupted 2x2 run, {steps} steps in "
        f"{time.perf_counter() - t0:.1f}s: {golden}")

    plan = {"streams": ["train_state"], "workers": 2, "tasks": {
        "checkpoint": {"stream": "train_state", "preset": "checkpoint",
                       "every": ckpt_every, "placement": "async",
                       "options": {"directory": str(ckpt)}}}}
    with sharding.mesh_context(mesh_full):
        state = fresh()
        with Session(plan, raise_on_error=True) as session:
            session.set_checkpoint_meta(mesh=mesh_full)
            for i in range(fail_at + 1):
                state, _ = run(jit_full, state, b_full, i, i + 1)
                session.emit("train_state", i, lambda s=state: s)
            session.wait_idle()
    del state
    log(f"four: run with checkpoints every {ckpt_every} halted after step "
        f"{fail_at}: devices {[d.id for d in devices[2:4]]} lost")

    survivors = list(devices[:2])
    with Session(plan, raise_on_error=True) as session:
        start, state = session.restore(
            train.state_spec(cfg), elastic=True, devices=survivors,
            make_shardings=lambda m: train.state_shardings(cfg, m))
        mesh_new = session.remesh.mesh
        check(tuple(mesh_new.devices.shape) == (1, 2),
              f"remeshed to {mesh_new.devices.shape}, wanted (1, 2)")
        with sharding.mesh_context(mesh_new):
            jit_new, st_new, b_new, _ = train.jit_train_step(
                cfg, mesh_new, step_cfg, shape, donate=False)
            state = jax.device_put(state, st_new)
            spread(state, survivors, "1x2 mesh after elastic restore")
            _, resumed = run(jit_new, state, b_new, start + 1, steps)
    log(f"four: elastic restore of step {start} onto 1x2, resumed "
        f"losses {resumed}")
    check(bool(resumed) and max(resumed) == steps - 1, "resume did not finish")
    for i, loss in resumed.items():
        band = max(0.05, 0.02 * abs(golden[i]))
        check(abs(loss - golden[i]) <= band,
              f"step {i}: resumed loss {loss} vs uninterrupted {golden[i]} "
              f"outside {band}")
    log("four: resumed losses within max(0.05, 2%) of the uninterrupted run")


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded 2x2 -> 1x2 elastic path")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(ROOT / "smoke_out"),
                    help="directory for everything the smoke writes")
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        raise SmokeError(f"{ROOT} holds no src/repro: run chip_smoke.py "
                         "from a checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    n_chips = 4 if args.four_chips else 1
    devs = require_tpu(n_chips)
    import jax
    from repro.configs.base import ShapeConfig
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    log(f"device: {len(devs)} x {devs[0].device_kind} ({devs[0].platform}), "
        f"jax {jax.__version__}, compile cache {cache}, output {out}")
    shape = ShapeConfig("chip_smoke", "train", 2048, 8)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(out, devs, smoke=False, shape=shape, steps=8,
                        fail_at=4, ckpt_every=2, seed=args.seed)
    else:
        train_phase(out, smoke=False, shape=shape, steps=6, ckpt_every=5,
                    seed=args.seed)
        serve_phase(out, smoke=False, n_requests=8, prompt_len=128,
                    max_new=16, max_len=256, num_pages=65, seed=args.seed)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
