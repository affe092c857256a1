"""Mesh-level kill-point driver: drop a host mid-run, resume elastically.

Run as a script (the pytest wrapper in ``test_fault.py`` and the CI smoke
step both do); it re-execs itself with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so a CPU-only
machine presents a multi-device platform (the test suite's conftest
requires the *in-process* device count to stay 1, hence the subprocess).

Scenario (all deterministic — injected session clock, seeded synthetic
batches):

  golden    train ``--steps`` on a (2, 2) ('data', 'model') mesh over
            4 devices (2 simulated hosts x 2 devices), no failures.
  failure   same run with the ``fault`` preset heartbeating per step and
            v2 shard checkpoints every ``--ckpt-every``; host 1 stops
            beating at ``--fail-at``, the controller declares it failed
            after the grace window, and the run halts.
  recover   ``Session.restore(elastic=True)`` over host 0's surviving
            2 devices: ``plan_elastic_remesh`` picks the (1, 2) grid
            (smallest merge factor at equal device count), the v2
            checkpoint re-places under the shrunken mesh, and training
            resumes to ``--steps``.

The driver prints one JSON object on the last stdout line: golden/resumed
losses, the detection step, the remesh plan, and the restored step. The
wrapper asserts the resumed tail matches golden within lossy checkpoint
bounds.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

_CHILD_ENV = "REPRO_KILLPOINT_CHILD"
_DEVICES = 8


def reexec_with_devices() -> int:
    """Re-run this script in a child with the multi-device XLA platform."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_DEVICES} "
        + env.get("XLA_FLAGS", "")).strip()
    env[_CHILD_ENV] = "1"
    env["JAX_PLATFORMS"] = "cpu"           # virtual CPU devices, never a chip
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)]
                          + sys.argv[1:], env=env)
    return proc.returncode


def run_scenario(steps: int, fail_at: int, ckpt_every: int,
                 ckpt_dir: str, grace_s: float = 2.5) -> dict:
    import jax
    import numpy as np

    from repro.configs import base as configs
    from repro.core import Session
    from repro.distributed import sharding
    from repro.launch import train
    from repro.launch.mesh import make_mesh

    assert len(jax.devices()) >= 4, jax.devices()
    cfg = configs.get("smollm-135m", smoke=True)
    shape = configs.SMOKE_SHAPE
    step_cfg = train.StepConfig()

    def synth_batch(step: int, shardings) -> dict:
        rng = np.random.RandomState(1000 + step)
        b, s = shape.global_batch, shape.seq_len
        return jax.device_put({
            "tokens": rng.randint(0, cfg.vocab_size, (b, s)).astype(np.int32),
            "labels": rng.randint(0, cfg.vocab_size, (b, s)).astype(np.int32),
        }, shardings)

    def fresh_state(mesh, shardings):
        with sharding.mesh_context(mesh):
            state = train.init_state(cfg, jax.random.PRNGKey(0), step_cfg.opt)
        # jit does not reshard a committed argument to its in_shardings
        return jax.device_put(state, shardings)

    # -- golden: the non-failed run on the full (2, 2) mesh ------------------
    mesh_full = make_mesh((2, 2), ("data", "model"), devices=jax.devices())
    jit_full, st_full, b_full, _ = train.jit_train_step(
        cfg, mesh_full, step_cfg, shape, donate=False)
    golden_losses = []
    with sharding.mesh_context(mesh_full):
        state = fresh_state(mesh_full, st_full)
        for i in range(steps):
            state, metrics = jit_full(state, synth_batch(i, b_full))
            golden_losses.append(float(metrics["loss"]))

    # -- failure: fault preset + checkpoints; host 1 dies at fail_at ---------
    # hosts 0/1 own devices 0-1/2-3; the injected clock advances 1s per step
    now = [0.0]
    plan = {
        "streams": ["train_state", "health"],
        "workers": 2,
        "tasks": {
            "checkpoint": {"stream": "train_state", "preset": "checkpoint",
                           "every": ckpt_every, "placement": "async",
                           "options": {"directory": ckpt_dir}},
            "fault": {"stream": "health", "preset": "fault", "every": 1,
                      "placement": "sync", "pipelined": False,
                      "options": {"hosts": [0, 1], "grace_s": grace_s}},
        },
    }
    detect_step = None
    failed = []
    with sharding.mesh_context(mesh_full):
        state = fresh_state(mesh_full, st_full)
        with Session(plan, clock=lambda: now[0],
                     raise_on_error=True) as session:
            session.set_checkpoint_meta(mesh=mesh_full)
            ctrl = session.fault_controller()
            for i in range(steps):
                now[0] += 1.0
                state, metrics = jit_full(state, synth_batch(i, b_full))
                session.emit("train_state", i, lambda s=state: s)
                beats = {0: 0.1}
                if i < fail_at:
                    beats[1] = 0.1          # host 1 beats until it dies
                session.emit("health", i, {"hosts": beats})
                failed = ctrl.failed_hosts()
                if failed:
                    detect_step = i         # halt: the mesh lost a host
                    break
            session.wait_idle()
        fail_report = session.report()

    assert failed == [1], f"expected host 1 failed, got {failed}"
    assert detect_step is not None and detect_step >= fail_at

    # -- recover: elastic restore on host 0's surviving devices --------------
    survivors = list(jax.devices()[:2])
    resume_plan = {"streams": ["train_state"], "workers": 2, "tasks": {
        "checkpoint": {"stream": "train_state", "preset": "checkpoint",
                       "every": ckpt_every, "placement": "async",
                       "options": {"directory": ckpt_dir}}}}
    resumed_losses: dict[int, float] = {}
    with Session(resume_plan, raise_on_error=True) as session:
        template = train.state_spec(cfg)
        start, state = session.restore(
            template, elastic=True, devices=survivors,
            make_shardings=lambda m: train.state_shardings(cfg, m))
        rm = session.remesh
        mesh_new = rm.mesh
        with sharding.mesh_context(mesh_new):
            jit_new, st_new, b_new, _ = train.jit_train_step(
                cfg, mesh_new, step_cfg, shape, donate=False)
            state = jax.device_put(state, st_new)
            session.set_checkpoint_meta(mesh=mesh_new)
            for i in range(start + 1, steps):
                state, metrics = jit_new(state, synth_batch(i, b_new))
                resumed_losses[i] = float(metrics["loss"])
                session.emit("train_state", i, lambda s=state: s)
            session.wait_idle()

    return {
        "golden_losses": golden_losses,
        "resumed_losses": resumed_losses,
        "detect_step": detect_step,
        "restored_step": start,
        "failed_hosts": failed,
        "new_shape": list(rm.plan.new_shape),
        "merge_factor": rm.plan.model_merge_factor,
        "fault_report": {
            "failed_hosts": fail_report["fault"]["failed_hosts"],
            "alive_hosts": fail_report["fault"]["alive_hosts"],
        },
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=9)
    ap.add_argument("--fail-at", type=int, default=4)
    ap.add_argument("--ckpt-every", type=int, default=2)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    if os.environ.get(_CHILD_ENV) != "1":
        sys.exit(reexec_with_devices())

    import tempfile
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="repro_killpoint_")
    out = run_scenario(args.steps, args.fail_at, args.ckpt_every, ckpt_dir)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
