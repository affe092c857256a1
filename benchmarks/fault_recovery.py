"""Fault recovery: elastic-restore latency and fault-preset overhead.

Two measurements, both tied to the elastic-fault-tolerance arc:

  * **elastic vs full blocking restore.** After a host failure the naive
    recovery path restores the v2 shard checkpoint onto the *original*
    mesh (blocking on placements for devices that no longer exist in a
    real deployment) and then re-shards the whole tree onto the survivors.
    ``Session.restore(elastic=True)`` instead plans the shrunken mesh with
    ``plan_elastic_remesh`` and re-places leaves under it in one read —
    the TP-shard merge is implicit because v2 leaves are stored logically
    complete. This part needs a multi-device platform, so it re-execs in a
    subprocess with ``--xla_force_host_platform_device_count=8`` (the same
    pattern as the kill-point test; the in-process device count must stay
    untouched for the rest of the suite).

  * **fault-preset steady-state overhead.** The same jitted step loop with
    and without the ``fault`` task (sync, every=1: heartbeat + EWMA +
    mitigation evaluation per step). The acceptance gate is < 2 % of step
    time on the no-failure path (full mode; quick mode only records).

The metrics dict lands in ``BENCH_runtime.json`` under ``fault`` on
``--full`` runs of ``benchmarks.run``. CI smoke-runs quick mode.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_CHILD_ENV = "REPRO_FAULT_BENCH_CHILD"


# ---------------------------------------------------------------------------
# child: restore comparison on a multi-device platform
# ---------------------------------------------------------------------------

def _child_restore_bench(quick: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import Session
    from repro.launch.mesh import make_mesh

    n_leaves = 4 if quick else 8
    dim = (256, 1024) if quick else (1024, 2048)

    state = {f"w{i}": jnp.asarray(
        np.random.RandomState(i).rand(*dim).astype(np.float32))
        for i in range(n_leaves)}
    template = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                for k, v in state.items()}

    mesh_full = make_mesh((2, 2), ("data", "model"), devices=jax.devices())

    def shardings_for(mesh):
        return {k: NamedSharding(mesh, P(None, "model"))
                for k in template}

    ckpt_dir = os.path.join(os.environ.get("TMPDIR", "/tmp"),
                            f"repro_fault_bench_{os.getpid()}")
    plan = {"streams": ["state"], "tasks": {
        "checkpoint": {"stream": "state", "preset": "checkpoint",
                       "every": 1, "placement": "sync",
                       "options": {"directory": ckpt_dir}}}}
    with Session(plan) as s:
        s.set_checkpoint_meta(mesh=mesh_full)
        s.emit("state", 0, state)

    # full blocking restore: read onto the ORIGINAL mesh, then re-shard
    # the whole tree onto the survivors' mesh (the naive recovery path)
    survivors = list(jax.devices()[:2])
    with Session(plan) as s:
        t0 = time.perf_counter()
        _, st_full = s.restore(template, shardings=shardings_for(mesh_full))
        _, rm = _elastic(s, template, survivors, shardings_for,
                         plan_only=True)
        st_moved = jax.device_put(st_full, shardings_for(rm.mesh))
        jax.block_until_ready(st_moved)
        t_full = time.perf_counter() - t0

    # elastic restore: one read, re-placed directly under the shrunken mesh
    with Session(plan) as s:
        t0 = time.perf_counter()
        _, st_el = _elastic(s, template, survivors, shardings_for)
        jax.block_until_ready(st_el)
        t_elastic = time.perf_counter() - t0
        rm = s.remesh

    for k in template:
        np.testing.assert_array_equal(np.asarray(st_el[k]),
                                      np.asarray(st_moved[k]))

    import shutil
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    raw_mb = sum(v.size * 4 for v in state.values()) / 1e6
    return {"full_restore_s": t_full, "elastic_restore_s": t_elastic,
            "restore_speedup": t_full / t_elastic,
            "new_shape": list(rm.plan.new_shape),
            "merge_factor": rm.plan.model_merge_factor,
            "state_mb": raw_mb}


def _elastic(session, template, survivors, shardings_for, plan_only=False):
    if plan_only:
        # resolve the remesh geometry without paying a second read
        from repro.distributed.fault import plan_elastic_remesh
        from repro.launch.mesh import make_mesh
        meta = session.checkpoint.read_meta()
        plan = plan_elastic_remesh(tuple(meta["mesh"]["shape"]),
                                   tuple(meta["mesh"]["axes"]),
                                   len(survivors))
        mesh = make_mesh(plan.new_shape, plan.axis_names, devices=survivors)

        class _RM:
            pass

        rm = _RM()
        rm.mesh = mesh
        rm.plan = plan
        return None, rm
    step, st = session.restore(template, elastic=True, devices=survivors,
                               make_shardings=shardings_for)
    return step, st


def _spawn_child(quick: bool) -> dict:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                        + env.get("XLA_FLAGS", "")).strip()
    env[_CHILD_ENV] = "1"
    env["JAX_PLATFORMS"] = "cpu"           # virtual CPU devices, never a chip
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [os.path.join(repo, "src"), repo,
                    env.get("PYTHONPATH", "")] if p)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)]
        + (["--quick"] if quick else []),
        env=env, capture_output=True, text=True, timeout=540)
    if proc.returncode != 0:
        raise RuntimeError(f"fault bench child failed:\n{proc.stderr[-4000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# in-process: fault-preset steady-state overhead
# ---------------------------------------------------------------------------

def _overhead_bench(quick: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import Session

    # the step must be training-sized (a few ms) for the 2% gate to mean
    # anything — the preset's absolute cost is tens of microseconds
    steps = 60 if quick else 300
    batch, dim = (64, 512) if quick else (128, 1024)
    w = jnp.asarray(np.random.RandomState(0).rand(dim, dim)
                    .astype(np.float32) / dim)

    @jax.jit
    def step_fn(x):
        for _ in range(4):
            x = jnp.tanh(x @ w)
        return x

    def drive(plan, emit_health):
        x = jnp.ones((batch, dim), jnp.float32)
        times = []
        with Session(plan) as session:
            for i in range(steps + 10):
                t0 = time.perf_counter()
                x = step_fn(x)
                jax.block_until_ready(x)
                dt = time.perf_counter() - t0
                if emit_health:
                    session.emit("health", i, {"host": 0, "step_s": dt})
                if i >= 10:                     # warmup excluded
                    times.append(time.perf_counter() - t0)
        return float(np.median(times))

    base_plan = {"streams": [], "tasks": {}}
    fault_plan = {"streams": ["health"], "tasks": {
        "fault": {"stream": "health", "preset": "fault", "every": 1,
                  "placement": "sync", "pipelined": False,
                  "options": {"hosts": [0], "grace_s": 30.0}}}}
    base_s = drive(base_plan, emit_health=False)
    fault_s = drive(fault_plan, emit_health=True)
    overhead = (fault_s - base_s) / base_s
    return {"base_step_s": base_s, "fault_step_s": fault_s,
            "overhead_frac": overhead, "steps": steps}


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

def run(quick: bool = True) -> dict:
    out = _spawn_child(quick)
    out.update(_overhead_bench(quick))
    print(f"fault.full_restore,{out['full_restore_s'] * 1e6:.0f},"
          f"{out['state_mb']:.0f}MB")
    print(f"fault.elastic_restore,{out['elastic_restore_s'] * 1e6:.0f},"
          f"speedup={out['restore_speedup']:.2f}x "
          f"shape={out['new_shape']} f={out['merge_factor']}")
    print(f"fault.preset_overhead,{out['fault_step_s'] * 1e6:.0f},"
          f"overhead={out['overhead_frac'] * 100:.2f}%")
    if not quick:
        assert out["overhead_frac"] < 0.02, (
            f"fault preset costs {out['overhead_frac'] * 100:.2f}% of step "
            "time (gate: < 2%)")
    return out


if __name__ == "__main__":
    if os.environ.get(_CHILD_ENV) == "1":
        print(json.dumps(_child_restore_bench("--quick" in sys.argv)))
    else:
        run(quick="--quick" in sys.argv)
