"""Fault tolerance: heartbeats, stragglers, remesh planning, the fault
preset, sink retry/degrade, the time-budget Adaptive trigger, and the
mesh-level kill-point (subprocess, multi-device XLA platform)."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from _hyp import given, settings, st
from repro.core.runtime import (PipelineRuntime, PipelineTask, Placement,
                                TransientError)
from repro.core.session import (Adaptive, InSituPlan, InSituTaskError,
                                PlanError, Session, TaskSpec)
from repro.distributed.fault import (ElasticRestore, FaultController,
                                     HeartbeatTracker, StragglerMonitor,
                                     merge_model_shards, plan_elastic_remesh)

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


# ---------------------------------------------------------------------------
# HeartbeatTracker on a fake clock
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def test_heartbeat_seeds_last_seen_from_injected_clock():
    # regression: seeding from time.monotonic() while driving with a
    # near-zero injected clock declared every host dead at t=0
    clk = FakeClock(5.0)
    hb = HeartbeatTracker([0, 1], grace_s=2.0, clock=clk)
    assert hb.failed_hosts() == []
    assert hb.alive_hosts() == [0, 1]


def test_heartbeat_grace_transitions_on_fake_clock():
    clk = FakeClock(0.0)
    hb = HeartbeatTracker([0, 1, 2], grace_s=3.0, clock=clk)
    clk.t = 2.0
    hb.beat(1)
    hb.beat(2)
    clk.t = 4.0          # host 0 last seen at 0 -> 4s silent > 3s grace
    assert hb.failed_hosts() == [0]
    assert hb.alive_hosts() == [1, 2]
    clk.t = 6.0          # hosts 1/2 now 4s silent too
    assert hb.failed_hosts() == [0, 1, 2]
    hb.beat(0)           # a failed host that beats again is alive
    assert hb.failed_hosts() == [1, 2]


def test_heartbeat_explicit_now_still_wins():
    hb = HeartbeatTracker([0], grace_s=1.0, clock=FakeClock(0.0))
    hb.beat(0, now=10.0)
    assert hb.failed_hosts(now=10.5) == []
    assert hb.failed_hosts(now=12.0) == [0]


# ---------------------------------------------------------------------------
# StragglerMonitor thresholds and EWMA
# ---------------------------------------------------------------------------

def test_straggler_ewma_converges():
    mon = StragglerMonitor(alpha=0.5)
    mon.observe(0, 1.0)
    assert mon.ewma[0] == 1.0           # first sample seeds the EWMA
    mon.observe(0, 2.0)
    assert mon.ewma[0] == pytest.approx(1.5)
    mon.observe(0, 2.0)
    assert mon.ewma[0] == pytest.approx(1.75)


def test_straggler_flags_and_mitigation_tiers():
    mon = StragglerMonitor(alpha=1.0, factor=1.5)
    for h in range(4):
        mon.observe(h, 1.0)
    assert mon.stragglers() == []
    assert mon.mitigation(0) == "none"
    mon.observe(3, 2.0)                 # 2.0 > 1.5 x median(1.0)
    assert mon.stragglers() == [3]
    assert mon.mitigation(3) == "reduce_insitu_pi"
    mon.observe(3, 4.0)                 # 4.0 > 2 x 1.5 x median
    assert mon.mitigation(3) == "replace_at_checkpoint"
    assert mon.mitigation(0) == "none"


# ---------------------------------------------------------------------------
# plan_elastic_remesh: examples + properties
# ---------------------------------------------------------------------------

def test_remesh_prefers_smallest_merge_factor_on_ties():
    # 2 survivors of a (2, 2): (1, 2) with f=1 and (2, 1) with f=2 both
    # keep 2 devices; the deterministic tie-break picks f=1
    plan = plan_elastic_remesh((2, 2), ("data", "model"), 2)
    assert plan.new_shape == (1, 2)
    assert plan.model_merge_factor == 1


def test_remesh_non_power_of_two_model_axis():
    # model=6 can shrink to 3 now (divisor 2 was impossible with the
    # hardcoded [1, 2, 4, 8, 16] list): 9 survivors -> (3, 3), not (1, 6)
    plan = plan_elastic_remesh((4, 6), ("data", "model"), 9)
    assert plan.new_shape == (3, 3)
    assert plan.model_merge_factor == 2
    assert plan.new_device_count == 9


def test_remesh_pod_axis_shrinks_by_whole_pods():
    plan = plan_elastic_remesh((2, 16, 16), ("pod", "data", "model"), 300)
    pod, data, model = plan.new_shape
    assert pod in (1, 2)
    assert pod * data * model <= 300


def test_remesh_raises_when_nothing_fits():
    with pytest.raises(ValueError):
        plan_elastic_remesh((2, 2), ("data", "model"), 0)


def test_remesh_shard_sources():
    plan = plan_elastic_remesh((4, 8), ("data", "model"), 8)
    # 8 survivors: (1, 8) f=1 beats (2, 4) f=2 on the tie-break? No —
    # (2, 4) has 8 devices too; smallest f wins at equal count: f=1
    assert plan.model_merge_factor == 1
    assert list(plan.shard_sources(3)) == [3]
    plan2 = plan_elastic_remesh((1, 8), ("data", "model"), 2)
    assert plan2.new_shape == (1, 2)
    assert plan2.model_merge_factor == 4
    assert list(plan2.shard_sources(1)) == [4, 5, 6, 7]


@settings(max_examples=80, deadline=None)
@given(data=st.integers(1, 32), model=st.integers(1, 32),
       survivors=st.integers(1, 1024))
def test_remesh_properties_2d(data, model, survivors):
    try:
        plan = plan_elastic_remesh((data, model), ("data", "model"),
                                   survivors)
    except ValueError:
        # nothing fits only when even (1, 1) doesn't
        assert survivors < 1
        return
    d, m = plan.new_shape
    assert d * m <= survivors                 # never exceeds the survivors
    assert model % m == 0                     # model divides the old axis
    assert plan.model_merge_factor == model // m


@settings(max_examples=60, deadline=None)
@given(pod=st.integers(1, 4), data=st.integers(1, 16),
       model=st.integers(1, 16), survivors=st.integers(1, 512))
def test_remesh_properties_3d(pod, data, model, survivors):
    try:
        plan = plan_elastic_remesh((pod, data, model),
                                   ("pod", "data", "model"), survivors)
    except ValueError:
        assert survivors < 1
        return
    p, d, m = plan.new_shape
    assert p * d * m <= survivors
    assert 1 <= p <= pod                      # whole pods only
    assert model % m == 0


def test_merge_model_shards():
    shards = [np.full((2, 3), i, np.float32) for i in range(4)]
    merged = merge_model_shards(shards, 2, axis=0)
    assert len(merged) == 2
    assert merged[0].shape == (4, 3)
    np.testing.assert_array_equal(merged[1][:2], shards[2])
    np.testing.assert_array_equal(merged[1][2:], shards[3])
    with pytest.raises(ValueError):
        merge_model_shards(shards, 3)
    with pytest.raises(ValueError):
        merge_model_shards(shards, 0)


# ---------------------------------------------------------------------------
# FaultController: ingest, escalation, shedding
# ---------------------------------------------------------------------------

def test_controller_ingest_detects_failed_host():
    clk = FakeClock(0.0)
    ctrl = FaultController([0, 1], grace_s=2.0, clock=clk)
    for i in range(5):
        clk.t = float(i)
        beats = {0: 0.1} if i >= 2 else {0: 0.1, 1: 0.1}
        out = ctrl.ingest(i, {"hosts": beats})
    assert out["failed_hosts"] == [1]
    assert ctrl.report()["alive_hosts"] == [0]


def test_controller_payload_forms():
    ctrl = FaultController([0, 1], clock=FakeClock(0.0))
    ctrl.ingest(0, {"host": 0, "step_s": 0.5})
    ctrl.ingest(0, {"hosts": {1: 0.25}})
    ctrl.ingest(0, {0: 0.5, 1: 0.25})
    assert set(ctrl.monitor.ewma) == {0, 1}
    with pytest.raises(ValueError):
        ctrl.ingest(0, "not a mapping")


def test_controller_escalates_once_per_transition():
    ctrl = FaultController([0, 1, 2], alpha=1.0, factor=1.5,
                           clock=FakeClock(0.0))
    base = {0: 1.0, 1: 1.0}
    ctrl.ingest(0, {"hosts": {**base, 2: 1.0}})
    assert ctrl.shed_events == 0
    ctrl.ingest(1, {"hosts": {**base, 2: 2.0}})      # -> reduce_insitu_pi
    assert ctrl.shed_events == 1
    assert ctrl.mitigations[2] == "reduce_insitu_pi"
    ctrl.ingest(2, {"hosts": {**base, 2: 2.0}})      # same tier: no re-shed
    assert ctrl.shed_events == 1
    ctrl.ingest(3, {"hosts": {**base, 2: 8.0}})      # -> replace
    assert ctrl.shed_events == 2
    assert ctrl.replace_candidates == {2}
    ctrl.ingest(4, {"hosts": {**base, 2: 1.0}})      # recovers
    assert 2 not in ctrl.mitigations


# ---------------------------------------------------------------------------
# The fault preset wired into a Session
# ---------------------------------------------------------------------------

def _fault_plan(extra_tasks=None, **opts):
    options = {"hosts": [0, 1], "grace_s": 2.0, "alpha": 1.0,
               "factor": 1.5, **opts}
    tasks = {"fault": {"stream": "health", "preset": "fault", "every": 1,
                       "placement": "sync", "pipelined": False,
                       "options": options}}
    tasks.update(extra_tasks or {})
    streams = sorted({t["stream"] for t in tasks.values()})
    return {"streams": streams, "workers": 2, "tasks": tasks}


def test_fault_preset_validates_options():
    with pytest.raises(PlanError, match="hosts"):
        Session(_fault_plan(hosts=[]))
    with pytest.raises(PlanError, match="unknown fault option"):
        Session(_fault_plan(bogus=1))


def test_fault_preset_heartbeats_on_session_clock():
    clk = FakeClock(0.0)
    with Session(_fault_plan(), clock=lambda: clk.t) as s:
        ctrl = s.fault_controller()
        for i in range(6):
            clk.t = float(i)
            beats = {0: 0.1} if i >= 2 else {0: 0.1, 1: 0.1}
            s.emit("health", i, {"hosts": beats})
        assert ctrl.failed_hosts() == [1]
    rep = s.report()
    assert rep["fault"]["failed_hosts"] == [1]
    assert rep["fault"]["alive_hosts"] == [0]
    assert 0 in rep["fault"]["straggler_ewma"]


def test_fault_preset_sheds_insitu_load_before_replacing():
    # a straggler first widens the other tasks' cadence (never its own,
    # never the checkpoint's), then joins the replace candidates
    extra = {"analytics": {"stream": "x", "preset": "spectra",
                           "every": 2, "placement": "sync",
                           "pipelined": False}}
    plan = _fault_plan(extra_tasks=extra, hosts=[0, 1, 2])
    clk = FakeClock(0.0)
    with Session(plan, clock=lambda: clk.t) as s:
        ctrl = s.fault_controller("fault")
        assert s.runtime.effective_every("analytics") == 2
        s.emit("health", 0, {"hosts": {0: 1.0, 1: 1.0, 2: 1.0}})
        s.emit("health", 1, {"hosts": {0: 1.0, 1: 1.0, 2: 2.0}})  # 2 lags
        assert ctrl.shed_events == 1
        assert s.runtime.effective_every("analytics") == 4   # widened
        assert s.runtime.effective_every("fault") == 1       # not itself
        assert ctrl.widened == {"analytics": 4}
        s.emit("health", 2, {"hosts": {0: 1.0, 1: 1.0, 2: 9.0}})  # escalates
        assert ctrl.report()["replace_at_checkpoint"] == [2]
    rep = s.report()
    assert rep["fault"]["shed_events"] == 2
    assert rep["fault"]["mitigations"] == {2: "replace_at_checkpoint"}


def test_fault_controller_lookup_errors():
    with Session(_fault_plan()) as s:
        with pytest.raises(PlanError):
            s.fault_controller("nope")
    plan = InSituPlan.from_dict({"streams": ["x"], "tasks": {
        "t": {"stream": "x", "preset": "spectra"}}})
    with Session(plan) as s:
        with pytest.raises(PlanError):
            s.fault_controller()


# ---------------------------------------------------------------------------
# Sink retry / degrade on the runtime
# ---------------------------------------------------------------------------

def _runtime_with_task(**kw):
    rt = PipelineRuntime(workers=2, staging_capacity=4)
    calls = []

    def sink(step, payload):
        calls.append(step)
        return step

    task = PipelineTask(name="t", source="s", sink=sink,
                        placement=Placement.SYNC, pipelined=False,
                        retry_backoff_s=0.0, **kw)
    rt.register(task)
    return rt, calls


def test_transient_sink_failure_retries_then_succeeds():
    rt, calls = _runtime_with_task(retries=3)
    fails = [2]

    def fault(step):
        if fails[0] > 0:
            fails[0] -= 1
            raise TransientError("flaky IO")

    rt.inject_sink_fault("t", fault)
    rt.submit(0, {"s": lambda: 1})
    rt.drain()
    assert calls == [0]                      # the sink ultimately ran
    assert rt.retry_counts["t"] == 2
    assert rt.degraded == {}
    assert rt.errors == []


def test_exhausted_retries_degrade_and_drop_instead_of_raising():
    rt, calls = _runtime_with_task(retries=2)
    rt.inject_sink_fault("t", lambda step: (_ for _ in ()).throw(
        TransientError("dead disk")))
    for step in range(4):
        rt.submit(step, {"s": lambda: step})
    rt.drain()
    assert calls == []
    assert rt.errors == []                   # degraded, never raised
    deg = rt.degraded["t"]
    assert deg["step"] == 0 and deg["dropped"] == 3
    assert rt.retry_counts["t"] == 2
    rep = rt.report()
    assert rep["degraded"]["t"]["dropped"] == 3
    assert rep["retries"]["t"] == 2


def test_clearing_fault_hook_does_not_resurrect_degraded_task():
    rt, calls = _runtime_with_task(retries=0)
    rt.inject_sink_fault("t", lambda step: (_ for _ in ()).throw(
        TransientError("boom")))
    rt.submit(0, {"s": lambda: 0})
    rt.inject_sink_fault("t", None)          # IO recovers...
    rt.submit(1, {"s": lambda: 1})           # ...but the task stays degraded
    rt.drain()
    assert calls == []
    assert rt.degraded["t"]["dropped"] == 1


def test_permanent_sink_failure_still_raises_through_finish():
    # only TransientError degrades; a permanent failure keeps the existing
    # captured-error path and surfaces with stream/task/step context
    plan = InSituPlan.from_dict({"streams": ["x"], "tasks": {
        "t": {"stream": "x", "preset": "spectra", "placement": "async",
              "retries": 5}}})
    s = Session(plan)
    s.runtime.inject_sink_fault(
        "t", lambda step: (_ for _ in ()).throw(RuntimeError("perm")))
    s.emit("x", 3, np.ones(4, np.float32))
    with pytest.raises(InSituTaskError, match=r"'t'.*'x'.*step 3"):
        s.finish(raise_on_error=True)
    assert s.runtime.degraded == {}          # degraded is for transients


def test_transient_error_from_sink_itself_degrades():
    rt = PipelineRuntime(workers=1, staging_capacity=2)
    rt.register(PipelineTask(
        name="t", source="s", placement=Placement.SYNC, pipelined=False,
        retries=1, retry_backoff_s=0.0,
        sink=lambda step, p: (_ for _ in ()).throw(
            TransientError("sink-side"))))
    rt.submit(0, {"s": lambda: 0})
    rt.drain()
    assert rt.errors == []
    assert rt.degraded["t"]["retries"] == 1


def test_retry_backoff_is_capped_exponential():
    rt = PipelineRuntime(workers=1, staging_capacity=2)
    sleeps = []
    rt._sleep = sleeps.append
    rt.register(PipelineTask(
        name="t", source="s", placement=Placement.SYNC, pipelined=False,
        retries=6, retry_backoff_s=0.5,
        sink=lambda step, p: (_ for _ in ()).throw(TransientError("x"))))
    rt.submit(0, {"s": lambda: 0})
    rt.drain()
    assert sleeps == [0.5, 1.0, 2.0, 2.0, 2.0, 2.0]   # capped at 2s


def test_plan_validates_retry_fields():
    base = {"streams": ["x"], "tasks": {
        "t": {"stream": "x", "preset": "spectra", "retries": -1}}}
    with pytest.raises(PlanError, match="retries"):
        InSituPlan.from_dict(base)
    base["tasks"]["t"] = {"stream": "x", "preset": "spectra",
                          "retry_backoff_s": -0.1}
    with pytest.raises(PlanError, match="retry_backoff_s"):
        InSituPlan.from_dict(base)


# ---------------------------------------------------------------------------
# Time-budget Adaptive trigger
# ---------------------------------------------------------------------------

def test_adaptive_budget_round_trips_through_dict():
    trig = Adaptive(2, max_every=16, after=3, budget_s=0.25)
    d = trig.to_dict()["trigger"]
    assert d["budget_s"] == 0.25
    plan = InSituPlan.from_dict({"streams": ["x"], "tasks": {
        "t": {"stream": "x", "preset": "spectra", "trigger": d}}})
    assert plan.tasks[0].trigger == trig
    assert plan.to_dict()["tasks"]["t"]["trigger"]["budget_s"] == 0.25


def test_adaptive_budget_validation():
    with pytest.raises(PlanError, match="budget_s"):
        InSituPlan.from_dict({"streams": ["x"], "tasks": {
            "t": {"stream": "x", "preset": "spectra",
                  "trigger": {"kind": "adaptive", "n": 1,
                              "budget_s": 0}}}})


def test_budget_widen_after_consecutive_over_budget_firings():
    plan = InSituPlan(streams=["x"], tasks=[
        TaskSpec(name="slow", stream="x",
                 trigger=Adaptive(1, max_every=8, after=2, budget_s=0.005),
                 placement=Placement.SYNC, pipelined=False,
                 sink=lambda step, p: time.sleep(0.02))])
    with Session(plan) as s:
        for i in range(3):
            s.emit("x", i, {"v": 1})
        # two consecutive over-budget sync firings -> period doubles
        assert s.runtime.effective_every("slow") == 2
        assert s.runtime.telemetry.counters()["budget/adapt/slow"] >= 1
    assert s.report()["effective_every"]["slow"] >= 2


def test_budget_under_budget_firings_reset_the_streak():
    rt = PipelineRuntime(workers=1, staging_capacity=2)
    rt.register(PipelineTask(
        name="t", source="s", placement=Placement.SYNC, pipelined=False,
        budget_s=10.0, adapt_after=2,
        sink=lambda step, p: p))
    for i in range(8):
        rt.submit(i, {"s": lambda: 1})
    rt.drain()
    assert rt.effective_every("t") == 1      # never over budget


def test_widen_every_caps():
    rt = PipelineRuntime(workers=1, staging_capacity=2)
    rt.register(PipelineTask(name="t", source="s", sink=lambda s_, p: p,
                             adapt_max_every=4))
    assert rt.widen_every("t") is True       # 1 -> 2
    assert rt.widen_every("t") is True       # 2 -> 4
    assert rt.widen_every("t") is False      # capped
    assert rt.effective_every("t") == 4
    rt.drain()


# ---------------------------------------------------------------------------
# Elastic restore plumbing (single-device; the multi-device path is the
# subprocess kill-point below)
# ---------------------------------------------------------------------------

def test_elastic_restore_requires_mesh_meta(tmp_path):
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    plan = {"streams": ["state"], "tasks": {
        "checkpoint": {"stream": "state", "preset": "checkpoint",
                       "every": 1, "placement": "sync",
                       "options": {"directory": str(tmp_path)}}}}
    state = {"w": jnp.arange(8, dtype=jnp.float32)}
    with Session(plan) as s:
        s.emit("state", 0, state)
    with Session(plan) as s:
        with pytest.raises(PlanError, match="mesh geometry"):
            s.restore(state, elastic=True, devices=jax.devices())


def test_elastic_restore_single_device_roundtrip(tmp_path):
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    plan = {"streams": ["state"], "tasks": {
        "checkpoint": {"stream": "state", "preset": "checkpoint",
                       "every": 1, "placement": "sync",
                       "options": {"directory": str(tmp_path)}}}}
    mesh = make_mesh((1, 1), ("data", "model"))
    state = {"w": jnp.arange(8, dtype=jnp.float32)}
    with Session(plan) as s:
        s.set_checkpoint_meta(mesh=mesh)
        s.emit("state", 2, state)
    # the manifest carries the mesh geometry the elastic path plans from
    mgr_meta = s.checkpoint.read_meta()
    assert mgr_meta["mesh"] == {"shape": [1, 1], "axes": ["data", "model"]}
    with Session(plan) as s:
        step, restored = s.restore(state, elastic=True,
                                   devices=jax.devices()[:1])
        assert step == 2
        np.testing.assert_allclose(np.asarray(restored["w"]),
                                   np.arange(8, dtype=np.float32))
        rm = s.remesh
        assert isinstance(rm, ElasticRestore)
        assert rm.step == 2
        assert rm.plan.new_shape == (1, 1)
        assert tuple(rm.mesh.axis_names) == ("data", "model")
    assert s.remesh is rm


# ---------------------------------------------------------------------------
# The headline: mesh-level kill-point (subprocess, 8 fake devices)
# ---------------------------------------------------------------------------

def test_killpoint_host_drop_resumes_on_remeshed_grid(tmp_path):
    """Drop a host mid-run; the run continues on the remeshed grid and
    final losses match the golden non-failed run within lossy bounds."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(TESTS_DIR), "src")
    proc = subprocess.run(
        [sys.executable, os.path.join(TESTS_DIR, "killpoint_driver.py"),
         "--steps", "9", "--fail-at", "4", "--ckpt-every", "2",
         "--ckpt-dir", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])

    assert out["failed_hosts"] == [1]
    assert out["fault_report"]["alive_hosts"] == [0]
    assert out["detect_step"] >= 4           # after the grace window
    # remesh: 2 surviving devices, model axis kept (f=1 beats f=2 on ties)
    assert out["new_shape"] == [1, 2]
    assert out["merge_factor"] == 1
    assert out["restored_step"] <= out["detect_step"]

    golden = out["golden_losses"]
    resumed = {int(k): v for k, v in out["resumed_losses"].items()}
    assert resumed, "no resumed steps"
    assert max(resumed) == len(golden) - 1   # ran to completion
    for i, loss in resumed.items():
        # lossy bound: checkpointed moments are spectral-compressed, so
        # the resumed trajectory drifts slightly from golden
        assert abs(loss - golden[i]) <= max(0.05, 0.02 * abs(golden[i])), (
            i, loss, golden[i])
