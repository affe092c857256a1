"""Program spans: each shows in a ``jax.profiler`` trace on its own thread,
each phase of the training loop and of the checkpoint sink has one, and no
new name is counted by a prefix that ``step_overlap_report`` sums."""
import glob
import threading

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.checkpoint import CheckpointConfig, CheckpointManager
from repro.core import InSituPlan, Session
from repro.core.runtime import (PipelineRuntime, PipelineTask, Placement,
                                TransientError)
from repro.core.telemetry import Telemetry
from repro.launch.train import default_train_plan, train_loop

# -- spans on the profiler's clock ---------------------------------------------


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """Spans opened on the main thread and on a worker while a trace is
    captured; returns the telemetry and, per span name, the (plane, line)
    of every trace event of that name."""
    out = tmp_path_factory.mktemp("trace")
    tm = Telemetry()

    def worker():
        for step in range(2):
            with tm.span("t/worker", step=step):
                pass

    jax.profiler.start_trace(str(out))
    try:
        with tm.span("t/main", step=0):
            th = threading.Thread(target=worker, name="insitu-t")
            th.start()
            th.join(timeout=30)
            assert not th.is_alive()
        with tm.span("t/main", step=1):
            pass
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(out / "plugins/profile/*/*.xplane.pb")))[-1]
    where: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("t/"):
                    where.setdefault(ev.name, []).append((plane.name, k))
    return tm, where


@pytest.mark.parametrize("who,thread", [("main", "MainThread"),
                                        ("worker", "insitu-t")])
def test_span_shows_in_profiler_trace_on_its_thread(profiled, who, thread):
    tm, where = profiled
    name, other = f"t/{who}", "t/worker" if who == "main" else "t/main"
    assert [s.thread for s in tm.spans(name)] == [thread, thread]
    assert len(where[name]) == 2                 # one event per span
    assert len(set(where[name])) == 1            # both on one thread's line
    assert not set(where[name]) & set(where[other])
    assert not where[name][0][0].startswith("/device:")


# -- the training loop's phases ------------------------------------------------


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    ckpt = tmp_path_factory.mktemp("ckpt")
    plan = default_train_plan(insitu_mode="hybrid", ckpt_dir=str(ckpt),
                              ckpt_every=2, analytics_every=2)
    out = train_loop("smollm-135m", steps=3, smoke=True, plan=plan,
                     log=lambda *_: None)
    return out["telemetry"]


def _loop_spans(tm, name):
    return [s for s in tm.spans(name) if s.thread == "MainThread"]


def test_every_step_batches_dispatches_then_syncs(trained):
    computes = _loop_spans(trained, "step/compute")
    assert [s.step for s in computes] == [0, 1, 2]
    for c in computes:
        batch, dispatch, sync = (
            [s for s in _loop_spans(trained, name) if s.step == c.step]
            for name in ("data/batch", "step/dispatch", "step/sync"))
        assert len(batch) == len(dispatch) == len(sync) == 1
        batch, dispatch, sync = batch[0], dispatch[0], sync[0]
        assert batch.t1 <= c.t0 <= dispatch.t0
        assert dispatch.t1 <= sync.t0 and sync.t1 <= c.t1


def test_wait_idle_follows_the_last_step(trained):
    last = _loop_spans(trained, "step/compute")[-1]
    waits = _loop_spans(trained, "insitu/wait_idle")
    assert waits and waits[-1].t0 >= last.t1
    drains = _loop_spans(trained, "insitu/drain")
    assert drains and drains[-1].t0 >= waits[-1].t1


@pytest.mark.parametrize("task", ["analytics", "checkpoint"])
def test_each_firing_provides_on_the_loop(trained, task):
    provides = trained.spans(f"insitu-provide/{task}")
    assert [s.step for s in provides] == [0, 2]
    assert all(s.thread == "MainThread" for s in provides)


def test_each_save_writes_under_a_sink_span(trained):
    sinks = trained.spans("sink/checkpoint")
    assert [s.step for s in sinks] == [0, 2]
    encodes = {s.step: s for s in trained.spans("insitu-async/checkpoint")}
    for s in sinks:
        assert s.thread.startswith("insitu-")
        assert encodes[s.step].t0 <= s.t0 and s.t1 <= encodes[s.step].t1


# -- the sink, one span per attempt --------------------------------------------


@pytest.mark.parametrize("placement", [Placement.SYNC, Placement.ASYNC])
@pytest.mark.parametrize("fails,attempts", [(1, 2), (5, 3)])
def test_sink_retry_is_a_second_span_of_one_firing(placement, fails,
                                                   attempts):
    rt = PipelineRuntime(workers=1, staging_capacity=2)
    rt.register(PipelineTask(name="t", source="s", sink=lambda step, p: p,
                             placement=placement, retries=2,
                             retry_backoff_s=0.0))
    left = [fails]

    def fault(step):
        if left[0]:
            left[0] -= 1
            raise TransientError("flaky IO")

    rt.inject_sink_fault("t", fault)
    rt.submit(7, {"s": lambda: np.ones(4)})
    rt.wait_idle()
    rt.drain()
    sinks = rt.telemetry.spans("sink/t")
    assert [s.step for s in sinks] == [7] * attempts
    assert all(a.t1 <= b.t0 for a, b in zip(sinks, sinks[1:]))
    assert len({s.thread for s in sinks}) == 1
    assert (sinks[0].thread == "MainThread") == (placement is Placement.SYNC)
    assert ("t" in rt.degraded) == (attempts == 3)


# -- every caller of wait_idle gets the span ------------------------------------


def _wait_via_runtime(tmp_path):
    rt = PipelineRuntime(workers=1)
    rt.wait_idle()
    return rt.telemetry


def _wait_via_manager(tmp_path):
    mgr = CheckpointManager(CheckpointConfig(str(tmp_path), every=1))
    mgr.wait_idle()
    mgr.finish()
    return mgr.telemetry


def _wait_via_session_finish(tmp_path):
    sess = Session(InSituPlan.from_dict({"streams": ["x"], "tasks": {
        "t": {"stream": "x", "preset": "spectra", "placement": "async"}}}))
    sess.emit("x", 0, np.ones(4, np.float32))
    sess.finish()
    return sess.telemetry


@pytest.mark.parametrize("wait", [_wait_via_runtime, _wait_via_manager,
                                  _wait_via_session_finish])
def test_wait_idle_is_spanned_for_every_caller(wait, tmp_path):
    spans = wait(tmp_path).spans("insitu/wait_idle")
    assert [s.thread for s in spans] == ["MainThread"]


# -- names stay out of the aggregated prefixes ----------------------------------


@pytest.mark.parametrize("name", [
    "data/batch", "step/dispatch", "step/sync", "insitu-provide/t",
    "insitu/wait_idle", "sink/t"])
def test_new_span_is_not_counted_by_the_overlap_report(name):
    tm = Telemetry()
    with tm.span(name, step=0):
        pass
    assert len(tm.spans(name)) == 1
    assert all(v == 0.0 for v in tm.step_overlap_report().values())
