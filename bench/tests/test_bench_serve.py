"""The open-loop serving generator on the CPU at the registry's reduced
qwen3-4b sizes: arrivals, the latency statistics, and ``correct`` (a sound
run passes with and without snapshots; a decode that keeps its old KV pool,
or a token altered where the engine produces it, fails; the float8 control
reads a wider gap than the limit). No serving cell is in BENCHMARK.json
yet (see PERF.md), so the cells here are built from files directly."""
import json
import time
from pathlib import Path

import numpy as np
import pytest

import harness

BENCH = Path(__file__).resolve().parents[1]
SEED = 2**31 + 7
SNAPSHOTS = {"streams": ["kv_pages"], "workers": 2, "tasks": {"kv_snapshot": {
    "stream": "kv_pages", "preset": "serve_snapshot", "every": 4,
    "placement": "async", "backpressure": "drop",
    "options": {"base_every": 8, "codec": "zlib"}}}}
# At this reduced size a sound run reads a widest logit gap of ~3e-4, the
# float8 control ~0.07-0.09, a planted fault 0.5 or more (CPU readings).
TRAFFIC = {"kind": "open_loop", "rate_per_s": 3.0, "lead_in_s": 1.0,
           "shape_seed": 13, "check_requests": 4,
           "limits": {"logit_gap": 0.01},
           "engine": {"prompt_len": 64, "max_len": 128, "num_pages": 64,
                      "max_reqs": 4},
           "prompt_len": {"median": 16, "sigma": 0.8, "min": 4, "max": 64},
           "output_len": {"median": 8, "sigma": 0.8, "min": 2, "max": 64}}
PLANS = {"snap": SNAPSHOTS, "nosnap": {"streams": ["kv_pages"], "tasks": {}}}


def _cell(kind):
    e2e = [{"name": n, "unit": u} for n, u in
           (("ttft_p90_ms", "ms"), ("itl_p95_ms", "ms"), ("setup_s", "s"))]
    if kind == "snap":
        e2e.append({"name": "state_age_p95_s", "unit": "s"})
    return harness.Cell(
        name=f"qwen3-4b.serve.{kind}", chips=1, config_name="qwen3-4b",
        config=json.loads((BENCH / "configs" / "qwen3-4b.json").read_text()),
        traffic_name=kind, traffic=dict(TRAFFIC, plan=PLANS[kind]),
        end_to_end=e2e, per_layer=[])


def _run(cell, tmp_path, control=False):
    return harness.run(cell.name, SEED, 3, False, time.perf_counter(),
                       cell=cell, smoke=True, require_chip=False,
                       workdir=tmp_path / "run", control=control)


def test_arrivals_same_work_for_every_seed():
    drv = harness.load_generator("open_loop")
    tr = dict(TRAFFIC, rate_per_s=4.0, lead_in_s=8.0,
              prompt_len={"median": 256, "sigma": 0.8, "min": 32, "max": 1024},
              output_len={"median": 128, "sigma": 0.8, "min": 16, "max": 512})
    a = drv.draw_arrivals(tr, 1, 10, 257)
    b = drv.draw_arrivals(tr, 2**31 + 3, 10, 257)
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
    assert a[-1].due == pytest.approx(b[-1].due)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    again = drv.draw_arrivals(tr, 1, 10, 257)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, again))


def test_latency_counts_every_request_due_in_the_window():
    drv = harness.load_generator("open_loop")
    mk = lambda due, times: drv.Served(drv.Arrival(due, np.zeros(4, np.int32), 4),
                                       None, list(times))
    served = [mk(0.5, [0.6, 0.7, 0.8]),     # due before the window
              mk(1.5, [1.7, 1.8, 2.4]),     # due in it
              mk(2.5, [3.5])]               # first token after the close
    lat = drv.latency(served, [mk(2.9, [])], 0.0, 1.0, 3.0)
    assert lat["ttft"] == pytest.approx([0.2, 0.5, 0.1])
    assert lat["itl"] == pytest.approx([0.1, 0.6])


@pytest.mark.parametrize("kind", ["snap", "nosnap"])
def test_sound_run_is_correct(kind, tmp_path):
    out = _run(_cell(kind), tmp_path)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {e["name"] for e in _cell(kind).end_to_end}
    if kind == "snap":
        assert out["checks"]["snapshot_mismatch"]["value"] == 0


def test_control_is_not_correct(tmp_path):
    out = _run(_cell("nosnap"), tmp_path, control=True)
    assert out["control"] > out["checks"]["logit_gap"]["limit"], out


@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered"])
def test_broken_decode_is_not_correct(fault, tmp_path, monkeypatch):
    from repro.serving import pages
    orig = pages.PagedServingEngine.step

    def step(self):
        pool = self.pool
        orig(self)
        if fault == "state_unchanged":
            self.pool = pool
        else:
            for r in self.active:
                if r is not None and len(r.out) == 3:
                    r.out[-1] = (r.out[-1] + 1) % self.cfg.vocab_size
    monkeypatch.setattr(pages.PagedServingEngine, "step", step)
    out = _run(_cell("nosnap"), tmp_path)
    assert not out["correct"]
    c = out["checks"]["logit_gap"]
    assert c["value"] > c["limit"], out["checks"]
