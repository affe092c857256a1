"""Load one benchmark cell by name and run it.

Everything a cell is made of is found by name, so that a new configuration,
traffic mix, cell or per-layer metric is a new file and never an edit:

- ``BENCHMARK.json`` (next to this directory) lists the cells and metrics;
- ``bench/configs/<config>.json`` holds a configuration's sizes;
- ``bench/traffic/<traffic>.json`` holds a traffic mix or training job; its
  ``kind`` names the generator, ``bench/generators/<kind>.py``;
- ``bench/metrics/<metric>.py`` holds a per-layer metric's reader, with its
  ``UNIT``, ``LAYER``, ``MOVES`` and the op or module names it matches.

A generator's ``run(ctx)`` warms up, measures one window and checks what the
window produced; this module turns its record into the result line.
"""
from __future__ import annotations

import importlib.util
import json
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Any, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(RuntimeError):
    """The run cannot give a result (no chip, a bad cell, a compile in the
    window): the command exits non-zero and prints no result line."""


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list            # BENCHMARK.json entries reported with --trace 0
    per_layer: list             # (entry, reader module) reported with --trace 1


@dataclass
class RunContext:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float              # perf_counter at process start: set-up's zero
    workdir: Path               # run-scoped scratch inside the checkout
    peaks: Any                  # work.ChipPeaks of the device
    smoke: bool = False         # tests: the registry's reduced configuration
    compiles: "CompileLog" = None
    control: bool = False       # control runs: also read the control


class CompileLog:
    """Every XLA compilation (or persistent-cache load) of the process, as
    (end perf_counter, seconds, function name)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax.monitoring
        self.events: list[tuple[float, float, str]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.events.append((time.perf_counter(), float(duration),
                                str(kw.get("fun_name", "?"))))

    def inside(self, t0: float, t1: float) -> list[str]:
        return [name for end, dur, name in self.events
                if end > t0 and end - dur < t1]


# ---------------------------------------------------------------------------
# loading by name
# ---------------------------------------------------------------------------

def load_module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise BenchError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod          # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchError(f"missing file {path}") from None


def _covers(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json "
                         f"(known: {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    per_layer = []
    for entry in bench["per_layer"]:
        if not _covers(entry, workload):
            continue
        mod = load_module(root / "bench" / "metrics" / f"{entry['name']}.py",
                          f"bench_metric_{entry['name'].replace('.', '_')}")
        for key, attr in (("unit", "UNIT"), ("layer", "LAYER"),
                          ("moves", "MOVES")):
            if getattr(mod, attr, None) != entry[key]:
                raise BenchError(
                    f"metric {entry['name']}: {attr} of its reader "
                    f"{getattr(mod, attr, None)!r} != BENCHMARK.json "
                    f"{key} {entry[key]!r}")
        per_layer.append((entry, mod))
    return Cell(
        name=workload, chips=int(w["chips"]),
        config_name=w["config"], config=_read_json(root / cfg_entry["file"]),
        traffic_name=w["traffic"],
        traffic=_read_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[e for e in bench["end_to_end"] if _covers(e, workload)],
        per_layer=per_layer)


def load_generator(kind: str, root: Path = ROOT) -> ModuleType:
    path = root / "bench" / "generators" / f"{kind}.py"
    if not path.exists():
        raise BenchError(f"no generator for traffic kind {kind!r} ({path})")
    return load_module(path, f"bench_generator_{kind}")


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def check_device(chips: int) -> dict:
    """The device as JAX reports it; fails off the TPU or short of chips."""
    import jax
    import work
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU found: JAX sees {len(devs)} "
                         f"{devs[0].platform} device(s)")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    try:
        work.peaks(devs[0].device_kind)
    except KeyError as e:
        raise BenchError(str(e)) from None
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak_bytes(chips: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(workload: str, seed: int, seconds: float, trace: bool, t_start: float,
        *, root: Path = ROOT, require_chip: bool = True,
        cell: Optional[Cell] = None, smoke: bool = False,
        workdir: Optional[Path] = None, control: bool = False) -> dict:
    """Run one cell and return its result line (a dict). Run-scoped files
    go to ``workdir`` (default ``<root>/.bench_run/<workload>``), removed
    at the end."""
    import work
    cell = cell or load_cell(workload, root)
    if require_chip:
        device = check_device(cell.chips)
    else:
        import jax
        d0 = jax.devices()[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": cell.chips}
    # off the chip (tests) the shares are computed against the v5e row
    peaks = work.peaks(device["kind"] if require_chip else "TPU v5 lite")
    generator = load_generator(cell.traffic["kind"], root)
    workdir = workdir or root / ".bench_run" / workload
    shutil.rmtree(workdir, ignore_errors=True)
    workdir.mkdir(parents=True)
    ctx = RunContext(cell=cell, seed=seed, seconds=seconds, trace=trace,
                     t_start=t_start, workdir=workdir, peaks=peaks,
                     smoke=smoke, compiles=CompileLog(), control=control)
    try:
        rec = generator.run(ctx)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    inside = ctx.compiles.inside(*rec["window"])
    if inside:
        raise BenchError(f"{len(inside)} compilation(s) inside the measured "
                         f"window: {sorted(set(inside))}")
    device["memory_peak_bytes"] = rec["memory_peak_bytes"]
    checks = rec["checks"]
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    if trace:
        tr = rec["record"]["trace"]
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        metrics = {}
        for entry, mod in cell.per_layer:
            value = mod.read(rec["record"])
            if value is not None:
                metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    else:
        metrics = {e["name"]: {"value": rec["end_to_end"][e["name"]],
                               "unit": e["unit"]} for e in cell.end_to_end}
    out = {"correct": bool(correct), "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = rec["record"]["trace"].breakdown()
    if control:
        out["control"] = rec["record"].get("control_gap")
    out["checks"] = checks
    return out
