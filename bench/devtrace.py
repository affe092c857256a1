"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

Read with ``jax.profiler.ProfileData`` and nothing else. From the device
planes (``/device:TPU:<n>``) it takes the operations (line ``XLA Ops``) and
the programs (line ``XLA Modules``); from the caller it takes host spans
already put on the trace's clock. It gives, within one window:

- busy seconds: the union of the intervals in which an operation ran,
  averaged over the devices; the idle share is 1 - busy / window;
- device seconds summed by operation name and by program name;
- idle gaps, each charged to the innermost host span that covers its middle.

The host's ``perf_counter`` and the trace's clock are tied together by one
``TraceAnnotation`` (``CLOCK_MARK``) recorded at a known ``perf_counter``.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Iterable

CLOCK_MARK = "bench/clock"
_MODULE_ID = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(glob.glob(str(Path(trace_dir) / "plugins" / "profile"
                                 / "*" / "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Path(found[-1])


def module_name(name: str) -> str:
    """``jit_step(123)`` -> ``jit_step``: one name for every launch."""
    return _MODULE_ID.sub("", name)


def _merge(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                      # averaged over devices
    op_s: dict = field(default_factory=dict)        # op name -> seconds
    module_s: dict = field(default_factory=dict)    # program -> seconds
    module_n: dict = field(default_factory=dict)    # program -> launches
    idle_s: dict = field(default_factory=dict)      # host activity -> seconds
    n_devices: int = 1

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, pattern: str) -> tuple[float, int]:
        """Summed device seconds and launches of programs matching."""
        rx = re.compile(pattern)
        hits = [m for m in self.module_s if rx.search(m)]
        return (sum(self.module_s[m] for m in hits),
                sum(self.module_n[m] for m in hits))

    def breakdown(self) -> dict:
        """The ten longest of each, operations under their printed names
        (cut at 120 characters: an HLO op prints its whole signature)."""
        top = lambda d: [[k[:120], v] for k, v in
                         sorted(d.items(), key=lambda kv: -kv[1])[:10]]
        return {"device_ops": top(self.op_s), "idle_gaps": top(self.idle_s)}


def _span_index(spans, w0: float, w1: float, bin_ns: float = 1e6):
    """A lookup of the innermost (shortest) span covering a time, through
    bins of ``bin_ns``, so that a window of many small gaps stays cheap."""
    bins: dict = defaultdict(list)
    for s in spans:
        a, b = max(s[1], w0), min(s[2], w1)
        for k in range(int((a - w0) // bin_ns), int((b - w0) // bin_ns) + 1):
            bins[k].append(s)

    def innermost(t: float) -> str:
        cover = [s for s in bins.get(int((t - w0) // bin_ns), ())
                 if s[1] <= t <= s[2]]
        return min(cover, key=lambda s: s[2] - s[1])[0] if cover else "host:untraced"
    return innermost


def clock_offset_ns(pd, mark: str = CLOCK_MARK) -> float:
    """Trace-clock start of the first host event named ``mark``."""
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == mark:
                    return float(ev.start_ns)
    raise ValueError(f"no {mark!r} event in the trace")


def reduce(path: Path, window_ns: tuple[float, float],
           host_spans: Iterable[tuple[str, float, float]] = (), *,
           device_plane: Callable[[str], bool] = lambda n: n.startswith("/device:TPU"),
           ops_line: Callable[[str], bool] = lambda n: n == "XLA Ops",
           modules_line: Callable[[str], bool] = lambda n: n == "XLA Modules",
           ) -> TraceSummary:
    """Reduce the trace at ``path`` over ``window_ns`` (trace clock).

    ``host_spans``: (name, start_ns, end_ns) on the trace clock, used only to
    say what the host was doing in each idle gap."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    return reduce_profile(pd, window_ns, host_spans, device_plane=device_plane,
                          ops_line=ops_line, modules_line=modules_line)


def reduce_profile(pd, window_ns, host_spans=(), *, device_plane, ops_line,
                   modules_line) -> TraceSummary:
    w0, w1 = window_ns
    if w1 <= w0:
        raise ValueError(f"empty window {window_ns}")
    op_s: dict = defaultdict(float)
    module_s: dict = defaultdict(float)
    module_n: dict = defaultdict(int)
    busy_per_dev, gaps = [], []
    for plane in pd.planes:
        if not device_plane(plane.name):
            continue
        ops, mods = [], []
        for line in plane.lines:
            is_ops, is_mod = ops_line(line.name), modules_line(line.name)
            if not (is_ops or is_mod):
                continue
            for ev in line.events:
                a = float(ev.start_ns)
                b = a + float(ev.duration_ns)
                a, b = max(a, w0), min(b, w1)
                if b <= a:
                    continue
                if is_ops:
                    ops.append((a, b))
                    op_s[ev.name] += (b - a) * 1e-9
                else:
                    mods.append((a, b))
                    key = module_name(ev.name)
                    module_s[key] += (b - a) * 1e-9
                    module_n[key] += 1
        merged = _merge(ops or mods)
        if not merged:
            continue
        busy_per_dev.append(sum(b - a for a, b in merged) * 1e-9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps.extend((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    if not busy_per_dev:
        raise ValueError("no device operation inside the window")
    n_dev = len(busy_per_dev)
    idle_s: dict = defaultdict(float)
    innermost = _span_index(host_spans, w0, w1)
    for a, b in gaps:
        idle_s[innermost(0.5 * (a + b))] += (b - a) * 1e-9 / n_dev
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9, busy_s=sum(busy_per_dev) / n_dev,
        op_s={k: v / n_dev for k, v in op_s.items()},
        module_s={k: v / n_dev for k, v in module_s.items()},
        module_n=dict(module_n), idle_s=dict(idle_s), n_devices=n_dev)
