"""When does saved state become durable, on the benchmark's own clock.

``AckWatcher`` polls what a user of the system could observe (a committed
checkpoint directory, a published snapshot's acknowledgement) and notes on
the host clock when each step is first seen. ``state_age_p95`` turns those
notes into the end-to-end state age: the work a failure would lose.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Callable, Iterable

import numpy as np


class AckWatcher:
    """Calls ``poll()`` every ``period_s`` on a thread of its own; each step
    it returns is noted with the time it was first seen."""

    def __init__(self, poll: Callable[[], Iterable[int]],
                 period_s: float = 0.01) -> None:
        self.poll, self.period_s = poll, period_s
        self.seen: dict[int, float] = {}
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="bench-acks",
                                        daemon=True)
        self._thread.start()

    def scan(self) -> None:
        now = time.perf_counter()
        for step in self.poll():
            self.seen.setdefault(step, now)

    def _run(self) -> None:
        while not self._stop.is_set():
            self.scan()
            self._stop.wait(self.period_s)

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=10)
        self.scan()


def committed_checkpoints(directory) -> Callable[[], list[int]]:
    """Steps whose ``step_<n>/manifest.json`` is published: the program
    renames a checkpoint into place only after its files are fsynced."""
    directory = str(directory)

    def poll() -> list[int]:
        try:
            names = os.listdir(directory)
        except FileNotFoundError:
            return []
        return [int(n[5:]) for n in names
                if n.startswith("step_") and n[5:].isdigit()
                and os.path.exists(os.path.join(directory, n, "manifest.json"))]
    return poll


def state_age_p95(step_end: dict, acked: dict, start: float,
                  t_open: float, t_close: float) -> float:
    """95th percentile, over the steps that end in the window, of the time
    since the end of the step whose state the newest acknowledged save
    holds (since ``start`` while none is)."""
    ages = []
    for t in sorted(v for v in step_end.values() if t_open < v <= t_close):
        done = [s for s, t1 in acked.items() if t1 <= t and s in step_end]
        since = step_end[max(done)] if done else start
        ages.append(t - since)
    return float(np.percentile(ages, 95))
