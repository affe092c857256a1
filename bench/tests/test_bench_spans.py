"""The readers of the loop thread's tail and dispatch spans and of the
checkpoint sink's spans, on made-up records: what each counts, where the
window cuts a span, and nothing read from a program without the spans."""
from pathlib import Path

import pytest

import harness

METRICS = Path(__file__).resolve().parents[1] / "metrics"
MAIN = "MainThread"


def _reader(name):
    return harness.load_module(METRICS / f"{name}.py", f"t_{name.replace('.', '_')}")


def _record(spans, window=(1.0, 11.0), steps=4):
    return {"window": window, "window_s": window[1] - window[0],
            "steps_in_window": steps, "spans": spans, "main_thread": MAIN}


# a window from 1 s to 11 s over four steps, saves at steps 0, 2 and 4
SPANS = [
    ("step/compute", 0.5, 1.5, MAIN, 0),
    ("step/dispatch", 0.5, 1.5, MAIN, 0),          # cut: 0.5 s inside
    ("step/dispatch", 3.0, 3.25, MAIN, 1),
    ("step/dispatch", 5.0, 5.25, MAIN, 2),
    ("step/dispatch", 7.0, 7.5, "insitu-0", 3),    # not the loop thread
    ("sink/checkpoint", 0.2, 0.9, "insitu-0", 0),  # ends before the window
    ("sink/checkpoint", 4.0, 4.5, "insitu-1", 2),  # a failed attempt ...
    ("sink/checkpoint", 4.75, 6.0, "insitu-1", 2),  # ... and its retry
    ("sink/checkpoint", 9.0, 9.5, MAIN, 4),
    ("sink/checkpoint", 10.5, 12.0, "insitu-0", 6),  # ends after the window
    ("insitu/wait_idle", 9.0, 10.0, MAIN, -1),
    ("insitu/wait_idle", 9.25, 9.5, "insitu-0", -1),  # not the loop thread
    ("insitu/drain", 10.0, 12.0, MAIN, -1),          # cut: 1 s inside
]


@pytest.mark.parametrize("name,value", [
    ("step_dispatch_ms.train", 1e3 * (0.5 + 0.25 + 0.25) / 4),
    ("insitu_tail_ms.train", 1e3 * (1.0 + 1.0)),
    ("ckpt_write_ms_per_save", 1e3 * ((0.5 + 1.25) + 0.5) / 2),
])
def test_span_reader_counts_what_the_window_holds(name, value):
    assert _reader(name).read(_record(SPANS)) == pytest.approx(value)


@pytest.mark.parametrize("name,span", [
    ("step_dispatch_ms.train", "step/dispatch"),
    ("insitu_tail_ms.train", "insitu/wait_idle"),
    ("ckpt_write_ms_per_save", "sink/checkpoint"),
])
def test_span_reader_reads_nothing_without_its_span(name, span):
    # a program that records none of the span (the drain alone is older)
    spans = [s for s in SPANS if s[0] != span]
    assert _reader(name).read(_record(spans)) is None
    # or records it only outside the window
    outside = [s for s in SPANS if s[0] != span] + [(span, 12.0, 13.0, MAIN, 9)]
    assert _reader(name).read(_record(outside)) is None


def test_tail_is_the_loop_thread_alone():
    spans = [s for s in SPANS if s[0] != "insitu/drain"]
    assert _reader("insitu_tail_ms.train").read(_record(spans)) == pytest.approx(1e3)
    spans = [("insitu/wait_idle", 2.0, 3.0, "insitu-0", -1)]
    assert _reader("insitu_tail_ms.train").read(_record(spans)) is None
