"""Trace reduction on a small trace recorded on the CPU (the fixture holds
three annotated bursts of matrix products, each followed by a 20 ms host
sleep annotated ``bench/host_wait``)."""
from pathlib import Path

import pytest

import devtrace

FIXTURE = Path(__file__).parent / "fixtures" / "cpu_trace.xplane.pb"


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(FIXTURE))


def _host_spans(pd):
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            if line.name == "python3":
                out += [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                        for e in line.events if e.name.startswith("bench/")]
    return out


def _reduce(pd, window):
    return devtrace.reduce_profile(
        pd, window, _host_spans(pd),
        device_plane=lambda n: n == "/host:CPU",
        ops_line=lambda n: n.startswith("tf_XLAPjRtCpuClient"),
        modules_line=lambda n: False)


def _window(pd):
    spans = [s for s in _host_spans(pd) if s[0] in ("bench/step", "bench/host_wait")]
    return min(s[1] for s in spans), max(s[2] for s in spans)


def test_clock_mark_found(profile):
    t = devtrace.clock_offset_ns(profile)
    assert t > 0
    assert t < _window(profile)[0]


def test_busy_idle_and_ops(profile):
    s = _reduce(profile, _window(profile))
    assert 0 < s.busy_s < s.window_s
    assert 0.0 < s.idle_share < 1.0
    assert "dot_general.1" in s.op_s
    # three 20 ms sleeps: at least 50 ms of idle time charged to them
    assert s.idle_s.get("bench/host_wait", 0.0) >= 0.05
    assert sum(s.idle_s.values()) == pytest.approx(s.window_s - s.busy_s, rel=1e-6)


def test_window_clips_events(profile):
    w0, w1 = _window(profile)
    half = _reduce(profile, (w0, 0.5 * (w0 + w1)))
    whole = _reduce(profile, (w0, w1))
    assert half.window_s == pytest.approx(0.5 * whole.window_s)
    assert half.busy_s <= whole.busy_s
    with pytest.raises(ValueError):
        _reduce(profile, (w1, w0))


def test_breakdown_shape(profile):
    b = _reduce(profile, _window(profile)).breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    for entries in b.values():
        assert 0 < len(entries) <= 10
        assert all(isinstance(n, str) and v >= 0 for n, v in entries)
    secs = [v for _, v in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_module_names_drop_launch_ids():
    assert devtrace.module_name("jit__compress_tree_packed(42)") == \
        "jit__compress_tree_packed"
    assert devtrace.module_name("jit_step") == "jit_step"


def test_find_xplane(tmp_path):
    d = tmp_path / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(FIXTURE.read_bytes())
    assert devtrace.find_xplane(tmp_path).name == "host.xplane.pb"
    with pytest.raises(FileNotFoundError):
        devtrace.find_xplane(tmp_path / "none")
