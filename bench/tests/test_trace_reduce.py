"""Trace reduction, on intervals made by hand and on a small trace recorded
on a TPU v5e (``data/small.xplane.pb``, made by ``record_trace.py``: three
rounds of a named Pallas kernel and a jitted step inside the harness's
spans, with the device idle between rounds).

    python -m pytest bench/tests/test_trace_reduce.py
"""
from pathlib import Path

import pytest

import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"
SPANS = ("bench.wait_due", "bench.ingest", "bench.block_ready")


def test_union_merges_overlaps_and_keeps_gaps():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]


def test_overlap_and_gaps():
    cover = [(1, 2), (4, 6)]
    assert tr.gaps(cover, (0, 8)) == [(0, 1), (2, 4), (6, 8)]
    assert tr.overlap(cover, [(1.5, 5)]) == pytest.approx(1.5)


def _summary(ops, spans, window=(0.0, 10.0)):
    ev = [tr.Event(n, s, e) for n, s, e in ops]
    return tr.Summary(window, {"/device:TPU:0": ev}, {"/device:TPU:0": []},
                      spans)


def test_idle_share_and_idle_inside_spans():
    s = _summary([("k", 1, 3), ("k", 2, 4), ("m", 8, 9)],
                 {"bench.ingest": [(0, 5)], "bench.block_ready": [(5, 10)]})
    assert s.busy_s == pytest.approx(4.0)
    assert s.idle_share == pytest.approx(0.6)
    assert s.idle_inside(("bench.ingest",)) == pytest.approx(2.0)
    assert s.idle_inside(("bench.block_ready",)) == pytest.approx(4.0)
    b = s.breakdown()
    assert b["device_ops"] == [["k", 4.0], ["m", 1.0]]
    assert b["idle_gaps"][0] == ["bench.block_ready", 4.0]


def test_roofline_share_is_bytes_over_peak_over_time():
    peaks = {"hbm_bytes_per_s": 1e9}
    assert tr.roofline_share(5e8, 1.0, peaks) == pytest.approx(50.0)
    assert tr.roofline_share(0, 1.0, peaks) is None
    assert tr.roofline_share(1e8, 0.0, peaks) is None


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(str(DATA))
    return profile, tr.reduce(profile, SPANS)


def test_recorded_trace_has_window_spans_and_one_chip(recorded):
    _, s = recorded
    assert 0.05 < s.window_s < 5.0
    assert s.count("bench.ingest") == 3
    assert s.count("bench.block_ready") == 3
    assert list(s.ops) == ["/device:TPU:0"]


def test_recorded_busy_is_union_of_device_ops(recorded):
    profile, s = recorded
    plane = next(p for p in profile.planes if p.name == "/device:TPU:0")
    lo, hi = s.window
    ivs = []
    for line in plane.lines:
        if line.name == "XLA Ops":
            for ev in line.events:
                a, b = max(ev.start_ns * 1e-9, lo), min(ev.end_ns * 1e-9, hi)
                if b > a:
                    ivs.append((a, b))
    assert ivs, "no device operation in the window"
    busy = sum(b - a for a, b in tr.union(ivs))
    assert s.busy_s == pytest.approx(busy)
    assert 0.0 < s.idle_share < 1.0
    # the sleeps between rounds are idle time outside every span
    inside = s.idle_inside(SPANS)
    assert 0.0 <= inside < s.window_s * s.idle_share


def test_recorded_kernel_and_module_found_by_name(recorded):
    _, s = recorded
    assert s.op_seconds(lambda e: e.short.startswith(
        "jit_step/%double_kernel")) > 0  # the kernel, inside its executable
    assert s.module_seconds(lambda e: "step" in e.name) > 0
    assert len(s.breakdown()["device_ops"]) <= 10
