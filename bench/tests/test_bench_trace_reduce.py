"""Trace reduction on a small trace recorded on a TPU v5e (160 top-level
ops of the handoff cell's encode and decode programs) and on synthetic
traces whose answers are known."""
from pathlib import Path

import pytest

import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def recorded():
    return tr.load_json(DATA / "handoff_ops.json")


def _sweep_busy(events, t0, t1):
    """Busy time by counting open intervals at every boundary."""
    edges = []
    for _, _, s, d in events:
        s, e = max(s, t0), min(s + d, t1)
        if e > s:
            edges += [(s, 1), (e, -1)]
    busy, depth, last = 0.0, 0, None
    for t, step in sorted(edges):
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_busy_is_the_union_of_op_intervals(recorded):
    starts = [e[2] for e in recorded.device]
    ends = [e[2] + e[3] for e in recorded.device]
    t0, t1 = min(starts) - 1e5, max(ends) + 1e5
    out = tr.reduce(recorded, t0, t1)
    assert out["busy_s"] * 1e9 == pytest.approx(_sweep_busy(recorded.device, t0, t1))
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["window_s"] == pytest.approx((t1 - t0) / 1e9)


def test_window_clips_ops(recorded):
    starts = sorted(e[2] for e in recorded.device)
    t0, t1 = starts[40], starts[120]
    out = tr.reduce(recorded, t0, t1)
    assert out["busy_s"] * 1e9 == pytest.approx(_sweep_busy(recorded.device, t0, t1))
    assert sum(out["op_count"].values()) < len(recorded.device)


def test_op_times_and_names(recorded):
    t0 = min(e[2] for e in recorded.device)
    t1 = max(e[2] + e[3] for e in recorded.device)
    out = tr.reduce(recorded, t0, t1)
    assert sum(out["op_s"].values()) == pytest.approx(
        sum(e[3] for e in recorded.device) / 1e9)
    assert "jit__encode_side/bin_parity_xorsum_units.1" in out["op_s"]
    assert len(out["device_ops"]) == 10
    secs = [s for _, s in out["device_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_op_label_and_top_level():
    assert tr.op_label("jit_f(123)", "%while.24 = (s32[]) while(...)") == "jit_f/while.24"
    events = [("loop", 0.0, 100.0), ("body.1", 10.0, 5.0), ("body.2", 50.0, 5.0),
              ("next", 100.0, 20.0)]
    assert [e[0] for e in tr.top_level(events)] == ["loop", "next"]


def test_idle_gaps_go_to_the_innermost_covering_span():
    trace = tr.Trace([(0, "p/a", 0.0, 10.0), (0, "p/b", 40.0, 10.0),
                      (0, "p/c", 90.0, 10.0)], sync_ns=0.0)
    spans = [("serve", 0.0, 100.0), ("barrier", 12.0, 38.0), ("plan", 55.0, 85.0)]
    out = tr.reduce(trace, 0.0, 120.0, spans)
    assert out["busy_s"] == pytest.approx(30e-9)
    gaps = dict(out["idle_gaps"])
    assert gaps["barrier"] == pytest.approx(30e-9)      # gap 10..40, mid 25
    assert gaps["plan"] == pytest.approx(40e-9)         # gap 50..90, mid 70
    assert gaps["(no host span)"] == pytest.approx(20e-9)   # gap 100..120
    assert "serve" not in gaps


def test_load_reads_a_recorded_xplane():
    """A v5e trace of three calls of a small jitted program, recorded after
    the clock-sync annotation.  On that chip the device's timestamps run
    about 1.3 ms ahead of the host's dispatch of the same call, so the ops
    are only known to lie within a few ms of the sync or after it."""
    trace = tr.load(str(DATA / "tiny.xplane.pb"))
    assert trace.sync_ns is not None
    assert len(trace.device) == 36
    names = {name for _, name, _, _ in trace.device}
    assert all(n.startswith("jit__lambda/") for n in names)
    assert len(names) == 12
    assert all(trace.sync_ns - 5e6 < s < trace.sync_ns + 1e9
               for _, _, s, _ in trace.device)
