"""The trace reduction, on synthetic intervals and on a small trace
recorded on a TPU v5e (``data/probe.xplane.pb``: three rounds of
``bench.data`` / ``bench.step`` / ``bench.read`` spans around two small
jitted programs)."""
import os

import pytest

import run

trace = run.module("trace.py")
PROBE = os.path.join(os.path.dirname(__file__), "data", "probe.xplane.pb")


def test_union_merges_overlaps_and_drops_empty():
    got = trace.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9), (6, 8)])
    assert got == [(0, 4), (5, 8)]


def test_leaves_drop_events_that_contain_others():
    evs = [("while", 0, 10), ("a", 1, 3), ("b", 4, 9), ("c", 12, 13),
           ("loop", 20, 30), ("d", 20, 25)]
    assert [n for n, _, _ in trace.leaves(evs)] == ["a", "b", "c", "d"]


def test_gaps_and_charge():
    busy = trace.union([(2, 4), (6, 7)])
    g = trace.gaps(busy, 0, 10)
    assert g == [(0, 2), (4, 6), (7, 10)]
    spans = [("bench.data", 0, 3), ("bench.step", 3, 6.5),
             ("bench.read", 6.5, 10)]
    idle = trace.charge(g, spans)
    assert idle == {"bench.data": 2, "bench.step": 2, "bench.read": 3}
    assert trace.charge([(20, 21)], spans) == {"other": 1}


def test_probe_trace_by_brute_force():
    spans, devices = trace.planes(PROBE)
    assert {n for n, _, _ in spans} == {"bench.data", "bench.step",
                                        "bench.read"}
    assert sum(1 for n, _, _ in spans if n == "bench.step") == 3
    assert list(devices) == ["/device:TPU:0"]
    r = trace.reduce(PROBE, 1)
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    assert r["window_s"] == pytest.approx((hi - lo) / 1e9)
    # busy by brute force: the set of covered nanoseconds, clipped
    covered = set()
    for _, s, e in devices["/device:TPU:0"]:
        covered.update(range(int(max(s, lo)), int(min(e, hi))))
    assert r["busy_s"] == pytest.approx(len(covered) / 1e9, rel=1e-6)
    assert 0 < r["busy_s"] < r["window_s"]
    idle = sum(r["idle"].values())
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-9)
    ops = r["breakdown"]["device_ops"]
    assert len(ops) <= 10 and ops == sorted(ops, key=lambda x: -x[1])
    assert trace.op_seconds(r["ops"], "convolution_tanh_fusion") > 0
    assert trace.op_seconds(r["ops"], "convolution") == 0
    assert trace.op_seconds({"%psp_tick.3 = (s32[16]) custom-call(": 2.0,
                             "%psp_tick = f32[] fusion(": 1.0,
                             "%psp_ticker.1 = f32[]": 4.0}, "psp_tick") == 3.0


def test_missing_trace_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace.find_xplane(str(tmp_path))
