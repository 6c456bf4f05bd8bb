"""The benchmark's reduction from trace events to per-layer numbers, on
hand-worked events and on a small trace recorded on a TPU v5e chip (a
few steps of a two-layer run, kept under bench/testdata/)."""

from __future__ import annotations

import pytest

from bench_tiny import BENCH
from harness.trace import TraceSummary, load_events, union_ns

DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"


def op(plane, name, start, dur, meta=""):
    return {"plane": plane, "line": "XLA Ops", "name": name, "meta": meta,
            "start_ns": float(start), "dur_ns": float(dur)}


def span(name, start, dur):
    return {"plane": "/host:CPU", "line": "python", "name": name,
            "meta": "", "start_ns": float(start), "dur_ns": float(dur)}


@pytest.fixture
def events():
    # window [0, 100] set by the host spans; device 0 busy on [10, 40]
    # (two overlapping ops) and [60, 80]; device 1 busy on [0, 50] with a
    # collective on [20, 70] that other ops cover on [20, 50]
    return [
        span("h2d", 0, 10), span("dispatch", 10, 5), span("sync", 15, 85),
        op(DEV0, "fusion.1", 10, 20),
        op(DEV0, "custom-call.3", 25, 15, meta="_fwd_kernel_flat"),
        op(DEV0, "fusion.2", 60, 20),
        op(DEV0, "op-outside", 120, 10),
        op(DEV1, "fusion.1", 0, 50),
        op(DEV1, "collective-permute-done.1", 20, 50),
        {"plane": DEV1, "line": "XLA Modules", "name": "jit_step",
         "meta": "", "start_ns": 0.0, "dur_ns": 100.0},
    ]


def test_union_merges_overlaps():
    assert union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert union_ns([]) == 0


def test_busy_and_idle(events):
    t = TraceSummary(events)
    assert (t.w0, t.w1) == (0.0, 100.0)
    assert t.devices == [DEV0, DEV1]
    assert t.busy_s(DEV0) == pytest.approx(50e-9)   # [10,40] + [60,80]
    assert t.busy_s(DEV1) == pytest.approx(70e-9)   # [0,70]; modules line out
    assert t.mean_busy_s() == pytest.approx(60e-9)
    assert t.idle_share() == pytest.approx(0.4)
    assert t.window_s == pytest.approx(100e-9)


def test_kernel_time_by_long_name(events):
    t = TraceSummary(events)
    assert t.op_seconds(["_fwd_kernel_flat"]) == pytest.approx(15e-9)
    assert t.op_seconds(["fusion.1"]) == pytest.approx(70e-9)
    assert t.op_seconds(["nothing"]) == 0.0


def test_collective_exposure(events):
    t = TraceSummary(events)
    assert t.exposed_collective_s(DEV0) == 0.0
    assert t.exposed_collective_s(DEV1) == pytest.approx(20e-9)  # [50,70]
    assert t.mean_exposed_collective_s() == pytest.approx(10e-9)


def test_gap_attribution(events):
    t = TraceSummary(events)
    # device 0 idles on [0,10] (h2d open), [40,60] and [80,100] (sync open)
    gaps = t.named_gaps(10)
    assert sorted(gaps, key=lambda g: -g[1]) == gaps
    assert [g[0] for g in gaps] == ["sync", "sync", "h2d"]
    assert [round(g[1] * 1e9) for g in gaps] == [20, 20, 10]


def test_containers_count_as_busy_only(events):
    # a while loop on device 0 spanning [5, 90] around its body's ops
    t = TraceSummary(events + [op(DEV0, "while.1", 5, 85)])
    assert t.busy_s(DEV0) == pytest.approx(85e-9)
    assert t.op_seconds(["while"]) == 0.0
    assert t.op_seconds(["fusion.2"]) == pytest.approx(20e-9)
    assert "while.1" not in [n for n, _ in t.top_ops()]
    # a loop on device 1 does not hide its collective's exposed part
    t = TraceSummary(events + [op(DEV1, "while.2", 0, 80)])
    assert t.exposed_collective_s(DEV1) == pytest.approx(20e-9)


def test_top_ops_per_chip(events):
    top = TraceSummary(events).top_ops(2)
    assert top[0][0] == "fusion.1"
    assert top[0][1] == pytest.approx(35e-9)   # (20 + 50) / 2 devices


def test_recorded_chip_trace():
    events = load_events(BENCH / "testdata" / "trace_v5e_tiny.json.gz")
    t = TraceSummary(events)
    assert t.devices and t.devices[0].startswith("/device:TPU")
    assert 0 < t.mean_busy_s() <= t.window_s
    assert 0.0 <= t.idle_share() < 1.0
    ops = t.op_seconds([""])          # every op that is not a container
    assert ops > 0
    kernels = t.op_seconds(['custom_call_target="tpu_custom_call"'])
    assert 0 < kernels <= ops
    assert any(e["container"] for e in t.ops[t.devices[0]])
    assert {g[0] for g in t.named_gaps()} <= {"next_batch", "h2d",
                                              "dispatch", "sync", "none"}
