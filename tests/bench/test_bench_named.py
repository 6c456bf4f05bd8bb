"""The per-layer metrics that read what the program names itself: its
attention kernels by name in a trace, and its planner spans and table
counters in memory (``repro.obs``)."""

from __future__ import annotations

import types

import numpy as np
import pytest

from bench_tiny import BENCH, make_root, run_tiny
from harness.catalog import load_cell, metric_reader
from harness.feed import PlanSpec, build_batch
from harness.named import base_name, kernel_intervals
from harness.trace import TraceSummary, kernel_names
from harness.traffic import causal_pairs, draw_step

DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"
KERNELS = ("attn_fwd_ms", "attn_dq_ms", "attn_dkv_ms")
HOST = ("attn_grid_fill_pct", "attn_tile_fill_pct", "plan_ms", "tables_ms")
CUSTOM = 'custom_call_target="tpu_custom_call"'


def op(plane, name, start, dur, meta=""):
    return {"plane": plane, "line": "XLA Ops", "name": name, "meta": meta,
            "start_ns": float(start), "dur_ns": float(dur)}


def kernel(plane, name, start, dur):
    return op(plane, name, start, dur,
              meta=f"%{name} = (bf16[1,24,8192,128]) custom-call(%q.1), "
                   f"{CUSTOM}")


def read(name, rec):
    return metric_reader(BENCH, name)(rec)


@pytest.fixture
def events():
    # window [0, 1000]: two traced steps on two chips; the forward kernel
    # runs twice per step (once recomputed), and a fusion consuming its
    # output quotes %doc_attn_fwd.3 in its HLO text
    host = {"plane": "/host:CPU", "line": "python", "name": "sync",
            "meta": "", "start_ns": 0.0, "dur_ns": 1000.0}
    evs = [host]
    for dev in (DEV0, DEV1):
        evs += [kernel(dev, "doc_attn_fwd.3", 0, 40),
                kernel(dev, "doc_attn_fwd.7", 100, 40),
                kernel(dev, "doc_attn_dq.1", 200, 30),
                kernel(dev, "doc_attn_dkv", 300, 50),
                op(dev, "fusion.9", 400, 25,
                   meta="%fusion.9 = bf16[1,24,8192,128] "
                        "fusion(%doc_attn_fwd.3), kind=kLoop"),
                kernel(dev, "doc_attn_fwd_extra.2", 500, 60),
                op(dev, "while.1", 0, 600)]
    return evs


def test_base_name():
    assert base_name("doc_attn_fwd.3") == "doc_attn_fwd"
    assert base_name("doc_attn_fwd") == "doc_attn_fwd"
    assert base_name("%doc_attn_dq.12 = bf16[2] custom-call()") == \
        "doc_attn_dq"
    assert base_name("ROOT %doc_attn_dkv.1 = (bf16[2]) custom-call()") == \
        "doc_attn_dkv"
    assert base_name("fusion.480") == "fusion"


def test_kernel_readers_match_short_names(events):
    t = TraceSummary(events)
    rec = types.SimpleNamespace(trace=t, traced=[{}, {}], chips=2,
                                bench_dir=BENCH)
    # (40 + 40) ns on each of two chips, over two chips and two steps
    assert read("attn_fwd_ms", rec) == pytest.approx(80e-6 / 2)
    assert read("attn_dq_ms", rec) == pytest.approx(30e-6 / 2)
    assert read("attn_dkv_ms", rec) == pytest.approx(50e-6 / 2)
    assert len(kernel_intervals(t, "doc_attn_fwd")) == 4
    # the HLO text of the consumer names the kernel: matching the text
    # would count the fusion too
    assert t.op_seconds(["%doc_attn_fwd."]) > 2 * 80e-9
    # the three named kernels are the whole of attn_kernel_ms here but
    # for the kernel of another name
    split = sum(read(m, rec) for m in KERNELS)
    assert read("attn_kernel_ms", rec) == pytest.approx(split + 60e-6 / 2)
    assert kernel_names(BENCH, "doc_attention") == [CUSTOM]


def test_kernel_readers_find_nothing_without_names(events):
    renamed = [dict(e, name=e["name"].replace("doc_attn", "checkpoint"))
               for e in events]
    rec = types.SimpleNamespace(trace=TraceSummary(renamed),
                                traced=[{}], chips=2)
    assert all(read(m, rec) is None for m in KERNELS)
    rec = types.SimpleNamespace(trace=None, traced=[], chips=1)
    assert all(read(m, rec) is None for m in KERNELS)


def test_host_readers_find_nothing_outside_the_window():
    rec = types.SimpleNamespace(window_ns=(0, 1))
    assert all(read(m, rec) is None for m in HOST)


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("named"))
    return load_cell("tiny.t", root)


def test_table_counters_recount(tiny_cell):
    """Over the feed's batches, the counters equal a recount from the
    emitted tables' flags and the traffic's pair count, and the fill
    readers read them."""
    from repro import obs
    from repro.table_layout import FLAG_VALID

    traffic = tiny_cell.traffic
    spec = PlanSpec(strategy="flashcp", cp=2, overlap="chunked",
                    grid="flat", target_imbalance=1.05)
    t0 = obs.records()[-1].end_ns + 1 if obs.records() else 0
    visits = slots = pairs = 0
    for step in range(3):
        samples = draw_step(traffic, 5, step, 512)
        arrays = build_batch(spec, samples, step).arrays
        flags = [a for k, a in arrays.items() if k.endswith("_flags")]
        visits += sum(int(np.count_nonzero(f & FLAG_VALID)) for f in flags)
        slots += sum(f.size for f in flags)
        pairs += sum(causal_pairs(s.doc_lens) for s in samples)
    rec = types.SimpleNamespace(window_ns=(t0, obs.records()[-1].end_ns))
    tables = [r for r in obs.records(*rec.window_ns)
              if r.name == "planner.tables"]
    assert len(tables) == 3
    assert sum(r.counts["tables.visits"] for r in tables) == visits
    assert sum(r.counts["tables.slots"] for r in tables) == slots
    # the fwd/dQ queue and the dKV queue each cover every pair once
    assert sum(r.counts["tables.pairs"] for r in tables) == 2 * pairs
    assert read("attn_grid_fill_pct", rec) == \
        pytest.approx(100.0 * visits / slots)
    assert read("attn_tile_fill_pct", rec) == \
        pytest.approx(100.0 * 2 * pairs / (visits * 128 * 128))
    assert 0 < read("attn_grid_fill_pct", rec) <= 100
    assert 0 < read("attn_tile_fill_pct", rec) <= 100


def test_host_readers_on_the_tiny_cell(tiny_cell):
    root = tiny_cell.bench_dir.parent
    rec = run_tiny(root)["record"]
    got = {m: read(m, rec) for m in HOST}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["attn_grid_fill_pct"] <= 100
    assert got["attn_tile_fill_pct"] <= 100
    # the planner's spans lie inside the feed's plan_encode span
    outer = rec.spans.durations_s("plan_encode", 0, rec.window_ns[1])
    assert got["plan_ms"] + got["tables_ms"] <= 1e3 * max(outer)
