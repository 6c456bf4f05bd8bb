"""Host spans and counters of ``repro.obs``, and the planner's spans."""

import glob
import os
import threading

import numpy as np
import pytest

from repro import obs


def test_spans_nest_with_parent_ids():
    r = obs.Recorder()
    with r.span("outer"):
        with r.span("inner"):
            pass
        with r.span("sibling"):
            pass
    inner, sibling, outer = r.records()
    assert [x.name for x in (inner, sibling, outer)] == \
        ["inner", "sibling", "outer"]
    assert outer.parent is None
    assert inner.parent == sibling.parent == outer.id
    assert len({inner.id, sibling.id, outer.id}) == 3
    assert outer.start_ns <= inner.start_ns <= inner.end_ns \
        <= sibling.start_ns <= sibling.end_ns <= outer.end_ns
    # the record keeps the harness's layout: name, start, end first
    assert outer[:3] == (outer.name, outer.start_ns, outer.end_ns)


def test_count_lands_on_innermost_span_of_its_thread():
    r = obs.Recorder()
    entered, release = threading.Event(), threading.Event()

    def other():
        with r.span("other"):
            r.count("n", 5)
            entered.set()
            release.wait(10)
            r.count("n", 1)

    t = threading.Thread(target=other)
    with r.span("outer"):
        r.count("n", 1)
        with r.span("inner"):
            t.start()
            assert entered.wait(10)
            r.count("n", 2)
            r.count("n", 3)
            r.count("m")
            release.set()
            t.join(10)
    assert not t.is_alive()
    r.count("n", 100)           # no span open: dropped
    by = {x.name: x for x in r.records()}
    assert by["inner"].counts == {"n": 5, "m": 1}
    assert by["outer"].counts == {"n": 1}
    assert by["other"].counts == {"n": 6}
    assert by["other"].parent is None       # another thread's stack


def test_ring_keeps_the_newest():
    r = obs.Recorder(capacity=8)
    for i in range(20):
        with r.span(f"s{i}"):
            pass
    assert [x.name for x in r.records()] == [f"s{i}" for i in range(12, 20)]


def test_records_select_by_end():
    r = obs.Recorder()
    with r.span("a"):
        pass
    with r.span("b"):
        pass
    a, b = r.records()
    assert [x.name for x in r.records(since_ns=b.end_ns)] == ["b"]
    assert [x.name for x in r.records(until_ns=a.end_ns)] == ["a"]
    assert r.records(since_ns=b.end_ns + 1) == []


def test_disabled_records_nothing():
    r = obs.Recorder()
    r.enabled = False
    with r.span("off"):
        r.count("n", 1)
    assert r.records() == []
    obs.set_enabled(False)
    try:
        with obs.span("off.module"):
            obs.count("n", 1)
        assert not [x for x in obs.records() if x.name == "off.module"]
    finally:
        obs.set_enabled(True)


def test_span_decorates_every_call():
    r = obs.Recorder()

    @r.span("f")
    def f(x):
        r.count("calls")
        return x + 1

    assert f(1) == 2 and f(2) == 3
    assert [x.counts for x in r.records()] == [{"calls": 1}, {"calls": 1}]


def test_span_is_a_host_event_of_a_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("obs.test_span"):
            jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    hits = [(plane.name, ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for ev in line.events
            if ev.name == "obs.test_span"]
    assert len(hits) == 1
    assert hits[0][0].startswith("/host:") and hits[0][1] > 0


def _plan_batch(grid: str, cp: int = 2):
    from repro.planner import (emit_visit_tables, encode_plan_batch,
                               flashcp_plan, plan_many)

    lens = [np.array([700, 200, 100, 24]), np.array([1024])]
    plans = plan_many(lambda l: flashcp_plan(l, cp)[0], lens, workers=1)
    stack, _ = encode_plan_batch(plans, align=128, workers=1)
    tabs = emit_visit_tables(stack["doc"], stack["pos"], stack["gath_doc"],
                             stack["gath_pos"], num_workers=cp,
                             strategy="flashcp", overlap="chunked",
                             grid=grid, cache=False)
    pairs = sum(int((d * (d + 1) // 2).sum()) for d in lens)
    return tabs, pairs


@pytest.mark.parametrize("grid", ["flat", "rect", "both"])
def test_planner_spans_and_table_counters(grid):
    from repro.table_layout import FLAG_VALID

    t0 = obs.records()[-1].end_ns if obs.records() else 0
    tabs, pairs = _plan_batch(grid)
    recs = [r for r in obs.records(since_ns=t0 + 1)
            if r.name.startswith("planner.")]
    assert [r.name for r in recs] == \
        ["planner.plan", "planner.encode", "planner.tables"]
    c = recs[-1].counts
    flags = [a for k, a in tabs.items() if k.endswith("_flags")]
    nvis = [(a, tabs[k[:-4] + "idx"]) for k, a in tabs.items()
            if k.endswith("_nvis")]
    assert c["tables.visits"] == \
        sum(int(np.count_nonzero(f & FLAG_VALID)) for f in flags) \
        + sum(int(n.sum()) for n, _ in nvis)
    assert c["tables.slots"] == sum(f.size for f in flags) \
        + sum(i.size for _, i in nvis)
    # each direction of each layout covers every pair once
    assert c["tables.pairs"] == pairs * {"flat": 2, "rect": 2,
                                         "both": 4}[grid]
    # both directions visit the same tiles
    assert c["tables.visits"] * 128 * 128 >= c["tables.pairs"]


def test_memoized_tables_are_counted_too():
    from repro.planner import emit_visit_tables

    doc = np.zeros((1, 512), np.int32)
    pos = np.tile(np.arange(512, dtype=np.int32), (1, 1))
    kw = dict(num_workers=1, strategy="llama3", overlap="none", grid="flat")
    t0 = obs.records()[-1].end_ns if obs.records() else 0
    emit_visit_tables(doc, pos, **kw)
    emit_visit_tables(doc, pos, **kw)      # a hit of the table cache
    first, second = [r for r in obs.records(since_ns=t0 + 1)
                     if r.name == "planner.tables"]
    assert first.counts == second.counts
    assert first.counts["tables.visits"] == 2 * (4 * 5 // 2)
    assert first.counts["tables.pairs"] == 2 * 512 * 513 // 2
