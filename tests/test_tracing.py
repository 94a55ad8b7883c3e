"""Spans of the per-future path (``repro.core.tracing``): nothing is
recorded with tracing off; on, a future's spans come home with its
result; a profiler session in a worker records the worker's spans on the
trace's own clock, where they name the device's idle gaps; the ring is
bounded."""

import os
import sys
import time

import pytest

import repro.core as rc
from repro.core import tracing

TESTS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(TESTS)
WORKER_PHASES = ("worker.await_task", "worker.decode", "worker.run",
                 "worker.encode", "worker.send")


@pytest.fixture
def off(monkeypatch):
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    monkeypatch.setattr(tracing, "ENABLED", False)
    tracing.reset()
    yield
    tracing.reset()


@pytest.fixture
def on(monkeypatch):
    # recorded so that the teardown puts back what was there
    monkeypatch.setenv("REPRO_TRACE", "")
    monkeypatch.setattr(tracing, "ENABLED", False)
    tracing.enable()
    assert tracing.ENABLED and os.environ["REPRO_TRACE"] == "1"
    tracing.reset()
    yield
    tracing.enable(False)
    tracing.reset()


def _sleep_then(x, s=0.01):
    time.sleep(s)
    return x


def test_off_records_nothing(off):
    rc.plan("cluster", workers=1)
    f = rc.future(_sleep_then, 42)
    assert rc.value(f) == 42
    assert f._run.spans is None
    assert rc.value(rc.future(_sleep_then, 1)) == 1
    assert tracing.spans() == [] and tracing.dropped() == 0


@pytest.mark.parametrize("backend", ["cluster", "processes"])
def test_on_spans_of_one_future(on, backend):
    rc.plan(backend, workers=1)
    f = rc.future(_sleep_then, 7, label="traced")
    assert rc.value(f) == 7
    # worker.send ends after the result frame is out: it comes home
    # with the next result
    assert rc.value(rc.future(_sleep_then, 8)) == 8
    mine = [s for s in tracing.spans() if s.task_id == f.id]
    assert {s.label for s in mine} == {"traced"}
    assert [s for s in tracing.spans() if s.label == "traced"] == mine
    by = {s.name: s for s in mine}
    want = {"future.ship", "future.relay", *WORKER_PHASES}
    if backend == "cluster":
        want |= {"cluster.dispatch", "cluster.result"}
    assert set(by) == want and len(mine) == len(want)
    for s in mine:
        assert s.parent == tracing.PARENT[s.name]
        assert s.start_ns <= s.end_ns
        if s.parent in by:
            assert by[s.parent].start_ns <= s.start_ns, s
        on_worker = s.name.startswith("worker.")
        assert (s.pid != os.getpid()) == on_worker, s
    # the worker's phases tile its time
    for a, b in zip(WORKER_PHASES, WORKER_PHASES[1:]):
        assert by[a].end_ns == by[b].start_ns
    assert by["worker.run"].end_ns - by["worker.run"].start_ns >= 10e6
    assert by["future.ship"].end_ns <= by["future.relay"].start_ns
    if backend == "cluster":
        start = {s.name for s in tracing.spans() if s.task_id is None}
        assert start == {"worker.claim_chip", "worker.dial"}


def _start_profiler():
    import _profiled
    _profiled.start()


def _stop_profiler():
    import _profiled
    return _profiled.host_events()


def test_worker_spans_land_on_the_profiler_clock(off, monkeypatch):
    """Tracing off: a profiler session in the worker alone records the
    worker's spans, as host events of the trace as well as in the
    recorder, and the driver files its side of the same futures."""
    from bench import trace
    monkeypatch.setenv("PYTHONPATH", os.pathsep.join(
        p for p in (TESTS, os.environ.get("PYTHONPATH")) if p))
    rc.plan("cluster", workers=1)
    start = rc.future(_start_profiler)
    rc.value(start)
    mid = []
    for i in range(5):
        # the driver dawdles between requests: the worker awaits its
        # next task for most of each gap between two runs
        time.sleep(0.02)
        mid.append(rc.future(_sleep_then, i, 0.005))
        assert rc.value(mid[-1]) == i
    stop = rc.future(_stop_profiler)
    host = rc.value(stop)
    ids = {f.id for f in mid}
    spans = tracing.spans()
    # every future whose result carried worker spans: from the start
    # task (its encode ran under the session) to the stop task
    assert {s.task_id for s in spans if s.name == "cluster.dispatch"} \
        == ids | {start.id, stop.id}

    # the recorder's worker spans from the start task's encode to the
    # stop task's decode are the trace's worker events, one for one
    by = {(s.task_id, s.name): s for s in spans}
    lo = by[start.id, "worker.encode"].start_ns
    hi = by[stop.id, "worker.decode"].end_ns
    rec = sorted((s for s in spans if s.name in WORKER_PHASES
                  and lo <= s.start_ns and s.end_ns <= hi),
                 key=lambda s: s.start_ns)
    ev = sorted((e for e in host if e[0] in WORKER_PHASES),
                key=lambda e: e[1])
    assert [s.name for s in rec] == [e[0] for e in ev]
    assert len(rec) == 2 + 5 * len(WORKER_PHASES) + 2
    offsets = []
    for s, (_, es, ee) in zip(rec, ev):
        assert abs((ee - es) - (s.end_ns - s.start_ns)) <= 50e3, s
        offsets.append(es - s.start_ns)
    assert max(offsets) - min(offsets) <= 100e3

    # device work in each run: every idle gap between two runs is named
    # after the worker's wait for its next task
    runs = [e for e in ev if e[0] == "worker.run"]
    got = trace.reduce({
        "host": [[trace.OPEN, runs[0][1], runs[0][1]],
                 [trace.CLOSE, runs[-1][2], runs[-1][2]]] + host,
        "ops": [["op", s, e] for _, s, e in runs], "modules": []})
    assert len(got["idle_gaps"]) == len(runs) - 1
    assert {g[0] for g in got["idle_gaps"]} == {"worker.await_task"}


def test_ring_is_bounded(on):
    n = tracing.RING_SPANS + 10
    tracing.file(1, "many", [(tracing.PROC, [(1, "worker.run", i, i + 1)
                                             for i in range(n)])])
    got = tracing.spans()
    assert len(got) == tracing.RING_SPANS and tracing.dropped() == 10
    assert got[0].start_ns == 10 and got[-1].start_ns == n - 1
    tracing.reset()
    assert tracing.spans() == [] and tracing.dropped() == 0

    # a worker loop that never sends keeps a bounded backlog too
    ph = tracing.Phases()
    for i in range(ph.KEEP + 5):
        ph.enter("worker.run", i)
    ph.enter(None)
    kept = ph.take()[0][1]
    assert len(kept) == ph.KEEP and ph.dropped == 5
    assert kept[-1][0] == ph.KEEP + 4
    assert ph.take() is None


def test_off_site_touches_nothing(off):
    """Off, a span site records nothing and imports nothing."""
    ph = tracing.Phases()
    before = set(sys.modules)
    for name in ("worker.decode", "worker.run", "worker.send"):
        ph.enter(name, 3)
    assert ph._open is None and ph.take() is None
    assert set(sys.modules) == before


def _count_then(x):
    from repro.core import tracing as tr
    tr.count("rows", [x, 2 * x])
    tr.count("pairs", x)
    return x


def test_counters_off_by_default(off):
    """Off, a body's counters keep nothing and nothing comes home."""
    assert not tracing.recording()
    tracing.count("rows", 1)
    assert not tracing._pending_counts
    rc.plan("cluster", workers=1)
    f = rc.future(_count_then, 3)
    assert rc.value(f) == 3
    assert f._run.spans is None and tracing.counters() == []


@pytest.mark.parametrize("backend", ["cluster", "processes"])
def test_counters_come_home_with_the_result(on, backend):
    rc.plan(backend, workers=1)
    f = rc.future(_count_then, 5, label="counted")
    assert rc.value(f) == 5
    got = [c for c in tracing.counters() if c.task_id == f.id]
    assert [(c.name, c.value) for c in got] == [("rows", [5, 10]),
                                                ("pairs", 5)]
    assert {c.label for c in got} == {"counted"}
    assert all(c.pid != os.getpid() for c in got)
    g = rc.future(_count_then, 6)
    assert rc.value(g) == 6
    assert [c.value for c in tracing.counters() if c.task_id == g.id] \
        == [[6, 12], 6]
    tracing.reset()
    assert tracing.counters() == []


def test_counters_come_home_with_a_traced_result(off, monkeypatch):
    """Tracing off: a profiler session in the worker alone records the
    counters of the bodies that run under it, and they come home with
    those futures' results."""
    monkeypatch.setenv("PYTHONPATH", os.pathsep.join(
        p for p in (TESTS, os.environ.get("PYTHONPATH")) if p))
    rc.plan("cluster", workers=1)
    assert rc.value(rc.future(_count_then, 1)) == 1
    rc.value(rc.future(_start_profiler))
    mid = [rc.future(_count_then, i) for i in (2, 3)]
    assert [rc.value(f) for f in mid] == [2, 3]
    rc.value(rc.future(_stop_profiler))
    assert rc.value(rc.future(_count_then, 4)) == 4
    got = tracing.counters()
    assert {c.task_id for c in got} == {f.id for f in mid}
    assert sorted(c.value for c in got if c.name == "pairs") == [2, 3]
