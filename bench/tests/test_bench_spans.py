"""The readers of the program's spans: each gives what its definition
says from spans built by hand, and nothing from a program that recorded
none; in a traced run at test size the program's spans tile the worker's
time between two requests."""

import math
import statistics
import sys

import pytest

from bench import cell, spans
from bench.tests.helpers import SHORT, run_small

READERS = ("stream_queue_wait_ms", "driver_turnaround_ms",
           "worker_decode_ms", "worker_gap_ms", "worker_chip_claim_s")
MS = 1_000_000
DRIVER, WORKER = (1, "h"), (100, "h")


def _span(tid, name, s, e, proc, label):
    from repro.core.tracing import PARENT, Span
    return Span(tid, name, PARENT[name], int(s), int(e), *proc, label)


def _hand() -> list:
    """Five requests 10 ms apart on one worker (ids 10-14; the first and
    the last are left out), a future of another label, and two workers'
    chip claims."""
    rows = []
    for k in range(5):
        tid, base, label = 10 + k, 100 * MS + k * 10 * MS, f"stream.map0[{k}]"
        for name, s, e, proc in (
                ("stream.queued", base - (5 + k) * MS, base, DRIVER),
                ("cluster.dispatch", base - MS // 10,
                 base + k * MS // 100, DRIVER),
                ("worker.decode", base + MS - k * MS // 10, base + MS, WORKER),
                ("worker.run", base + MS, base + (3 + k) * MS, WORKER),
                ("cluster.result", base + 6 * MS, base + 7 * MS, DRIVER)):
            rows.append(_span(tid, name, s, e, proc, label))
    rows.append(_span(20, "worker.run", 0, 50 * MS, WORKER, "warm"))
    rows += [_span(None, "worker.claim_chip", 0, c * 1000 * MS, (p, "h"),
                   None) for p, c in ((100, 2), (101, 3))]
    return rows


def _read(bench, name):
    w = bench.workload(SHORT)
    return bench.reader(w, name).read({})


def test_readers_follow_their_definitions(monkeypatch):
    bench = cell.Bench(cell.ROOT)
    monkeypatch.setattr(spans, "_all", _hand)
    got = {n: _read(bench, n) for n in READERS}
    # requests 11-13 (k = 1..3)
    assert got["stream_queue_wait_ms"] == pytest.approx(7.0)
    assert got["worker_decode_ms"] == pytest.approx(0.2)
    # run k ends at 3 + k ms, run k+1 starts at 11 ms: 7 and 6 ms
    assert got["worker_gap_ms"] == pytest.approx(6.5)
    # result k starts at 6 ms, dispatch k+1 ends at 10 + (k+1)/100 ms
    assert got["driver_turnaround_ms"] == pytest.approx(
        statistics.median([4.02, 4.03]))
    assert got["worker_chip_claim_s"] == pytest.approx(3.0)


def test_no_spans_no_reading(monkeypatch):
    bench = cell.Bench(cell.ROOT)
    monkeypatch.setattr(spans, "_all", list)
    assert [_read(bench, n) for n in READERS] == [None] * len(READERS)
    # a program without the recorder
    monkeypatch.undo()
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    assert [_read(bench, n) for n in READERS] == [None] * len(READERS)


def test_traced_run_spans_tile_the_worker_gap():
    """For each traced request, the program's named spans cover all but
    at most a tenth of the worker's time from the end of its run to the
    start of the next run, as a median over requests."""
    from repro.core import tracing
    tracing.reset()
    line = run_small(SHORT, trace=True)
    for n in READERS:
        assert math.isfinite(line["metrics"][n]["value"]), n
    req = spans.requests()
    assert len(req) >= 5
    every = [s for d in req.values() for s in d.values()]
    runs = sorted((d["worker.run"] for d in req.values()),
                  key=lambda s: s.start_ns)
    shares = []
    for a, b in zip(runs, runs[1:]):
        lo, hi = a.end_ns, b.start_ns
        cuts = sorted((max(s.start_ns, lo), min(s.end_ns, hi))
                      for s in every if s.host == a.host
                      and s.name != "worker.run"
                      and min(s.end_ns, hi) > max(s.start_ns, lo))
        covered, edge = 0, lo
        for s, e in cuts:
            covered += max(0, e - max(s, edge))
            edge = max(edge, e)
        shares.append(covered / (hi - lo))
    assert statistics.median(shares) >= 0.9, shares
    # start-up: each worker's chip claim and dial, end to end, lie within
    # the driver's wait for its workers
    claims = {s.pid: s for s in spans.startup("worker.claim_chip")}
    dials = {s.pid: s for s in spans.startup("worker.dial")}
    assert claims and set(claims) == set(dials)
    for pid, c in claims.items():
        assert c.end_ns <= dials[pid].start_ns
        assert (dials[pid].end_ns - c.start_ns) / 1e9 \
            <= line["metrics"]["worker_start_s"]["value"]
    tracing.reset()
