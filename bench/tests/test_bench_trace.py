"""The reduction from a device trace to busy time, idle share, program
time and breakdown."""

import gzip
import json
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def _hand():
    return {"host": [["trace_open", 0, 10], ["trace_close", 110, 111],
                     ["body", 10, 60], ["pjit", 60, 100]],
            "ops": [["fusion.1", 20, 30], ["fusion.2", 25, 40],
                    ["dot.3", 50, 55], ["late", 105, 130],
                    ["early", 0, 12]],
            "modules": [["jit_eval_loss(1)", 20, 40],
                        ["jit_eval_loss(1)", 50, 55],
                        ["jit_other(2)", 105, 130]]}


def test_hand_trace():
    got = trace.reduce(_hand(), ("jit_eval_loss", "jit_other"))
    # window 10..110; busy 10..12, 20..40, 50..55, 105..110 = 32 ns
    assert got["window_s"] == pytest.approx(100e-9)
    assert got["busy_s"] == pytest.approx(32e-9)
    assert got["idle_share"] == pytest.approx(0.68)
    assert got["programs"]["jit_eval_loss"] == {"s": pytest.approx(25e-9),
                                                "n": 2}
    assert got["programs"]["jit_other"]["s"] == pytest.approx(5e-9)
    assert got["top_ops"][0] == ["fusion.2", pytest.approx(15e-9)]
    # idle gaps: 55..105 (50, mostly in pjit), 40..50 (10, in body),
    # 12..20 (8, in body)
    assert got["idle_gaps"] == [["pjit", pytest.approx(50e-9)],
                                ["body", pytest.approx(10e-9)],
                                ["body", pytest.approx(8e-9)]]


def test_trace_needs_its_marks():
    ev = _hand()
    ev["host"] = ev["host"][2:]
    with pytest.raises(ValueError):
        trace.reduce(ev)


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5e chip: three full-width forward losses
    between the marks. The reduction gives what it gave there, and its
    busy time is the union of the op intervals, counted here by hand."""
    with gzip.open(os.path.join(DATA, "eval_trace_v5e.json.gz")) as f:
        ev = json.load(f)
    want = ev["meta"]["reduced"]
    got = trace.reduce(ev, ("jit_eval_loss",))
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-12)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-12)
    assert got["programs"] == want["programs"]
    assert got["programs"]["jit_eval_loss"]["n"] == 3
    lo = max(e for n, s, e in ev["host"] if n == trace.OPEN)
    hi = min(s for n, s, e in ev["host"] if n == trace.CLOSE)
    edges = sorted({t for _, s, e in ev["ops"] for t in (s, e)
                    if lo <= t <= hi} | {lo, hi})
    busy = sum(b - a for a, b in zip(edges, edges[1:])
               if any(s <= a and b <= e for _, s, e in ev["ops"]))
    assert got["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    assert 0 < got["busy_s"] < got["window_s"]
    times = [t for _, t in got["top_ops"]]
    assert times == sorted(times, reverse=True) and len(times) == 10
    gaps = [t for _, t in got["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= got["window_s"] - got["busy_s"] + 1e-12
