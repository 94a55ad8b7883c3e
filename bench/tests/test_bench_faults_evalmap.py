"""The eval-map cell at test size: a sound run is correct, traced or not,
and each fault the cell can have, planted under the timed path, makes it
incorrect."""

import pytest

from bench.tests import faults
from bench.tests.helpers import SHORT, module, run_small


def test_sound_run_is_correct():
    line = run_small(SHORT)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 10 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "eval_tokens_per_s",
                                    "future_p95_ms"}
    assert list(line)[-1] == "checks"


def test_traced_run_reports_layers():
    line = run_small(SHORT, trace=True)
    assert line["correct"], line["checks"]
    assert {"future_overhead_ms", "worker_start_s"} <= set(line["metrics"])
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("fault", ["altered", "half"])
def test_fault_is_caught(fault, monkeypatch):
    mod = module(SHORT)
    monkeypatch.setattr(mod, "score", faults.score_altered if fault ==
                        "altered" else faults.score_half)
    line = run_small(SHORT)
    assert line["correct"] is False, line["checks"]
