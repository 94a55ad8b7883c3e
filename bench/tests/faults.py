"""Faults planted under the timed path, for the tests that see
``correct`` come out false. Each replaces a function the timed path calls
and is shipped to the worker like the original."""

from __future__ import annotations


def _score():
    import importlib
    return importlib.import_module("bench.configs.evalmap-xlstm125m").score


def score_altered(index, **kwargs):
    """A request whose answer is altered where it is produced."""
    i, val, elapsed, tr = _score()(index, **kwargs)
    return i, val * 1.01, elapsed, tr


def score_half(index, *, seed, batch, seq):
    """A request scored over the first half of its positions only."""
    return _score()(index, seed=seed, batch=batch, seq=seq // 2)
