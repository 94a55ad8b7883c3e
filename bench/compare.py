"""The numbers that decide ``correct``: gaps between the program and the
plain reference, each compared with a limit of its own."""

from __future__ import annotations

import statistics


def rel_gap(got: float, want: float) -> float:
    return abs(got - want) / abs(want)


def max_rel_gap(got, want) -> float:
    """The widest relative gap over paired values."""
    got, want = list(got), list(want)
    if len(got) != len(want) or not want:
        return float("inf")
    return max(rel_gap(g, w) for g, w in zip(got, want))


def median_rel_gap(got, want) -> float:
    """The median over paired values of the relative gap."""
    got, want = list(got), list(want)
    if len(got) != len(want) or not want:
        return float("inf")
    return statistics.median(rel_gap(g, w) for g, w in zip(got, want))
