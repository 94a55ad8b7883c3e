"""Median over the window's requests of the driver's round trip (creation
to value) less the seconds the body took on the worker, in milliseconds:
the per-future path of capture, pickling, admission, frames, worker
decode and result relay. Requests whose bodies start or stop the profiler
are left out."""

import statistics


def read(obs: dict):
    vals = obs.get("future_overhead_ms") or []
    return statistics.median(vals) if vals else None
