"""Median over the traced requests, taken per worker, of the time from
the end of one request's span ``worker.run`` to the start of the next
request's on the same worker, in milliseconds: the worker's side of the
device's idle gap between requests (``bench/spans.py``)."""

from bench import spans


def read(obs: dict):
    gaps = []
    for ds in spans.per_worker(spans.requests(), "worker.run").values():
        gaps += [b["worker.run"].start_ns - a["worker.run"].end_ns
                 for a, b in zip(ds, ds[1:])]
    return spans.median_ms(gaps)
