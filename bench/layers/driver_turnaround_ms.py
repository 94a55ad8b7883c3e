"""Median over the traced requests, taken per worker, of the time from
the start of span ``cluster.result`` of one request to the end of span
``cluster.dispatch`` of the next request on the same worker, in
milliseconds: the driver reading a result, relaying it, and shipping and
sending the next task (``bench/spans.py``)."""

from bench import spans


def read(obs: dict):
    gaps = []
    for ds in spans.per_worker(spans.requests(), "cluster.dispatch").values():
        for a, b in zip(ds, ds[1:]):
            if "cluster.result" in a and b["cluster.dispatch"].start_ns \
                    >= a["cluster.result"].start_ns:
                gaps.append(b["cluster.dispatch"].end_ns
                            - a["cluster.result"].start_ns)
    return spans.median_ms(gaps)
