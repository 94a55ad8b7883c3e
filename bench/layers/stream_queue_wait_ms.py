"""Median over the traced requests of span ``stream.queued``, in
milliseconds: from the stream creating a request's future to the backend
admitting it (``bench/spans.py``)."""

from bench import spans


def read(obs: dict):
    return spans.median_ms([spans.duration(d["stream.queued"])
                            for d in spans.requests().values()
                            if "stream.queued" in d])
