"""Median over the traced requests of span ``worker.decode``, in
milliseconds: the worker from taking a task frame to calling the task's
function (payloads, unpickling, the function rebuilt, capture set up;
``bench/spans.py``)."""

from bench import spans


def read(obs: dict):
    return spans.median_ms([spans.duration(d["worker.decode"])
                            for d in spans.requests().values()
                            if "worker.decode" in d])
