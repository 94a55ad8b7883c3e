"""The program's own spans of a traced run, as the per-layer readers take
them.

The readers run in the driver process after the window, where
``repro.core.tracing.spans()`` holds the spans of every future whose
result came home carrying worker spans: in a traced run, the requests
whose bodies ran while the worker's profiler session was live. A program
without that module, or a run that recorded nothing, gives no spans, and
every reader then returns ``None``.
"""

from __future__ import annotations

import statistics

#: the label prefix of the requests' futures (``stream(...).map(...)``)
LABEL = "stream.map0["


def _all() -> list:
    try:
        from repro.core import tracing
    except ImportError:
        return []
    return tracing.spans()


def requests() -> "dict[int, dict]":
    """Span name -> span, per request: the futures labelled
    ``stream.map0[...]`` that carry worker spans, less the first and the
    last of them, whose bodies start and stop the profiler."""
    tasks: "dict[int, dict]" = {}
    for s in _all():
        if s.label and s.label.startswith(LABEL):
            tasks.setdefault(s.task_id, {})[s.name] = s
    traced = sorted(t for t, d in tasks.items()
                    if any(n.startswith("worker.") for n in d))
    return {t: tasks[t] for t in traced[1:-1]}


def worker_of(task: dict) -> "tuple | None":
    """The worker process that ran a request, as ``(pid, host)``."""
    for name, s in task.items():
        if name.startswith("worker."):
            return (s.pid, s.host)
    return None


def per_worker(tasks: dict, name: str) -> "dict[tuple, list]":
    """Each worker's requests that have span ``name``, in the order that
    span started."""
    out: "dict[tuple, list]" = {}
    for d in tasks.values():
        if name in d:
            out.setdefault(worker_of(d), []).append(d)
    for ds in out.values():
        ds.sort(key=lambda d: d[name].start_ns)
    return out


def startup(name: str) -> list:
    """The workers' start-up spans called ``name``."""
    return [s for s in _all() if s.task_id is None and s.name == name]


def median_ms(values_ns: list) -> "float | None":
    return statistics.median(values_ns) / 1e6 if values_ns else None


def duration(s) -> int:
    return s.end_ns - s.start_ns
