"""The program's counters of a traced run, as the per-layer readers take
them.

The readers run in the driver process after the window, where
``repro.core.tracing.counters()`` holds the counters the task bodies
recorded while counters were recorded on their worker (in a traced run,
while the worker's profiler session was live) and that came home with
their results. A program without counters, or a run that recorded none,
gives none, and the readers then return ``None``.
"""

from __future__ import annotations

from bench import spans


def values(name: str) -> list:
    """The values of counter ``name`` of the requests' futures (labelled
    ``stream.map0[...]``), oldest first."""
    try:
        from repro.core import tracing
    except ImportError:
        return []
    read = getattr(tracing, "counters", None)
    if read is None:
        return []
    return [c.value for c in read() if c.name == name and c.label
            and c.label.startswith(spans.LABEL)]
