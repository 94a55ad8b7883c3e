"""A profiler session kept by a worker process across task bodies, for
tests: :func:`start` in one body, :func:`host_events` in a later one.
Workers import this module from ``tests/`` on their ``PYTHONPATH``."""

from __future__ import annotations

_SESSION: list = []


def start() -> None:
    import jax
    from jax._src.lib import _profiler
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    _SESSION[:] = [_profiler.ProfilerSession(opts)]


def host_events() -> "list[list]":
    """Stop the session; its host events as ``[name, start_ns, end_ns]``,
    in nanoseconds from the session's start."""
    from jax.profiler import ProfileData
    data = ProfileData.from_serialized_xspace(_SESSION.pop().stop())
    return [[e.name, int(e.start_ns), int(e.end_ns)]
            for plane in data.planes if plane.name.startswith("/host:CPU")
            for line in plane.lines for e in line.events]
