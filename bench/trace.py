"""Device traces: taken on a worker that holds the chip, reduced to numbers.

:func:`start` and :func:`stop` run inside the worker process (as futures
or inside a task body). The traced window runs from the end of the
``trace_open`` annotation that :func:`start` writes to the start of the
``trace_close`` annotation that :func:`stop` writes, both on the worker's
host clock, which the profiler aligns with the device's.

:func:`events` reads a trace into plain lists and
:func:`reduce` turns those into the window's length, the device's busy
time (the union of the intervals in which an XLA operation ran), the
device time of each named jitted program, the operations that took most
time, and the longest idle gaps labelled with what the host was doing.
"""

from __future__ import annotations

import time

#: a device plane's line of XLA operations and of whole XLA programs
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
OPEN, CLOSE = "trace_open", "trace_close"

#: the profiler session running in this process, if any
_SESSION: list = []


def start() -> None:
    """Start the profiler in this process and mark the window's start.
    The trace stays in memory: nothing is written to disk."""
    import jax
    from jax._src.lib import _profiler
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    _SESSION[:] = [_profiler.ProfilerSession(opts)]
    with jax.profiler.TraceAnnotation(OPEN):
        pass


def stop(programs: "tuple[str, ...]" = ()) -> dict:
    """Mark the window's end, stop the profiler, and return
    :func:`reduce` of the trace for the jitted ``programs`` (XLA module
    name prefixes, such as ``jit_eval_loss``), with the seconds that
    stopping (``stop_s``) and reading (``read_s``) the trace took."""
    import jax
    from jax.profiler import ProfileData
    with jax.profiler.TraceAnnotation(CLOSE):
        pass
    t0 = time.perf_counter()
    xspace = _SESSION.pop().stop()
    t1 = time.perf_counter()
    out = reduce(events(ProfileData.from_serialized_xspace(xspace)),
                 programs)
    out.update(stop_s=t1 - t0, read_s=time.perf_counter() - t1,
               xspace_bytes=len(xspace))
    return out


def events(data) -> dict:
    """The trace ``data`` (a ``jax.profiler.ProfileData``) as plain lists
    of ``[name, start_ns, end_ns]``: ``ops`` and ``modules`` from every
    device plane, ``host`` from the host plane, and the ``lines`` each
    device plane has."""
    out: dict = {"ops": [], "modules": [], "host": [], "lines": {}}
    for plane in data.planes:
        if plane.name.startswith("/device:") \
                and not plane.name.startswith("/device:CPU"):
            out["lines"][plane.name] = [line.name for line in plane.lines]
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    out[key].extend([e.name, e.start_ns, e.end_ns]
                                    for e in line.events)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out["host"].extend([e.name, e.start_ns, e.end_ns]
                                   for e in line.events)
    return out


def _union(intervals) -> "list[tuple[float, float]]":
    merged: "list[list[float]]" = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(evs, lo, hi):
    for name, s, e in evs:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield name, s, e


def reduce(ev: dict, programs: "tuple[str, ...]" = (), top: int = 10
           ) -> dict:
    """Numbers of the window between the ``trace_open`` and
    ``trace_close`` marks (seconds; shares as fractions)."""
    opens = [e for n, s, e in ev["host"] if n == OPEN]
    closes = [s for n, s, e in ev["host"] if n == CLOSE]
    if not opens or not closes:
        raise ValueError("the trace has no trace_open/trace_close marks")
    lo, hi = max(opens), min(closes)
    if hi <= lo:
        raise ValueError("the trace's window is empty")
    ops = list(_clip(ev["ops"], lo, hi))
    busy = _union((s, e) for _, s, e in ops)
    busy_ns = sum(e - s for s, e in busy)
    per_op: "dict[str, float]" = {}
    for name, s, e in ops:
        per_op[name] = per_op.get(name, 0.0) + (e - s)
    prog = {p: {"s": 0.0, "n": 0} for p in programs}
    for name, s, e in _clip(ev["modules"], lo, hi):
        for p in programs:
            if name.startswith(p):
                prog[p]["s"] += (e - s) / 1e9
                prog[p]["n"] += 1
    gaps, prev = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [(n, s, e) for n, s, e in ev["host"] if n not in (OPEN, CLOSE)]
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_ns / 1e9,
            "idle_share": 1.0 - busy_ns / (hi - lo),
            "programs": prog,
            "top_ops": [[n, t / 1e9] for n, t in sorted(
                per_op.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[_label(host, s, e), (e - s) / 1e9]
                          for s, e in gaps[:top]]}


def _label(host, lo, hi) -> str:
    """What the host did in ``[lo, hi]``: the innermost host event that
    covers at least half of the gap, else ``"no host event"``."""
    best, best_len = "no host event", None
    for name, s, e in host:
        if min(e, hi) - max(s, lo) >= 0.5 * (hi - lo):
            if best_len is None or e - s < best_len:
                best, best_len = name, e - s
    return best
