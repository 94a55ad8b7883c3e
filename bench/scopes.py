"""Device time of named scopes (``jax.named_scope``) in a device trace.

The device trace names each operation after its HLO instruction; the
compiled program's HLO text says, in each instruction's ``op_name``
metadata, which scopes the operation was traced under. :func:`hlo_ops`
reads the instruction names of each scope from that text (on the worker,
from ``compiled.as_text()``), and :func:`stat_scopes` reads the scopes
an op event's own stats name, where the trace carries them; :func:`stop`
ends a trace as
``bench/trace.py``'s ``stop`` does and adds, for each scope, the seconds
of the union of its operations' intervals inside the window and inside
the named programs' own intervals.
"""

from __future__ import annotations

import bisect
import re
import time

from bench import trace

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?"
                    r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")


def hlo_ops(hlo_text: str, scopes: "tuple[str, ...]") -> "dict[str, list]":
    """Scope -> the names of the instructions traced under it."""
    out: "dict[str, set]" = {s: set() for s in scopes}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        path = "/" + m.group(2) + "/"
        for s in scopes:
            if f"/{s}/" in path:
                out[s].add(m.group(1))
    return {s: sorted(v) for s, v in out.items()}


def op_name(event_name: str) -> str:
    """The HLO instruction an op event of the trace names: its name up to
    the first ``' = '``, without the leading ``%``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def stat_scopes(data, scopes: "tuple[str, ...]") -> "dict[str, tuple]":
    """Op event name -> the scopes that a string stat of the first device
    op of that name runs through (where the trace gives an op's framework
    name); a second way to tie ops to scopes beside :func:`hlo_ops`."""
    out: "dict[str, tuple]" = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:") \
                or plane.name.startswith("/device:CPU"):
            continue
        for line in plane.lines:
            if line.name != trace.OPS_LINE:
                continue
            for e in line.events:
                if e.name not in out:
                    texts = ["/" + v + "/" for _, v in e.stats
                             if isinstance(v, str)]
                    out[e.name] = tuple(sc for sc in scopes
                                        if any(f"/{sc}/" in t
                                               for t in texts))
    return out


def scope_seconds(ev: dict, programs: "tuple[str, ...]",
                  ops: "dict[str, list]",
                  named: "dict[str, tuple] | None" = None
                  ) -> "dict[str, float]":
    """For each scope, seconds of the union of the intervals of its
    operations (by instruction name, ``ops``, or by event name,
    ``named``: :func:`stat_scopes`) that lie in the window and in an
    interval of one of the ``programs`` (module name prefixes)."""
    named = named or {}
    opens = [e for n, s, e in ev["host"] if n == trace.OPEN]
    closes = [s for n, s, e in ev["host"] if n == trace.CLOSE]
    lo, hi = max(opens), min(closes)
    mods = trace._union((s, e) for n, s, e in trace._clip(ev["modules"],
                                                          lo, hi)
                        if n.startswith(programs))
    starts = [a for a, _ in mods]

    def in_program(s, e):
        i = bisect.bisect_right(starts, s) - 1
        return i >= 0 and e <= mods[i][1]
    ops_in = [(n, s, e) for n, s, e in trace._clip(ev["ops"], lo, hi)
              if in_program(s, e)]
    out = {}
    for scope, names in ops.items():
        want = set(names)
        out[scope] = sum(e - s for s, e in trace._union(
            (s, e) for n, s, e in ops_in
            if op_name(n) in want or scope in named.get(n, ()))) / 1e9
    return out


def stop(programs: "tuple[str, ...]", ops: "dict[str, list]") -> dict:
    """``bench/trace.py``'s ``stop`` with ``scopes``: seconds per scope
    (:func:`scope_seconds`)."""
    import jax
    from jax.profiler import ProfileData
    with jax.profiler.TraceAnnotation(trace.CLOSE):
        pass
    t0 = time.perf_counter()
    xspace = trace._SESSION.pop().stop()
    t1 = time.perf_counter()
    data = ProfileData.from_serialized_xspace(xspace)
    ev = trace.events(data)
    out = trace.reduce(ev, programs)
    out["scopes"] = scope_seconds(ev, programs, ops,
                                  stat_scopes(data, tuple(ops)))
    out.update(stop_s=t1 - t0, read_s=time.perf_counter() - t1,
               xspace_bytes=len(xspace))
    return out
