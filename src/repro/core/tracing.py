"""Spans of the per-future path: where a future's time goes between its
creation on the driver and its value there.

Off by default. Switch it on with ``REPRO_TRACE=1`` in the environment
(workers the driver launches inherit it) or :func:`enable`. A span is
``(task_id, name, parent, start_ns, end_ns)`` on ``time.perf_counter_ns()``
(``CLOCK_MONOTONIC`` on Linux, one clock for every process of a host);
the spans of one future share its id as ``task_id``. The process id and
host travel once per process. Spans are kept in a bounded ring per
process: :func:`spans` reads it, :func:`reset` empties it, :func:`dropped`
counts what the ring let go.

Three kinds of sites feed it:

* **Worker phases** (:class:`Phases`). A worker's task loop moves through
  ``worker.await_task`` -> ``worker.decode`` -> ``worker.run`` ->
  ``worker.encode`` -> ``worker.send`` and back; each boundary closes one
  span and opens the next, so the phases tile the worker's time. A phase
  is recorded when tracing is on, or when a profiler session is live in
  the process at the phase's start (``TraceMe.is_enabled()``, looked at
  only once ``jax`` is imported). In the second case it is also opened as
  a ``jax.profiler.TraceAnnotation`` of the same name, so a device trace
  carries it on the trace's own clock and names the device's idle gaps
  after it. Off, a site tests the switch, its loop's open phase and,
  once ``jax`` is imported, the profiler's flag: nothing is allocated.
  The phases of a task go home in its result frame
  (``CapturedRun.spans``); ``worker.send``, which ends after the frame is
  out, goes with the next one.
* **Driver stamps**: plain integer pairs the driver keeps on the future
  (``stream.queued``, ``future.ship``, ``future.relay``) and on its
  cluster handle (``cluster.dispatch``, ``cluster.result``) whatever the
  switch says. :func:`file` puts them in the ring when tracing is on
  here, or when the future's result came home carrying worker spans: so
  a worker traced by a profiler session gets the driver's side of the
  same futures with no switch.
* **Start-up stamps** (``worker.claim_chip``, ``worker.dial``), taken
  always by a cluster worker and sent in its hello; the driver files them
  with the worker's first traced result, or at the hello when on.

**Counters** are named values a task body records with :func:`count`
(numbers, or nested lists of them), under the same switch as a worker's
phases: on when tracing is on or a profiler session is live
(:func:`recording`), off otherwise, and then nothing is kept. They go
home with the task's phases in its result frame, are filed with its spans
under its id, and :func:`counters` reads them.

This module never imports ``jax``: the driver must not initialise a JAX
backend, and a worker without ``jax`` never pays for it.
"""

from __future__ import annotations

import collections
import os
import socket
import sys
import threading
import time
from typing import Iterable, NamedTuple

#: the switch; read at import from ``REPRO_TRACE``
ENABLED: bool = os.environ.get("REPRO_TRACE", "") not in ("", "0")

#: spans the ring holds per process
RING_SPANS = 1 << 16

#: the parent of each span: the span that leads to it on the path of one
#: future (``None`` for a root). Links from a worker's span to the
#: driver's, or back, are made only when both report the same host.
PARENT: "dict[str, str | None]" = {
    "stream.queued": None,
    "future.ship": None,
    "cluster.dispatch": "future.ship",
    "worker.await_task": None,
    "worker.decode": "cluster.dispatch",
    "worker.run": "worker.decode",
    "worker.encode": "worker.run",
    "worker.send": "worker.encode",
    "cluster.result": "worker.send",
    "future.relay": "cluster.result",
    "worker.claim_chip": None,
    "worker.dial": "worker.claim_chip",
}
#: links that cross from one process to another
_CROSS = frozenset({"worker.decode", "cluster.result"})

#: this process, sent once with its spans
PROC: "tuple[int, str]" = (os.getpid(), socket.gethostname())


class Counter(NamedTuple):
    task_id: "int | None"        # the future's id
    name: str
    value: object                # a number, or a nested list of numbers
    pid: int
    host: str
    label: "str | None"          # the future's label, where it was filed


class Span(NamedTuple):
    task_id: "int | None"        # the future's id; None for start-up
    name: str
    parent: "str | None"
    start_ns: int
    end_ns: int
    pid: int
    host: str
    label: "str | None"          # the future's label, where it was filed


_lock = threading.Lock()
_ring: "collections.deque" = collections.deque(maxlen=RING_SPANS)
_counters: "collections.deque" = collections.deque(maxlen=RING_SPANS)
_labels: "dict[int, str]" = {}
_dropped = 0
#: counters recorded in this process and not yet sent home (bounded: a
#: process with no worker loop never sends them)
_pending_counts: "collections.deque" = collections.deque(maxlen=1024)


def enable(on: bool = True) -> None:
    """Switch tracing on (or off) in this process and in the workers it
    launches from now on."""
    global ENABLED
    ENABLED = bool(on)
    if on:
        os.environ["REPRO_TRACE"] = "1"
    else:
        os.environ.pop("REPRO_TRACE", None)


def spans() -> "list[Span]":
    """The spans in this process's ring, oldest first."""
    with _lock:
        rows = list(_ring)
        labels = dict(_labels)
    return [Span(tid, name, parent, s, e, proc[0], proc[1], labels.get(tid))
            for tid, name, parent, s, e, proc in rows]


def counters() -> "list[Counter]":
    """The counters filed in this process, oldest first."""
    with _lock:
        rows = list(_counters)
        labels = dict(_labels)
    return [Counter(tid, name, v, proc[0], proc[1], labels.get(tid))
            for tid, name, v, proc in rows]


def recording() -> bool:
    """Whether :func:`count` keeps what it is given: tracing is on, or a
    profiler session is live in this process."""
    return ENABLED or _profiling()


def count(name: str, value) -> None:
    """Record counter ``name`` for the task running in this process; it
    goes home with the task's phases. Nothing is kept unless
    :func:`recording`."""
    if recording():
        _pending_counts.append((name, value))


def dropped() -> int:
    """Spans the ring let go since :func:`reset`."""
    return _dropped


def reset() -> None:
    """Empty the ring and the count of dropped spans."""
    global _dropped
    with _lock:
        _ring.clear()
        _counters.clear()
        _labels.clear()
        _dropped = 0


def _put(rows: "list[tuple]", counts: "list[tuple]", task_id: "int | None",
         label: "str | None") -> None:
    global _dropped
    with _lock:
        over = len(_ring) + len(rows) - RING_SPANS
        if over > 0:
            _dropped += over
        _ring.extend(rows)
        _counters.extend(counts)
        if label is not None and task_id is not None:
            _labels[task_id] = label
            if len(_labels) > RING_SPANS:
                del _labels[next(iter(_labels))]


def file(task_id: "int | None", label: "str | None", remote,
         *stamps: "dict | None") -> None:
    """Put one future's spans in this process's ring: each of ``stamps``
    maps span names to ``(start_ns, end_ns)`` taken in this process;
    ``remote`` is what came home with the result (:meth:`Phases.take`'s
    packs, or a hello's), whose spans and counters keep their own task
    ids.

    Stamps of another process are joined to this one's on the shared
    monotonic clock only when it reports this host: the cross-process
    parent links are then kept. A worker on another host keeps its spans,
    on its own clock, and loses those links; aligning clocks across hosts
    is out of scope."""
    joined = all(proc[1] == PROC[1] for proc, _ in remote or ())
    rows = []
    for st in stamps:
        for name, (s, e) in (st or {}).items():
            parent = PARENT.get(name)
            if not joined and name in _CROSS:
                parent = None
            rows.append((task_id, name, parent, s, e, PROC))
    counts = []
    for proc, items in remote or ():
        same = proc[1] == PROC[1]
        for item in items:
            if len(item) == 3:                       # a counter
                counts.append((*item, proc))
                continue
            tid, name, s, e = item
            parent = PARENT.get(name)
            if not same and name in _CROSS:
                parent = None
            rows.append((tid, name, parent, s, e, proc))
    _put(rows, counts, task_id, label)


def pack(items: "Iterable[tuple[str, int, int]]") -> list:
    """Start-up stamps ``(name, start_ns, end_ns)`` of this process, in
    the form :func:`file` takes as ``remote``."""
    return [(PROC, [(None, name, s, e) for name, s, e in items])]


# -- worker phases -----------------------------------------------------------

#: ``TraceMe.is_enabled`` and ``TraceAnnotation``, once ``jax`` is imported
_is_enabled = None
_annotation = None


def _profiling() -> bool:
    """Whether a profiler session is live in this process. Looks only
    once ``jax`` is imported; never imports it."""
    global _is_enabled, _annotation
    if _is_enabled is None:
        if "jax" not in sys.modules:
            return False
        try:
            from jax._src.lib import _profiler
            from jax.profiler import TraceAnnotation
        except Exception:                            # noqa: BLE001
            return False
        _annotation = TraceAnnotation
        _is_enabled = _profiler.TraceMe.is_enabled
    return _is_enabled()


class Phases:
    """The phases of one worker's task loop, made by the loop and used
    from its thread only. :meth:`enter` closes the open phase and opens
    the next; :meth:`take` hands the closed ones to the result frame."""

    #: closed phases kept unsent at most (a loop that never sends)
    KEEP = 256

    __slots__ = ("_open", "_pending", "_task", "dropped")

    def __init__(self):
        #: (name, task_id, start_ns, annotation) of the open phase
        self._open: "tuple | None" = None
        #: closed phases not yet sent: (task_id, name, start_ns, end_ns)
        self._pending: "list | None" = None
        #: the task the phases belong to
        self._task: "int | None" = None
        #: closed phases let go because too many were kept unsent
        self.dropped = 0

    def enter(self, name: "str | None", task_id: "int | None" = None
              ) -> None:
        """Close the open phase and open ``name`` (``None`` opens
        nothing). ``task_id``, given where a task frame is taken, names
        the task of the phases that follow; ``worker.await_task`` takes
        the id of the task whose frame closes it."""
        if task_id is not None:
            self._task = task_id
        if self._open is None and not ENABLED and not _profiling():
            return
        # the annotation is made before the stamp and opened right after
        # the old one closes, so the trace's clock and the stamps differ
        # by the cost of opening and closing alone
        live = name is not None and _profiling()
        ann = _annotation(name) if live else None
        t = time.perf_counter_ns()
        prev, self._open = self._open, None
        if prev is not None and prev[3] is not None:
            prev[3].__exit__(None, None, None)
        if ann is not None:
            ann.__enter__()
        if prev is not None:
            if self._pending is None:
                self._pending = []
            if len(self._pending) >= self.KEEP:
                del self._pending[0]
                self.dropped += 1
            self._pending.append((self._task if prev[1] is None
                                  else prev[1], prev[0], prev[2], t))
        if live or (name is not None and ENABLED):
            self._open = (name, None if name == "worker.await_task"
                          else self._task, t, ann)

    def take(self) -> "list | None":
        """The phases closed since the last call and the counters
        recorded since then, packed for the result frame as the task's
        (``None`` when there are none)."""
        if _pending_counts:
            counts = [(self._task, name, v) for name, v in _pending_counts]
            _pending_counts.clear()
            self._pending = (self._pending or []) + counts
        if self._pending is None:
            return None
        out, self._pending = [(PROC, self._pending)], None
        return out
