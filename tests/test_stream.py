"""Streaming frontend (`core/stream.py`): bounded in-flight backpressure,
admission-controlled dispatch, unbounded sources, RNG invariance across
``max_in_flight``, and mid-stream fault retry.

The value/ordering/error conformance of ``stream`` across every backend
(including the ``cluster+local-launcher`` row) lives in the matrix in
``test_conformance.py``; this file asserts the *streaming* properties the
eager ``future_map`` never had.
"""

import itertools
import threading
import time

import pytest

import repro.core as rc
from _cluster_harness import HarnessLauncher
from repro.core import future_map, stream
from test_conformance import BACKENDS, IDS, resolve_backend_kwargs

_FAST = dict(heartbeat_interval=0.1, heartbeat_timeout=3.0,
             relaunch_backoff=0.05, relaunch_backoff_cap=0.2)


@pytest.fixture(params=BACKENDS, ids=IDS)
def backend(request):
    _id, name, kw = request.param
    rc.plan(name, **resolve_backend_kwargs(kw))
    yield name
    rc.shutdown()


# --------------------------------------------------------------------------
# the stream conformance row, across the full backend matrix
# --------------------------------------------------------------------------

def test_stream_pipeline_stages_full_matrix(backend):
    """filter -> batch -> map -> collect, generator input, on every
    backend (incl. launched cluster workers)."""
    s = stream(i for i in itertools.islice(itertools.count(), 24))
    got = (s.filter(lambda v: v % 3 != 0)
           .batch(4)
           .map(sum, chunk=2)
           .collect(ordered=True))
    kept = [v for v in range(24) if v % 3 != 0]
    want = [sum(kept[i:i + 4]) for i in range(0, len(kept), 4)]
    assert got == want
    assert s.stats["peak_in_flight"] <= s.stats["max_in_flight"]


def test_stream_unordered_collect_is_same_multiset(backend):
    xs = list(range(20))
    got = stream(xs).map(lambda v: v * v, chunk=3).collect(ordered=False)
    assert sorted(got) == [v * v for v in xs]


# --------------------------------------------------------------------------
# backpressure: peak in-flight <= max_in_flight, by counting harnesses
# --------------------------------------------------------------------------

def test_backpressure_bounds_concurrency_threads():
    """Counting harness (shared memory): with ``max_in_flight`` below the
    worker count, the number of *simultaneously executing* bodies — not
    just the pump's own accounting — stays within the bound."""
    rc.plan("threads", workers=4)
    lock = threading.Lock()
    state = {"cur": 0, "peak": 0}

    def body(x):
        with lock:
            state["cur"] += 1
            state["peak"] = max(state["peak"], state["cur"])
        time.sleep(0.005)
        with lock:
            state["cur"] -= 1
        return x

    s = stream(range(40), max_in_flight=2)
    assert s.map(body).collect() == list(range(40))
    assert state["peak"] <= 2
    assert 0 < s.stats["peak_in_flight"] <= 2
    rc.shutdown()


def test_backpressure_bounds_concurrency_processes():
    """Counting harness (wall-clock spans): bodies report their execution
    windows; the maximum overlap across workers stays within
    ``max_in_flight`` even though more workers are available."""
    rc.plan("processes", workers=3)

    def body(x):
        import time as _t
        t0 = _t.time()
        _t.sleep(0.02)
        return (t0, _t.time())

    s = stream(range(12), max_in_flight=2)
    spans = s.map(body).collect()
    events = sorted([(t0, 1) for t0, _ in spans]
                    + [(t1, -1) for _, t1 in spans])
    cur = peak = 0
    for _, step in events:
        cur += step
        peak = max(peak, cur)
    assert peak <= 2
    assert s.stats["peak_in_flight"] <= 2
    rc.shutdown()


def test_admission_never_exceeds_cluster_idle_set():
    """On the cluster backend the pump admits through the driver's idle
    worker set: in-flight futures never exceed the live worker count even
    when ``max_in_flight`` is larger."""
    rc.plan("cluster", workers=2)
    backend = rc.active_backend()
    assert backend.free_slots() == 2

    def body(v):
        import time as _t
        _t.sleep(0.02)       # long vs the dispatch loop: completions land
        return v + 1         # while the pump waits, not mid-admission

    s = stream(range(30), max_in_flight=16)
    assert s.map(body, chunk=3).collect() == [v + 1 for v in range(30)]
    # "in flight" = dispatched-not-yet-harvested, so completed futures
    # awaiting harvest count too — but admission keeps the peak near the
    # worker count (2 running + harvest lag), nowhere near the 16 cap
    assert s.stats["peak_in_flight"] <= 4
    assert backend.free_slots() == 2             # all returned to idle
    rc.shutdown()


# --------------------------------------------------------------------------
# unbounded / huge sources: O(in-flight) memory, never materialized
# --------------------------------------------------------------------------

def test_unbounded_generator_as_completed_breaks_cleanly():
    rc.plan("threads", workers=2)
    seen = []
    for v in stream(itertools.count()).map(lambda v: v, chunk=4) \
            .as_completed():
        seen.append(v)
        if len(seen) >= 50:
            break                        # GeneratorExit cancels the tail
    assert sorted(seen)[:4] == [0, 1, 2, 3]
    # the backend is still healthy after the abandoned stream
    assert rc.value(rc.future(lambda: "alive")) == "alive"
    rc.shutdown()


def test_million_element_generator_is_streamed_not_materialized():
    """The acceptance criterion: a 1M-element generator reduces with peak
    in-flight <= max_in_flight and the pump never pulls more than the
    in-flight window ahead of consumption (i.e. input is not
    materialized)."""
    rc.plan("threads", workers=2)
    n, chunk, mif = 1_000_000, 5_000, 4
    state = {"pulled": 0, "consumed": 0, "max_lead": 0}

    def source():
        for i in range(n):
            state["pulled"] += 1
            yield 1

    def note(a, b):
        state["consumed"] += chunk       # one completed chunk per fold step
        state["max_lead"] = max(state["max_lead"],
                                state["pulled"] - state["consumed"])
        return a + b

    s = stream(source(), max_in_flight=mif)
    got = (s.batch(chunk)                # 5k source elements -> one item
           .map(sum, chunk=1)           # one future per batch
           .reduce(note))               # fold batch sums as they complete
    assert got == n
    assert state["pulled"] == n                       # fully consumed...
    assert 0 < s.stats["peak_in_flight"] <= mif       # ...bounded in flight
    # never pulled more than the in-flight window + assembly slack ahead
    # (+1 chunk because reduce() seeds the fold without calling the op)
    assert state["max_lead"] <= (mif + 3) * chunk
    rc.shutdown()


# --------------------------------------------------------------------------
# RNG invariance across max_in_flight (the CMRG guarantee, streamed)
# --------------------------------------------------------------------------

def test_rng_invariant_to_max_in_flight_and_chunk():
    import jax

    def draw(x, key):
        return float(jax.random.normal(key, ()))

    rc.set_session_seed(11)
    ref = future_map(draw, [0] * 8, seed=True, chunks=1)

    for backend, kw in [("threads", {"workers": 2}),
                        ("processes", {"workers": 2})]:
        rc.plan(backend, **kw)
        for mif in (1, 3, 16):
            for chunk in (1, 3):
                rc.set_session_seed(11)
                got = (stream([0] * 8, max_in_flight=mif)
                       .map(draw, seed=True, chunk=chunk)
                       .collect(ordered=True))
                assert got == ref, (backend, mif, chunk)
        rc.shutdown()


def test_int_seed_offsets_element_indices_like_future_map():
    import jax

    def draw(x, key):
        return float(jax.random.normal(key, ()))

    rc.set_session_seed(3)
    ref = future_map(draw, [0] * 4, seed=7, chunks=2)
    rc.set_session_seed(3)
    got = stream([0] * 4).map(draw, seed=7, chunk=3).collect()
    assert got == ref


# --------------------------------------------------------------------------
# retries: FutureError-driven re-dispatch, mid-stream worker kill
# --------------------------------------------------------------------------

def test_stream_retries_dead_chunk_processes(tmp_path):
    rc.plan("processes", workers=2)
    marker = str(tmp_path / "chunk-died")

    def elem(x, _marker=marker):
        import os as _os
        if x == 3 and not _os.path.exists(_marker):
            open(_marker, "w").close()
            _os._exit(7)
        return x * 2

    s = stream(range(6), max_in_flight=2)
    assert s.map(elem, retries=2).collect() == [0, 2, 4, 6, 8, 10]
    assert s.stats["retried"] >= 1
    rc.shutdown()


def test_stream_retries_exhausted_raises():
    rc.plan("processes", workers=2)

    def die(x):
        import os as _os
        _os._exit(13)

    with pytest.raises(rc.WorkerDiedError):
        stream(range(4)).map(die, retries=1).collect()
    rc.shutdown()


@pytest.mark.launcher
def test_mid_stream_worker_kill_relaunch_and_retry(tmp_path):
    """A harness-injected SIGKILL lands mid-stream on the worker running a
    chosen element (deterministic: the body publishes its pid then
    blocks); the driver relaunches, the pump re-dispatches the chunk, and
    the stream completes correctly."""
    h = HarnessLauncher()
    rc.plan("cluster", hosts=2, launcher=h, **_FAST)
    marker = str(tmp_path / "victim-pid")
    watcher = h.kill_on_pidfile(marker)

    def elem(x, _marker=marker):
        import os as _os
        import time as _time
        if x == 3 and not _os.path.exists(_marker):
            with open(_marker, "w") as fh:
                fh.write(str(_os.getpid()))
                fh.flush()
            while True:                  # stay mid-task until the kill lands
                _time.sleep(0.05)
        return x * 2

    s = stream(range(6), max_in_flight=2)
    assert s.map(elem, retries=2).collect() == [0, 2, 4, 6, 8, 10]
    assert s.stats["retried"] >= 1
    watcher.join(timeout=10)
    assert watcher.killed is not None
    # SIGKILL delivery is asynchronous: give the doomed process a moment
    # to actually die and be reaped before asserting on its exit status
    deadline = time.monotonic() + 10
    while watcher.killed.poll() is None and time.monotonic() < deadline:
        time.sleep(0.02)
    assert watcher.killed.poll() is not None
    rc.shutdown()


# --------------------------------------------------------------------------
# semantics edges
# --------------------------------------------------------------------------

def test_reduce_empty_and_init():
    assert stream([]).map(lambda v: v).reduce(lambda a, b: a + b,
                                              init=42) == 42
    with pytest.raises(ValueError):
        stream([]).map(lambda v: v).reduce(lambda a, b: a + b)
    assert stream([5]).map(lambda v: v).reduce(lambda a, b: a + b) == 5


def test_streams_are_immutable_and_chainable():
    base = stream(range(6))
    doubled = base.map(lambda v: v * 2)
    assert len(base._ops) == 0 and len(doubled._ops) == 1
    assert doubled.collect() == [0, 2, 4, 6, 8, 10]


def test_batch_validates():
    with pytest.raises(ValueError):
        stream([1]).batch(0)


def test_future_map_is_stream_sugar_same_results():
    """future_map's public contract is preserved by the sugar: ordering,
    chunk plan, retry kwarg and values match the streamed equivalent."""
    rc.plan("threads", workers=3)
    xs = list(range(17))
    assert future_map(lambda v: v - 1, xs, chunks=5) \
        == [v - 1 for v in xs]
    assert future_map(lambda v: v - 1, xs) == [v - 1 for v in xs]
    assert future_map(lambda v: v, []) == []
    rc.shutdown()


# --------------------------------------------------------------------------
# Byte-denominated backpressure: stream(..., max_in_flight_bytes=)
# --------------------------------------------------------------------------

def test_max_in_flight_bytes_bounds_admission():
    """Peak in-flight estimated bytes never exceeds the budget, and the
    stats expose both the budget and the observed peak."""
    import numpy as np
    rc.plan("threads", workers=4)
    arrs = [np.zeros(1 << 18) for _ in range(12)]        # 2 MiB each
    budget = 5 * (1 << 21)                               # 10 MiB
    s = stream(arrs, max_in_flight_bytes=budget)
    assert s.map(lambda a: float(a.sum())).collect(ordered=True) \
        == [0.0] * 12
    assert 0 < s.stats["peak_in_flight_bytes"] <= budget
    assert s.stats["max_in_flight_bytes"] == budget
    rc.shutdown()


def test_max_in_flight_bytes_progress_guarantee():
    """A chunk larger than the whole budget is still admitted — alone.
    Byte backpressure throttles to one-at-a-time, never wedges."""
    import numpy as np
    rc.plan("threads", workers=2)
    arrs = [np.zeros(1 << 18) for _ in range(3)]
    s = stream(arrs, max_in_flight_bytes=1024)           # tiny budget
    assert s.map(lambda a: a.shape[0]).collect(ordered=True) \
        == [1 << 18] * 3
    assert s.stats["peak_in_flight"] == 1
    rc.shutdown()


def test_max_in_flight_bytes_composes_with_count_bound():
    """Both bounds hold at once; small items hit the count bound, the
    byte peak stays under budget."""
    rc.plan("threads", workers=4)
    s = stream(iter(range(40)), max_in_flight=3,
               max_in_flight_bytes=1 << 20)
    assert sorted(s.map(lambda v: v + 1, chunk=4).collect()) \
        == [v + 1 for v in range(40)]
    assert s.stats["peak_in_flight"] <= 3
    assert s.stats["peak_in_flight_bytes"] <= 1 << 20
    rc.shutdown()


# --------------------------------------------------------------------------
# completion-first dispatch: the chunk waiting in the queue is submitted as
# soon as a completion frees its slot, before the pump relays that result
# and creates the next future
# --------------------------------------------------------------------------

@pytest.fixture
def traced(monkeypatch):
    """The driver's stamps of every future land in the span ring."""
    from repro.core import tracing
    monkeypatch.setattr(tracing, "ENABLED", True)
    tracing.reset()
    yield tracing
    tracing.reset()


def _stamps_by_request(tracing, n: int) -> list:
    """Span name -> span for requests ``stream.map0[0..n-1]``, in order."""
    by: "dict[str, dict]" = {}
    for s in tracing.spans():
        if s.label and s.label.startswith("stream.map0["):
            by.setdefault(s.label, {})[s.name] = s
    return [by[f"stream.map0[{i}]"] for i in range(n)]


def _nap(x):
    import time as _t
    _t.sleep(0.02)
    return x


def _run_stream(terminal: str, xs, **kw):
    """One ``.map`` over ``xs`` through a sync or an async terminal;
    returns ``(values, stats)``."""
    import asyncio
    s = stream(xs, **kw)
    m = s.map(_nap)
    if terminal == "as_completed":
        return list(m.as_completed()), s.stats
    if terminal == "collect":
        return m.collect(), s.stats

    async def drain():
        if terminal == "as_completed_async":
            return [v async for v in m.as_completed_async()]
        return await m.collect_async()
    return asyncio.run(drain()), s.stats


_TERMINALS = ["as_completed", "collect", "as_completed_async",
              "collect_async"]


@pytest.mark.parametrize("terminal", _TERMINALS)
def test_queued_chunk_dispatched_before_relay(traced, terminal):
    """One worker, the default ``max_in_flight`` (2): a chunk always waits
    in the queue when the running one completes. Every chunk but the
    first is submitted on a completion, and each leaves the queue before
    the previous chunk's result is relayed."""
    rc.plan("threads", workers=1)
    n = 8
    try:
        vals, stats = _run_stream(terminal, range(n))
    finally:
        rc.shutdown()
    if terminal.startswith("collect"):
        assert vals == list(range(n))
    else:
        assert sorted(vals) == list(range(n))
    assert stats["max_in_flight"] == 2
    assert stats["dispatched"] == n and stats["retried"] == 0
    assert stats["dispatched_on_completion"] == n - 1
    # one worker: one chunk in flight at a time; a completed chunk that
    # awaits its harvest is not counted
    assert stats["peak_in_flight"] == 1
    reqs = _stamps_by_request(traced, n)
    for a, b in zip(reqs, reqs[1:]):
        assert b["stream.queued"].end_ns <= a["future.relay"].start_ns


@pytest.mark.parametrize("terminal", _TERMINALS)
def test_completion_dispatch_keeps_byte_budget(terminal):
    """The byte budget still bounds admission when chunks are submitted
    on completion: a budget of one element keeps one chunk admitted."""
    import numpy as np
    rc.plan("threads", workers=1)
    arrs = [np.full(1 << 12, i) for i in range(6)]      # 32 KiB each
    try:
        vals, stats = _run_stream(terminal, arrs,
                                  max_in_flight_bytes=arrs[0].nbytes)
    finally:
        rc.shutdown()
    assert sorted(int(v[0]) for v in vals) == list(range(6))
    assert stats["peak_in_flight"] == 1
    assert stats["peak_in_flight_bytes"] == arrs[0].nbytes
    # nothing is ever queued behind the running chunk
    assert stats["dispatched_on_completion"] == 0


def test_completion_dispatch_with_retry_processes(tmp_path):
    """A chunk whose worker dies is retried and the stream stays ordered
    while the queued chunks are submitted on completion."""
    rc.plan("processes", workers=1)
    marker = str(tmp_path / "chunk-died")

    def elem(x, _marker=marker):
        import os as _os
        import time as _t
        if x == 2 and not _os.path.exists(_marker):
            open(_marker, "w").close()
            _os._exit(7)
        _t.sleep(0.02)
        return x * 2

    s = stream(range(6))
    try:
        assert s.map(elem, retries=1).collect() == [0, 2, 4, 6, 8, 10]
    finally:
        rc.shutdown()
    assert s.stats["retried"] == 1
    assert s.stats["dispatched"] == 7
    assert s.stats["dispatched_on_completion"] >= 1
    assert s.stats["peak_in_flight"] == 1


def test_cluster_dispatches_next_task_before_relay(traced):
    """On one cluster worker, the next task's frames are sent before the
    driver relays the previous result: relay and the next future's
    creation are off the worker's critical path."""
    rc.plan("cluster", workers=1)
    n = 6

    def nap(x):                  # shipped by value to the worker
        import time as _t
        _t.sleep(0.02)
        return x

    try:
        assert list(stream(range(n)).map(nap).as_completed()) \
            == list(range(n))
    finally:
        rc.shutdown()
    reqs = _stamps_by_request(traced, n)
    for a, b in zip(reqs, reqs[1:]):
        assert b["cluster.dispatch"].end_ns <= a["future.relay"].start_ns
        assert a["cluster.result"].end_ns <= b["cluster.dispatch"].start_ns
