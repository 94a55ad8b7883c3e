"""Future API conformance suite (the paper's future.tests analogue).

Every backend must produce the same values, the same relayed output and
conditions, the same exceptions, and the same RNG streams. This file is
parametrized over all registered backends; a new backend is conformance-
tested by merely existing in the registry.
"""

import os
import warnings

import pytest

import repro.core as rc
from repro.core import (first, first_successful, future, future_map, gather,
                        value)

BACKENDS = [
    ("sequential", "sequential", {}),
    ("threads", "threads", {"workers": 2}),
    ("processes", "processes", {"workers": 2}),
    ("cluster", "cluster", {"workers": 2}),
    # the same TCP backend bootstrapping its own fleet through the launcher
    # subsystem (LocalLauncher is the hosts=N default): the full conformance
    # surface must hold on *launched* workers, not just pre-connected ones
    ("cluster+local-launcher", "cluster", {"hosts": 2}),
    # the same launched fleet behind the full transport-security preamble:
    # TLS on every socket (driver listener, worker dial, peer fetch) plus
    # the shared-token handshake. The entire conformance surface must be
    # indistinguishable from plaintext. ``_secure`` resolves to real
    # credentials in the fixture (the cert is generated at runtime).
    ("cluster+tls+token", "cluster", {"hosts": 2, "_secure": True}),
    ("jax_async", "jax_async", {}),
    # the cooperative event-loop backend: sync bodies run as one segment on
    # the loop thread, async bodies are driven segment-by-segment — the full
    # relay/RNG/error surface must be indistinguishable from the others
    ("asyncio", "asyncio", {}),
]

IDS = [b[0] for b in BACKENDS]


def resolve_backend_kwargs(kw):
    """Expand fixture-only sentinels into real plan() kwargs — any suite
    reusing BACKENDS for its own matrix must route kwargs through here."""
    kw = dict(kw)
    if kw.pop("_secure", False):
        from _cluster_harness import ephemeral_tls
        kw.update(token="conformance-secret", tls=ephemeral_tls())
    return kw


@pytest.fixture(params=BACKENDS, ids=IDS)
def backend(request):
    _id, name, kw = request.param
    rc.plan(name, **resolve_backend_kwargs(kw))
    yield name
    rc.shutdown()


def test_same_value(backend):
    x = 11
    assert value(future(lambda: x * 3)) == 33


def test_value_timeout(backend):
    """value(timeout=) bounds the wait: TimeoutError while unresolved,
    and the future stays valid — a later bounded wait still collects."""
    import time as _time
    f = future(lambda: _time.sleep(0.5) or 7)
    if not rc.resolved(f):                # eager backends resolve at create
        with pytest.raises(TimeoutError):
            f.value(timeout=0.05)
    assert f.value(timeout=30.0) == 7
    assert value(f, timeout=30.0) == 7    # module-level form, resolved path


def test_snapshot_semantics(backend):
    x = 1
    f = future(lambda: x + 100)
    x = 2  # noqa: F841
    assert value(f) == 101


def test_exception_relayed_as_is(backend):
    f = future(lambda: int("not-a-number"))
    with pytest.raises(ValueError):
        value(f)


def test_stdout_relay(backend, capsys):
    f = future(lambda: print("from-the-future") or 1)
    assert value(f) == 1
    assert "from-the-future" in capsys.readouterr().out


def test_warning_relay(backend):
    def body():
        warnings.warn("relayed-warning")
        return 2

    f = future(body)
    with warnings.catch_warnings(record=True) as wlist:
        warnings.simplefilter("always")
        assert value(f) == 2
    assert any("relayed-warning" in str(w.message) for w in wlist)


def test_rng_stream_invariance(backend):
    """seed=: same stream regardless of backend — compare against the
    sequential reference computed with the same session seed."""
    import jax
    rc.set_session_seed(1234)
    f = future(lambda key: float(jax.random.normal(key, ())), seed=True)
    got = value(f)
    expected = float(jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(1234), 0), ()))
    assert got == pytest.approx(expected)


def test_map_matches_sequential(backend):
    xs = list(range(7))
    assert future_map(lambda v: v * v, xs) == [v * v for v in xs]


@pytest.mark.parametrize("n", [4, 8192], ids=["inline", "payload-ref"])
def test_jax_array_global_stays_a_jax_array(backend, n):
    """A ``jax.Array`` global reaches the body as a ``jax.Array`` on every
    backend, in a fresh worker too: pickled with the task, or
    content-addressed as a payload ref (8192 float32 is above ~16 KiB)."""
    import jax.numpy as jnp
    x = jnp.arange(n, dtype=jnp.float32)

    def body():
        import jax
        y = x.at[0].set(5.0).block_until_ready()
        return (type(x).__name__, isinstance(x, jax.Array), float(y[0]),
                float(y[1:].sum()))

    kind, is_jax, first, rest = value(future(body))
    assert is_jax, kind
    assert first == 5.0 and rest == float(sum(range(1, n)))


def test_nested_parallelism_protection(backend):
    """A future created inside a future must default to the sequential
    (popped) stack — no N^2 explosion (paper §Nested parallelism)."""
    def outer():
        from repro.core import active_backend
        inner = future(lambda: 1)
        return (type(active_backend()).__name__, value(inner))

    name, v = value(future(outer))
    assert v == 1
    assert name == "SequentialBackend"


# --------------------------------------------------------------------------
# continuation combinators: same values / relay / exceptions on every backend
# --------------------------------------------------------------------------

def test_then_map_chain_value(backend):
    f = future(lambda: 10).then(lambda v: v + 1).map(lambda v: v * 2)
    assert value(f) == 22


def test_then_flattens_returned_future(backend):
    f = future(lambda: 3).then(lambda v: future(lambda: v * 7))
    assert value(f) == 21


def test_chain_propagates_parent_error(backend):
    trace = []
    f = future(lambda: int("nope")).then(lambda v: trace.append(v))
    with pytest.raises(ValueError):
        value(f)
    with pytest.raises(ValueError):      # errors re-raised at every value()
        value(f)
    assert trace == []                   # continuation skipped on error


def test_chain_raises_continuation_error(backend):
    f = future(lambda: 1).map(lambda v: [0][3])
    with pytest.raises(IndexError):
        value(f)


def test_chain_relays_whole_chain_stdout(backend, capsys):
    f = future(lambda: print("from-parent") or 2)
    g = f.map(lambda v: print("from-map") or v * 2)
    assert value(g) == 4
    out = capsys.readouterr().out
    assert out.index("from-parent") < out.index("from-map")


def test_recover_handles_error_and_passes_value(backend):
    bad = future(lambda: 1 / 0).recover(lambda exc: type(exc).__name__)
    assert value(bad) == "ZeroDivisionError"
    ok = future(lambda: 5).recover(lambda exc: -1)
    assert value(ok) == 5


def test_gather_values_and_error_propagation(backend):
    fs = [future(lambda i=i: i * i) for i in range(5)]
    assert value(gather(fs)) == [0, 1, 4, 9, 16]
    mixed = gather([future(lambda: 1), future(lambda: int("x"))])
    with pytest.raises(ValueError):
        value(mixed)


def test_first_returns_earliest_completion(backend):
    import time
    fast = future(lambda: "fast")
    slow = future(lambda: time.sleep(0.2) or "slow")
    assert value(first([fast, slow])) == "fast"


def test_first_successful_skips_failures(backend):
    f = first_successful([future(lambda: 1 / 0), future(lambda: "ok")])
    assert value(f) == "ok"


def test_first_successful_all_failures_propagates_first(backend):
    f = first_successful([future(lambda: 1 / 0),
                          future(lambda: [0][3])])
    with pytest.raises(ZeroDivisionError):   # lowest-index failure wins
        value(f)


# --------------------------------------------------------------------------
# streaming frontend: same values/ordering/semantics on every backend
# (the deeper stream behaviours — backpressure, unbounded sources, faults —
# live in test_stream.py; this is the conformance-matrix `stream` row)
# --------------------------------------------------------------------------

def test_stream_matches_map(backend):
    xs = list(range(10))
    s = rc.stream(iter(xs))              # generator input, never re-listed
    assert s.map(lambda v: v * 3, chunk=4).collect(ordered=True) \
        == [v * 3 for v in xs]
    assert 0 < s.stats["peak_in_flight"] <= s.stats["max_in_flight"]


def test_stream_reduce_over_generator(backend):
    got = (rc.stream(i for i in range(30))
           .filter(lambda v: v % 2 == 0)
           .map(lambda v: v + 1, chunk=5)
           .reduce(lambda a, b: a + b))
    assert got == sum(v + 1 for v in range(30) if v % 2 == 0)


def test_stream_error_relayed_as_is(backend):
    with pytest.raises(ValueError):
        rc.stream([1, 2, 3]).map(lambda v: int("nope")).collect()


# --------------------------------------------------------------------------
# remote-result chains (worker-to-worker dataflow): on cluster rows the
# intermediates stay worker-resident as content-addressed blobs and the
# hops are locality-routed — none of which may be visible in the values,
# the exception relay, or the RNG streams on any row
# --------------------------------------------------------------------------

_CHAIN_N = 1 << 14       # 128 KiB float64: crosses RESULT_REF_THRESHOLD


def test_remote_result_chain_values(backend):
    import numpy as np
    f = future(lambda: np.arange(_CHAIN_N, dtype=np.float64))
    g = f.then(lambda a: np.sqrt(a + 1.0)).map(lambda a: float(a.sum()))
    expected = float(np.sqrt(
        np.arange(_CHAIN_N, dtype=np.float64) + 1.0).sum())
    assert value(g) == expected          # bit-identical, not approx


def test_remote_result_chain_exception_and_recover(backend):
    import numpy as np
    f = future(lambda: np.arange(_CHAIN_N, dtype=np.float64))
    with pytest.raises(ValueError):      # relayed as-is through the hop
        value(f.then(lambda a: int("nope")))
    h = f.then(lambda a: int("nope")).recover(lambda e: type(e).__name__)
    assert value(h) == "ValueError"


def test_remote_result_chain_rng_stream_invariance(backend):
    """A locality-routed hop must not consume a stream index: a seeded
    future created *after* the chain draws the same stream on every row."""
    import jax
    import numpy as np
    rc.set_session_seed(77)
    f = future(lambda: np.arange(_CHAIN_N, dtype=np.float64))   # index 0
    assert value(f.then(lambda a: float(a[0]))) == 0.0          # no index
    tail = future(lambda key: float(jax.random.normal(key, ())),
                  seed=True)                                    # index 1
    expected = float(jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(77), 1), ()))
    assert value(tail) == pytest.approx(expected)


def test_stream_two_maps_fused_parity(backend):
    xs = list(range(12))
    s = (rc.stream(iter(xs))
         .map(lambda v: v * 2, chunk=3)
         .map(lambda v: float(v) + 0.5))
    assert s.collect(ordered=True) == [v * 2 + 0.5 for v in xs]
    assert s.stats["dispatched"] == 4    # adjacent maps fused into one hop


def test_stream_fused_seeded_maps_rng_parity(backend):
    """Fusion keeps per-stage RNG streams: the two-map seeded pipeline is
    bit-identical to the sequential reference on every row."""
    import jax

    def run():
        rc.set_session_seed(9)
        return (rc.stream(i for i in range(6))
                .map(lambda v, key: v + float(jax.random.uniform(key)),
                     seed=True, chunk=2)
                .map(lambda v, key: v * float(jax.random.uniform(key)),
                     seed=True)
                .collect(ordered=True))

    got = run()
    rc.plan("sequential")
    assert got == run()                  # bit-identical floats


# --------------------------------------------------------------------------
# shared-state subsystem (state.py): the same task-body code must see one
# linearizable driver-hosted service on every row — in-process singleton on
# sequential/threads/jax_async, pipe RPC on processes, socket RPC on the
# cluster rows. Nothing here is row-conditional.
# --------------------------------------------------------------------------

@pytest.mark.state
def test_state_semantics_tuple(backend):
    """put/get/version/cas/delete semantics observed from inside a task
    body, as one comparable tuple (versions survive delete; cas 'create'
    expects the post-delete counter)."""
    def body():
        from repro.core import state
        out = []
        out.append(state.put("sem.k", "a"))            # version 1
        out.append(state.put("sem.k", "b"))            # version 2
        out.append(state.get("sem.k"))
        out.append(state.version("sem.k"))
        ok, ver, _ = state.cas("sem.k", 2, "c")        # fresh -> commits v3
        out.append((ok, ver))
        ok2, ver2, cur2 = state.cas("sem.k", 2, "zz")  # stale -> refused
        out.append((ok2, ver2, cur2))
        out.append(state.delete("sem.k"))
        out.append(state.get("sem.k", None))           # gone, default
        out.append(state.version("sem.k"))             # counter survives
        ok3, ver3, _ = state.cas("sem.k", 3, "d")      # re-create at v4
        out.append((ok3, ver3))
        return out

    assert value(future(body)) == [
        1, 2, "b", 2, (True, 3), (False, 3, "c"), True, None, 3, (True, 4)]
    # the driver's direct (singleton) view agrees with the task's RPC view
    assert rc.state.read("sem.k") == ("d", 4)


@pytest.mark.state
def test_state_concurrent_update_is_exact_fold(backend):
    """state.update from N concurrent tasks == the sequential fold: no
    lost updates, no torn versions, on every backend."""
    n_tasks, per_task = 8, 4

    def body():
        from repro.core import state
        for _ in range(per_task):
            state.update("fold.acc", lambda v: (v or 0) + 1)
        return True

    fs = [future(body) for _ in range(n_tasks)]
    assert value(gather(fs)) == [True] * n_tasks
    assert rc.state.get("fold.acc") == n_tasks * per_task
    assert rc.state.version("fold.acc") == n_tasks * per_task


@pytest.mark.state
def test_state_cas_exactly_one_winner(backend):
    """Racing cas(expected_version=0) from every task: exactly one commit
    wins; the losers observe the winner's version and value."""
    def body(i):
        from repro.core import state
        ok, ver, cur = state.cas("race.k", 0, i)
        return (ok, ver)

    fs = [future(lambda i=i: body(i)) for i in range(6)]
    got = value(gather(fs))
    assert sum(1 for ok, _ in got if ok) == 1
    assert all(ver == 1 for _, ver in got)     # losers saw the winner
    assert rc.state.version("race.k") == 1


@pytest.mark.state
def test_state_wait_blocks_until_put(backend):
    """wait(key, min_version) parks a task until another task publishes.
    The putter future is created first so fully-eager rows (sequential,
    jax_async) publish before the waiter runs; on pool rows both are in
    flight and the waiter genuinely blocks."""
    def putter():
        import time
        from repro.core import state
        time.sleep(0.05)
        state.put("sig.k", "go")
        return True

    def waiter():
        from repro.core import state
        val, ver = state.wait("sig.k", 1, timeout=30)
        return (val, ver >= 1)

    p = future(putter)
    w = future(waiter)
    assert value(w) == ("go", True)
    assert value(p) is True


@pytest.mark.state
def test_state_wait_timeout_relayed(backend):
    from repro.core.state import StateTimeout

    def body():
        from repro.core import state
        try:
            state.wait("never.k", 1, timeout=0.1)
        except Exception as exc:                        # noqa: BLE001
            return type(exc).__name__
        return "no-error"

    assert value(future(body)) == StateTimeout.__name__


@pytest.mark.state
def test_state_large_value_rides_the_blob_path(backend):
    """A value above PAYLOAD_REF_THRESHOLD crosses as a content-addressed
    blob (driver->worker and worker->driver) and round-trips bit-exact."""
    import numpy as np
    arr = np.arange(1 << 15, dtype=np.float64)          # 256 KiB
    rc.state.put("big.down", arr)

    def body():
        import numpy as np
        from repro.core import state
        a = state.get("big.down")
        state.put("big.up", a * 2.0)
        return float(a.sum()), a.shape, a.dtype.str

    got = value(future(body))
    assert got == (float(arr.sum()), arr.shape, arr.dtype.str)
    back = rc.state.get("big.up")
    assert np.array_equal(back, arr * 2.0)


@pytest.mark.parametrize("name", ["processes", "cluster"])
def test_worker_isolation(name):
    """Process-family backends really do run elsewhere — including the TCP
    cluster backend (workers are separate interpreters behind sockets)."""
    rc.plan(name, workers=1)
    assert value(future(lambda: os.getpid())) != os.getpid()
    rc.shutdown()


def test_cluster_worker_death_self_heal_in_matrix():
    """The conformance story includes fault behaviour: a dying TCP worker
    surfaces as WorkerDiedError and the pool self-heals (same contract the
    processes backend honours in test_faults.py)."""
    rc.plan("cluster", workers=2)
    with pytest.raises(rc.WorkerDiedError):
        value(future(lambda: os._exit(41)))
    assert future_map(lambda x: x * 10, [1, 2, 3]) == [10, 20, 30]
    rc.shutdown()
