"""Backend-invariant parallel RNG streams (paper §Proper parallel RNG).

The paper mandates L'Ecuyer-CMRG streams so that ``future(rnorm(3),
seed=TRUE)`` is *fully reproducible regardless of backend and worker count*.
JAX's counter-based threefry PRNG gives us the same guarantee with a simpler
construction: every future receives ``fold_in(session_key, future_counter)``
and every map-reduce **element** receives ``fold_in(session_key,
element_index)`` — indexed by element, never by worker or chunk, so results
are invariant to chunking and scheduling. The key is derived where the body
runs, from ``(session seed, index)``, never in the process that creates the
future.

Like the paper, an RNG draw inside a future that did *not* declare ``seed=``
triggers an informative :class:`RNGMisuseWarning` (detection is cheap: we
monkeypatch-count draws through this module's helpers).
"""

from __future__ import annotations

import threading
import warnings
from typing import NamedTuple

import jax
import numpy as np

from .errors import RNGMisuseWarning

_lock = threading.Lock()
_session_seed: int = 0
_future_counter: int = 0


def set_session_seed(seed: int) -> None:
    """Set the process-wide session seed (analogue of R's set.seed())."""
    global _session_seed, _future_counter
    with _lock:
        _session_seed = int(seed)
        _future_counter = 0


def next_stream_index() -> int:
    global _future_counter
    with _lock:
        idx = _future_counter
        _future_counter += 1
        return idx


def stream_key(index: int, seed: "int | None" = None) -> jax.Array:
    """Deterministic per-stream key: fold_in(session, index)."""
    base = _session_seed if seed is None else seed
    return jax.random.fold_in(jax.random.PRNGKey(base), index)


class StreamKey(NamedTuple):
    """A seeded future's RNG stream as ``(session seed, stream index)``.

    The creating process records only these two integers; the process that
    runs the body derives the key (:func:`call`). So a driver that only
    dispatches futures never initialises a JAX backend, and the key bits
    are the same on every backend."""
    seed: int
    index: int


def call(fn, args: tuple, kwargs: dict):
    """``fn(*args, **kwargs)`` with a :class:`StreamKey` ``key`` argument
    turned into its key here, in the process that runs the body."""
    key = kwargs.get("key")
    if isinstance(key, StreamKey):
        kwargs = dict(kwargs, key=stream_key(key.index, key.seed))
    return fn(*args, **kwargs)


# --------------------------------------------------------------------------
# Misuse detection
# --------------------------------------------------------------------------

class _RngFlag(threading.local):
    def __init__(self):
        self.declared: bool | None = None   # None = not inside a future
        self.touched: bool = False


_FLAG = _RngFlag()


class rng_scope:
    """Context manager installed by the evaluation harness around a future
    body. ``declared`` records whether the future was created with seed=."""

    def __init__(self, declared: bool):
        self.declared = declared

    def __enter__(self):
        self._prev = (_FLAG.declared, _FLAG.touched)
        _FLAG.declared, _FLAG.touched = self.declared, False
        return self

    def __exit__(self, *exc):
        touched = _FLAG.touched
        _FLAG.declared, _FLAG.touched = self._prev
        if touched and not self.declared:
            warnings.warn(
                "a future drew random numbers via repro.core.rng without "
                "declaring seed=; results may not be reproducible across "
                "backends (pass seed=True to future()/future_map())",
                RNGMisuseWarning, stacklevel=2)
        return False


def mark_rng_use() -> None:
    if _FLAG.declared is not None:
        _FLAG.touched = True


# Convenience draw helpers that participate in misuse detection. A future's
# body receives its stream key as the argument `key` when seed= is declared.

def normal(key: jax.Array, shape=(), dtype=np.float32) -> jax.Array:
    mark_rng_use()
    return jax.random.normal(key, shape, dtype)


def uniform(key: jax.Array, shape=(), dtype=np.float32, minval=0., maxval=1.):
    mark_rng_use()
    return jax.random.uniform(key, shape, dtype, minval, maxval)


def randint(key: jax.Array, shape, minval, maxval, dtype=np.int32):
    mark_rng_use()
    return jax.random.randint(key, shape, minval, maxval, dtype)
