"""plan(jax_async): futures backed by JAX's asynchronous dispatch.

JAX already *is* a future system at the device level: calling a jitted
function returns immediately with arrays that are promises over device
computation. This backend makes that explicit in Future-API terms —
``submit`` dispatches on the caller thread (cheap: tracing/compile cache hit
+ enqueue), ``resolved`` maps to ``is_ready()`` on the result leaves, and
``collect`` maps to ``block_until_ready()``.

This is the backend of choice *inside* a pod where the computation is one
SPMD program and host-level process parallelism would only add copies — the
analogue of the paper's observation that multithreading lives below the R
level, adapted to XLA.
"""

from __future__ import annotations

import threading
from typing import Any

import jax

from ..conditions import CapturedRun, capture_run
from .. import planning as plan_mod
from ..rng import call as rng_call, rng_scope
from .base import Backend, TaskSpec, register_backend


def _leaves(value: Any):
    return [x for x in jax.tree_util.tree_leaves(value)
            if isinstance(x, jax.Array)]


@register_backend("jax_async")
class JaxAsyncBackend(Backend):
    supports_immediate = True

    def __init__(self):
        self._cb_lock = threading.Lock()

    def free_slots(self) -> int:
        # Dispatch is asynchronous at the XLA level: submit() traces/
        # enqueues and returns immediately, the device stream queues depth-
        # unbounded. Admission therefore always grants one more slot (the
        # inherited try_submit always forwards to submit) — the caller's
        # own ``max_in_flight`` is what bounds outstanding work.
        # (dispatches_continuations stays False: submit() would run the
        # continuation inline on the *completion watcher* thread, which
        # must stay non-blocking — continuations take the bounced path.)
        return 1

    def submit(self, task: TaskSpec) -> CapturedRun:
        # Dispatch happens now (async); python-level errors are captured now,
        # device-level errors surface at collect() via block_until_ready.
        with plan_mod.use_nested_stack():
            with rng_scope(task.seed_declared):
                run = capture_run(
                    lambda: rng_call(task.fn, task.args, task.kwargs),
                    capture_stdout=task.capture_stdout,
                    capture_conditions=task.capture_conditions,
                )
        return run

    def poll(self, handle: CapturedRun) -> bool:
        if handle.error is not None:
            return True
        return all(leaf.is_ready() for leaf in _leaves(handle.value))

    def collect(self, handle: CapturedRun) -> CapturedRun:
        if handle.error is None:
            for leaf in _leaves(handle.value):
                leaf.block_until_ready()
        return handle

    def add_done_callback(self, handle: CapturedRun, cb) -> None:
        # Python-level work ran at submit; only device computation is
        # outstanding. XLA has no host-side completion hook, so one watcher
        # thread per handle parks in block_until_ready() and fans out to
        # every registered callback exactly once. The "fired" sentinel is
        # written under _cb_lock on *every* path that fires — including the
        # already-ready fast path, which used to leave _done_cbs unset, so
        # a registration racing it could spawn a second watcher and a
        # callback appended in that window was fanned out by both.
        fire = False
        with self._cb_lock:
            cbs = getattr(handle, "_done_cbs", None)
            if cbs == "fired":
                fire = True
            elif cbs is None:
                if self.poll(handle):
                    handle._done_cbs = "fired"
                    fire = True
                else:
                    handle._done_cbs = [cb]
                    threading.Thread(target=self._watch, args=(handle,),
                                     name="jax-done-watch",
                                     daemon=True).start()
            else:
                cbs.append(cb)
        if fire:
            cb(handle)

    def _watch(self, handle: CapturedRun) -> None:
        try:
            self.collect(handle)
        except Exception:                   # noqa: BLE001 — errored == resolved
            pass
        with self._cb_lock:
            pending = handle._done_cbs
            handle._done_cbs = "fired"
        for fn in pending:
            try:
                fn(handle)
            except Exception:               # noqa: BLE001 — one bad callback
                import traceback            # must not starve the others
                traceback.print_exc()

    def wait(self, handles, timeout=None):
        # Python-level work already ran at submit; only device computation
        # is outstanding. Untimed wait blocks on collect() of the first
        # handle (device errors stay inside collect(), surfacing at value()
        # like every other backend). XLA exposes no *timed* multi-wait, so a
        # finite timeout falls back to a bounded device-readiness poll —
        # confined here so multi-backend wait_any() slices stay bounded.
        import time
        handles = list(handles)
        ready = [h for h in handles if self.poll(h)]
        if ready or not handles or timeout == 0:
            return ready
        if timeout is None:
            try:
                self.collect(handles[0])
            except Exception:               # noqa: BLE001 — errored == resolved
                pass
            return [h for h in handles if self.poll(h)]
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            ready = [h for h in handles if self.poll(h)]
            if ready:
                return ready
            time.sleep(min(0.001, max(0.0, deadline - time.monotonic())))
        return [h for h in handles if self.poll(h)]
