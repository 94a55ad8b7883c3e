"""Worker-process main loop for the process/cluster backend.

Protocol (length-prefixed pickles over a multiprocessing Pipe):

  parent -> worker : ("put", digest, blob)          content-addressed payload
                     ("task", task_id, blob, refs)  blob = shipped function,
                                                    refs = digests it needs
                     ("nak", digest)                parent cannot serve it
                     ("state_rep", rid, status, p)  shared-state reply
                     ("stop",)
  worker -> parent : ("need", digest)               blob-store backfill
                     ("progress", task_id, payload) immediateConditions, live
                     ("state", rid, op, args)       shared-state op (state.py)
                     ("result", task_id, run_blob)  CapturedRun (sanitized)
                     ("ready",)                     handshake after spawn

Large globals arrive as ``put`` payloads at most once (the parent tracks
what this worker holds) and live in a bounded LRU :class:`BlobStore`; a
task whose refs were evicted asks them back with ``need``. The same
execute/resolve path is shared with the TCP ``cluster_worker``.

Unexpected worker death is detected by the parent as EOF/broken pipe and
surfaces as WorkerDiedError — the paper's 'terminated R workers' case.
"""

from __future__ import annotations

import dataclasses
import os
import pickle
from typing import Any

from .blobstore import BlobStore


def _sanitize_run(run) -> Any:
    """Make a CapturedRun safely picklable (exception objects may not be)."""
    if run.error is not None:
        try:
            pickle.dumps(run.error)
        except Exception:                                   # noqa: BLE001
            run = dataclasses.replace(
                run, error=RuntimeError(
                    f"{type(run.error).__name__}: {run.error}"))
    try:
        pickle.dumps(run.value)
    except Exception as exc:                                # noqa: BLE001
        run = dataclasses.replace(
            run, value=None,
            error=RuntimeError(
                f"future value of type {type(run.value).__name__} "
                f"is not exportable from the worker: {exc}"),
        )
    return run


def execute_shipped(blob: bytes, emit, resolve_ref=None,
                    phases=None) -> Any:
    """Resolve one shipped task blob: unship the function (content-addressed
    globals resolved through ``resolve_ref``), evaluate under capture_run,
    sanitize for the trip home. Shared by the pipe (processes) and socket
    (cluster) workers so relay/error behaviour is identical. ``phases``
    (the loop's :class:`~repro.core.tracing.Phases`) enters
    ``worker.run`` around the function's call and ``worker.encode``
    after it."""
    import contextlib

    from ..conditions import capture_run
    from ..globals_capture import payload_resolver, unship_function
    from ..rng import call as rng_call, rng_scope

    with payload_resolver(resolve_ref) if resolve_ref is not None \
            else contextlib.nullcontext():
        # nested shipped functions (e.g. future_map's chunk runner carrying
        # the user fn as a default) rebuild during these loads and resolve
        # their PayloadRefs through the ambient resolver
        payload = pickle.loads(blob)
        fn = unship_function(payload["fn"], resolve_ref=resolve_ref)

    def call():
        if phases is None:
            return rng_call(fn, payload["args"], payload["kwargs"])
        phases.enter("worker.run")
        try:
            return rng_call(fn, payload["args"], payload["kwargs"])
        finally:
            phases.enter("worker.encode")

    with rng_scope(payload["seed_declared"]):
        run = capture_run(
            call,
            capture_stdout=payload["capture_stdout"],
            capture_conditions=payload["capture_conditions"],
            immediate_emit=emit,
        )
    return _sanitize_run(run)


def error_run(exc: Exception) -> Any:
    """A CapturedRun carrying an infrastructure-ish failure produced
    *outside* the user's function (e.g. an unservable payload digest)."""
    from ..conditions import CapturedRun
    return CapturedRun(error=exc)


def hold_result(store: BlobStore, run, threshold: "int | None" = None):
    """Worker-resident results: when ``run.value`` encodes (losslessly —
    never through the opt-in int8 codec) to ``threshold`` bytes or more,
    park the blob in this worker's own store under its content digest and
    replace the value with a :class:`~.blobstore.PayloadRef`. Returns
    ``(run, held)`` where ``held`` is the ``((digest, nbytes),)`` manifest
    for the result frame — empty when the value travels inline.

    The digest is computed over the *encoded blob* (``blob_digest``), so it
    names exactly the bytes a fetch/offer exchange will move — no driver/
    worker codec-configuration agreement required."""
    from . import transport
    from .blobstore import (PayloadRef, RESULT_REF_THRESHOLD, as_ndarray,
                            blob_digest)
    if threshold is None:
        threshold = RESULT_REF_THRESHOLD
    if run.error is not None:
        return run, ()
    value = run.value
    if value is None or isinstance(value, (bool, int, float)):
        return run, ()
    arr, _kind = as_ndarray(value)
    if arr is not None and arr.nbytes < threshold:
        return run, ()
    try:
        blob = transport.encode_payload(value, int8=False)
    except Exception:                                       # noqa: BLE001
        return run, ()                 # unencodable: ship inline as before
    if len(blob) < threshold:
        return run, ()
    digest = blob_digest(blob)
    store.put(digest, blob)
    run = dataclasses.replace(run, value=PayloadRef(digest))
    return run, ((digest, len(blob)),)


def ensure_refs(store: BlobStore, refs, send_need, recv_msg,
                peer_fetch=None, on_peer_fetched=None) -> "str | None":
    """Make sure every digest in ``refs`` is present in ``store``, asking
    the driver with ``send_need(digest)`` and pumping ``recv_msg()`` for the
    ``put`` answers. Returns ``"stop"`` if a stop frame arrived mid-backfill
    (propagated to the main loop), raises ChannelError if the driver naks.

    ``peer_fetch(digest) -> blob | None`` is tried first for each missing
    digest (the cluster worker's worker-to-worker fetch along the driver's
    location hints); digests a peer cannot serve fall through to the
    ``need`` driver-fallback path, so a partitioned or evicted peer costs
    one failed fetch, never a stuck task. ``on_peer_fetched(digest,
    nbytes)`` fires after each successful peer fetch — the cluster worker
    uses it to tell the driver it now holds a copy (replica promotion).
    """
    from ..errors import ChannelError
    missing = [d for d in refs if d not in store]
    if not missing:
        return None
    if peer_fetch is not None:
        still = []
        for d in missing:
            blob = peer_fetch(d)
            if blob is not None:
                store.put(d, blob)
                if on_peer_fetched is not None:
                    on_peer_fetched(d, len(blob))
            else:
                still.append(d)
        missing = still
        if not missing:
            return None
    for d in missing:
        send_need(d)
    waiting = set(missing)
    while waiting:
        msg = recv_msg()
        if msg[0] == "put":
            store.put(msg[1], msg[2])
            waiting.discard(msg[1])
        elif msg[0] == "nak":
            raise ChannelError(
                f"driver cannot serve payload {msg[1].hex()[:12]} "
                f"(blob evicted everywhere?)")
        elif msg[0] == "stop":
            return "stop"
        # anything else (e.g. a late frame) is ignored during backfill
    return None


def worker_main(conn, nested_stack_blob: bytes, session_seed: int,
                blob_store_bytes: "int | None" = None) -> None:
    """Entry point of a spawned worker process."""
    # Workers must see a *popped* plan stack (nested-parallelism protection)
    # and must never oversubscribe numeric libraries.
    os.environ.setdefault("OMP_NUM_THREADS", "1")
    os.environ.setdefault("XLA_FLAGS", "--xla_cpu_multi_thread_eigen=false")

    from .. import planning as plan_mod
    from .. import rng as rng_mod
    from .. import tracing
    from ..errors import ChannelError
    from ..state import PipeStateClient, state_context
    from .transport import become_executor

    become_executor()
    nested = pickle.loads(nested_stack_blob)
    plan_mod._TLS.stack = tuple(nested)         # worker-local plan stack
    rng_mod.set_session_seed(session_seed)

    store = BlobStore(blob_store_bytes)
    # shared-state client: task bodies calling `repro.core.state.*` reach
    # the parent's in-process StateService over this same pipe — the main
    # thread is the pipe's only reader, and only calls while inside a task
    st_client = PipeStateClient(conn, store=store)
    conn.send(("ready",))
    phases = tracing.Phases()
    phases.enter("worker.await_task")
    while True:
        try:
            msg = conn.recv()
        except (EOFError, OSError):
            return
        if msg[0] == "stop":
            return
        if msg[0] == "put":
            store.put(msg[1], msg[2])
            continue
        if msg[0] != "task":
            continue
        task_id, blob = msg[1], msg[2]
        refs = msg[3] if len(msg) > 3 else ()
        phases.enter("worker.decode", task_id)

        def emit(cond, _tid=task_id):
            try:
                conn.send(("progress", _tid, cond))
            except (OSError, ValueError):
                pass

        try:
            # pin the task's refs so a backfill put for one missing ref
            # cannot evict a sibling ref of the same task
            with store.pinned(refs):
                stopped = ensure_refs(store, refs,
                                      lambda d: conn.send(("need", d)),
                                      conn.recv)
                if stopped == "stop":
                    return
                with state_context(st_client):
                    run = execute_shipped(
                        blob, emit,
                        resolve_ref=lambda r: store.resolve(r.digest),
                        phases=phases)
        except (EOFError, OSError):
            return                           # channel gone mid-backfill
        except ChannelError as exc:
            run = error_run(exc)
        phases.enter("worker.send")
        run.spans = phases.take()
        try:
            conn.send(("result", task_id, run))
        except (OSError, ValueError):
            return
        phases.enter("worker.await_task")
