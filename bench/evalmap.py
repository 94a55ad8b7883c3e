"""The eval-map cell's run on the driver: model evaluation mapped as
futures, one per request.

``stream(requests).map(score, chunk=1).as_completed()`` on
``plan("cluster")`` with one worker per chip. Set-up starts the workers
and has each run the configuration's ``warm`` (weights from the seed, the
jitted loss compiled at the traffic's shape). The window is a closed
loop: the source yields request ``i`` while fewer than ``--seconds`` have
passed, and the stream admits it when fewer than ``max_in_flight`` are
outstanding. A request's latency runs from its creation on the driver
(when the stream pulls it) to its value on the driver.

After the window a sample of the resolved requests, drawn from the seed,
is scored again by the configuration's plain reference on the worker's
chip, after the program's state is freed, and the configuration's
``numbers`` of the two are compared with its ``LIMITS``.

A configuration module gives ``warm(c, seed, batch, seq, traced)``,
``score(index, *, seed, batch, seq)`` returning ``(index, loss,
body_seconds, trace_or_None)``, ``finish_trace()``, ``release_program()``,
``reference(c, t, seed, indices)``, ``numbers(got, ref)`` and ``LIMITS``.
"""

from __future__ import annotations

import functools
import time

import numpy as np


def run(ctx, mod, eval_flops: int) -> dict:
    """One run of the cell; ``eval_flops`` are a request's operations, for
    ``eval_step_mfu``."""
    from repro.core import active_backend, future, plan, stream, value

    from bench import compare, worker

    c, t = ctx.config, ctx.traffic
    workers = ctx.workload["chips"]
    t0 = time.monotonic()
    plan("cluster", workers=workers)
    active_backend()
    worker_start_s = time.monotonic() - t0
    ctx.log(f"{workers} worker(s) ready in {worker_start_s:.3f} s")
    traced = t["traced_futures"] if ctx.trace else None
    dev = value(future(mod.warm, c, ctx.seed, t["batch"], t["seq"], traced,
                       label="warm"))

    created: "dict[int, float]" = {}
    done: "list[tuple]" = []
    body = functools.partial(mod.score, seed=ctx.seed, batch=t["batch"],
                             seq=t["seq"])

    def requests():
        i = 0
        while True:
            now = time.monotonic()
            if now - w0 >= ctx.seconds:
                return
            created[i] = now
            yield i
            i += 1

    w0 = time.monotonic()
    setup_s = w0 - ctx.t0
    s = stream(requests(), max_in_flight=t["max_in_flight"]).map(
        body, chunk=t["chunk"])
    for out in s.as_completed():
        done.append((out, time.monotonic()))
    w1 = done[-1][1]
    window_s = w1 - w0
    tr = next((o[3] for o, _ in done if o[3] is not None), None)
    if ctx.trace and tr is None:
        tr = value(future(mod.finish_trace, label="trace-stop"))

    if tr:
        ctx.log(f"trace: stop {tr['stop_s']:.3f} s, read {tr['read_s']:.3f} s,"
                f" {tr['xspace_bytes']} bytes")
    lat = [(end - created[o[0]], o[2], o[0]) for o, end in done]
    lat_ms = sorted(1e3 * x for x, _, _ in lat)
    p95 = lat_ms[min(len(lat_ms) - 1, int(0.95 * len(lat_ms)))]
    if traced:
        lo, hi = traced[0], traced[0] + traced[1]
        lat = [x for x in lat if not lo <= x[2] <= hi]
    overhead_ms = [1e3 * (rt - body_s) for rt, body_s, _ in lat]
    ctx.log(f"window: {len(done)} requests in {window_s:.3f} s, p95 "
            f"{p95:.3f} ms, peak in flight {s.stats.get('peak_in_flight')}")

    mem = value(future(worker.device_memory, label="device-memory"))
    value(future(mod.release_program, label="release"))
    losses = {o[0]: o[1] for o, _ in done}
    rng = np.random.default_rng(np.random.SeedSequence(ctx.seed))
    sample = sorted(int(i) for i in rng.choice(
        sorted(losses), min(t["checked_sample"], len(losses)),
        replace=False))
    t_ref = time.monotonic()
    ref = value(future(mod.reference, c, t, ctx.seed, sample,
                       label="reference"))
    ctx.log(f"reference: {len(sample)} requests in "
            f"{time.monotonic() - t_ref:.3f} s")

    mine = [losses[i] for i in sample]
    got = mod.numbers(mine, ref)
    ctx.log("widest loss gap (not compared): "
            f"{compare.max_rel_gap(mine, ref)!r}")
    checks = {n: (v, mod.LIMITS[n]) for n, v in got.items()}
    tokens = len(done) * t["batch"] * t["seq"]
    layers = {
        "worker_start_s": worker_start_s,
        "future_overhead_ms": overhead_ms,
        "trace": tr,
        "eval_flops": eval_flops,
        "device_kind": dev["kind"],
        "config": c,
    }
    return {
        "end_to_end": {"setup_s": setup_s,
                       "eval_tokens_per_s": tokens / window_s,
                       "future_p95_ms": p95},
        "layers": layers, "trace": tr,
        "attempted": len(created), "failed": len(created) - len(done),
        "checks": checks,
        "device": {"platform": mem["platform"], "kind": mem["kind"],
                   "count": mem["count"] * workers,
                   "memory_peak_bytes": mem["peak_bytes"]},
    }
