"""evalmap-xlstm125m: model evaluation mapped as futures, one per request.

``stream(requests).map(score, chunk=1).as_completed()`` on
``plan("cluster")`` with one worker per chip. Set-up starts the workers
and has each build the model's weights from the seed in one jitted call
and compile the jitted loss at the traffic's shape. The window is a closed
loop: the source yields request ``i`` while fewer than ``--seconds`` have
passed, and the stream admits it when fewer than ``max_in_flight`` are
outstanding. A request's latency runs from its creation on the driver
(when the stream pulls it) to its value on the driver.

After the window a sample of the resolved requests, drawn from the seed,
is scored again by the plain reference on the worker's chip, and the
median over the sample of the relative gap of the losses is compared. The
widest gap is logged beside it but not compared: a few requests read
gaps as wide as the control's from the program's own float32 rounding,
while the median keeps them apart (PERF.md).
"""

from __future__ import annotations

import functools
import time

import numpy as np

#: the jitted per-request loss, as the device trace names it
PROGRAMS = ("jit_eval_loss",)

#: limit of the median relative loss gap, between the program's and the
#: control's readings; PERF.md gives the readings it rests on
LIMITS = {"loss_gap_median": 1.2e-6}

# -- task bodies: run on the worker ------------------------------------------

def warm(c: dict, seed: int, batch: int, seq: int,
         traced: "list | None") -> dict:
    """Build the model's weights from the seed and compile the loss at the
    request shape; ``traced`` = ``[first, count]`` requests to trace."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.core.backends.cluster_worker import device_report
    from repro.models import Model

    from bench import arch, worker

    cfg = get_arch(arch.register(c))
    model = Model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))

    def eval_loss(params, tokens, labels):
        return model.loss(params, {"tokens": tokens, "labels": labels})[0]

    loss = jax.jit(eval_loss)
    tok = jnp.zeros((batch, seq), jnp.int32)
    float(loss(params, tok, tok))
    worker.STATE.clear()
    worker.STATE.update(cfg=cfg, params=params, loss=loss, traced=traced,
                  trace=None)
    return device_report()


def score(index: int, *, seed: int, batch: int, seq: int) -> tuple:
    """One request: its tokens from ``(seed, index)``, its loss, and the
    seconds this body took. The request that opens the traced range
    starts the profiler; the one that ends it stops the profiler and
    carries the trace's numbers."""
    import jax
    import jax.numpy as jnp
    from repro.data import synth_batch

    from bench import trace, worker

    st = worker.STATE
    tr = None
    traced = st["traced"]
    if traced and index == traced[0]:
        trace.start()
        st["trace"] = "on"
    elif traced and index == traced[0] + traced[1] \
            and st["trace"] == "on":
        tr = trace.stop(PROGRAMS)
        st["trace"] = "done"
    with jax.profiler.TraceAnnotation("body"):
        t0 = time.perf_counter()
        b = synth_batch(st["cfg"], batch=batch, seq=seq, seed=seed,
                        step=index)
        val = float(st["loss"](st["params"],
                                   jnp.asarray(b["tokens"]),
                                   jnp.asarray(b["labels"])))
        elapsed = time.perf_counter() - t0
    return index, val, elapsed, tr


def finish_trace() -> "dict | None":
    """Stop a trace the window left running."""
    from bench import trace, worker
    if worker.STATE.get("trace") != "on":
        return None
    worker.STATE["trace"] = "done"
    return trace.stop(PROGRAMS)


def release_program() -> None:
    import gc

    import jax

    from bench import worker
    worker.STATE.clear()
    jax.clear_caches()
    gc.collect()


def reference(c: dict, t: dict, seed: int, indices: list) -> list:
    from bench import xlstm_ref
    return xlstm_ref.eval_losses(c, t, seed, indices)


def numbers(got: list, ref: list) -> dict:
    """The compared number of losses ``got`` against the reference's."""
    from bench import compare
    return {"loss_gap_median": compare.median_rel_gap(got, ref)}


def control(c: dict, t: dict, seed: int) -> dict:
    """Readings on this worker's chip, against the reference, over as many
    requests as a run compares: the control (the reference in the
    precision below the configuration's, in the program's place) and a fault (half of
    each request's positions left out)."""
    from bench import compare
    from bench import xlstm_ref as R
    idx = list(range(t["checked_sample"]))
    ref = R.eval_losses(c, t, seed, idx)
    ctl = R.eval_losses(c, t, seed, idx, **R.control_precision(c))
    half = R.eval_losses(c, t, seed, idx, half=True)
    return {"control": numbers(ctl, ref), "half_batch": numbers(half, ref),
            "widest": {"control": compare.max_rel_gap(ctl, ref),
                       "half_batch": compare.max_rel_gap(half, ref)}}


# -- the cell: runs in the driver --------------------------------------------

def run(ctx) -> dict:
    from repro.core import active_backend, future, plan, stream, value

    from bench import compare, flops, worker

    c, t = ctx.config, ctx.traffic
    workers = ctx.workload["chips"]
    t0 = time.monotonic()
    plan("cluster", workers=workers)
    active_backend()
    worker_start_s = time.monotonic() - t0
    ctx.log(f"{workers} worker(s) ready in {worker_start_s:.3f} s")
    traced = t["traced_futures"] if ctx.trace else None
    dev = value(future(warm, c, ctx.seed, t["batch"], t["seq"], traced,
                       label="warm"))

    created: "dict[int, float]" = {}
    done: "list[tuple]" = []
    body = functools.partial(score, seed=ctx.seed, batch=t["batch"],
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
        tr = value(future(finish_trace, label="trace-stop"))

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
    value(future(release_program, label="release"))
    losses = {o[0]: o[1] for o, _ in done}
    rng = np.random.default_rng(np.random.SeedSequence(ctx.seed))
    sample = sorted(int(i) for i in rng.choice(
        sorted(losses), min(t["checked_sample"], len(losses)),
        replace=False))
    t_ref = time.monotonic()
    ref = value(future(reference, c, t, ctx.seed, sample, label="reference"))
    ctx.log(f"reference: {len(sample)} requests in "
            f"{time.monotonic() - t_ref:.3f} s")

    mine = [losses[i] for i in sample]
    got = numbers(mine, ref)
    ctx.log("widest loss gap (not compared): "
            f"{compare.max_rel_gap(mine, ref)!r}")
    checks = {n: (v, LIMITS[n]) for n, v in got.items()}
    tokens = len(done) * t["batch"] * t["seq"]
    layers = {
        "worker_start_s": worker_start_s,
        "future_overhead_ms": overhead_ms,
        "trace": tr,
        "eval_flops": flops.forward_flops(c, t["batch"], t["seq"]),
        "device_kind": dev["kind"],
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
