"""Smoke test of the futures-driven training path on TPU chips.

    python chip_smoke.py                 # one chip: phases (a) and (b)
    python chip_smoke.py --four-chips    # four chips: phase (c) only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--four-chips]

This process is the driver: it plans ``cluster`` workers, each of which
owns one TPU chip, and never initialises a JAX backend itself.

(a) ``MultiPodDriver`` trains the full-width xlstm-125m (random weights
    from a seed) on one pod for 2 rounds of 2 local steps; the round loss
    must be finite and fall.
(b) On ``plan([spec("cluster", workers=1), "jax_async"])`` one task runs
    ``future_map`` over jitted full-width loss evaluations on the worker's
    ``jax_async`` plan, then the same calls on ``sequential``; the values
    must match bit for bit.
(c) ``--four-chips``: ``MultiPodDriver`` with 4 pods on
    ``plan("cluster", workers=4)``; the four workers must hold four
    distinct TPU chips, and one ``pod_round`` call run on several of them
    must give bit-identical deltas.

Without a TPU the script fails and prints no result. ``--rehearse`` runs
the same phases on CPU workers at the reduced config, to test the control
flow. The last line of a passing run is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
with the device as the workers report it.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: full-width train step batch x seq: the largest of 8 x 1024, 4 x 1024
#: whose step fits one v5e chip's 16 GB by its ahead-of-time memory
#: analysis (8 x 1024 needs ~19 GiB, 4 x 1024 ~10 GiB with donation)
BATCH, SEQ = 4, 1024
REHEARSE_BATCH, REHEARSE_SEQ = 2, 32
#: the whole run must end well inside the 1200 s the chip check allows
DEADLINE_S = 1100


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# -- task bodies (run on a cluster worker, never in this process) ----------

def chip_memory() -> dict:
    """The worker's device report plus its HBM peak and limit."""
    import jax
    from repro.core.backends.cluster_worker import device_report
    report = device_report()
    stats = jax.devices()[0].memory_stats() or {}
    report["peak_bytes"] = stats.get("peak_bytes_in_use")
    report["limit_bytes"] = stats.get("bytes_limit")
    return report


def forward_losses(arch: str, smoke: bool, seed: int, batch: int, seq: int,
                   n: int) -> dict:
    """``n`` jitted full-model loss evaluations as futures on this worker's
    nested plan (``jax_async``), then the same calls on ``sequential``."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.core import future_map, spec
    from repro.core.backends.cluster_worker import device_report
    from repro.core.planning import active_backend, use_nested_stack
    from repro.data import synth_batch
    from repro.models import Model

    cfg = get_arch(arch, smoke=smoke)
    model = Model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    loss_fn = jax.jit(lambda p, b: model.loss(p, b)[0])
    batches = [{k: jnp.asarray(v) for k, v in synth_batch(
        cfg, batch=batch, seq=seq, seed=seed, step=i).items()}
        for i in range(n)]
    t0 = time.perf_counter()
    jax.block_until_ready(loss_fn(params, batches[0]))
    compile_s = time.perf_counter() - t0

    def one(b):
        return loss_fn(params, b)

    nested = active_backend().name
    t0 = time.perf_counter()
    got = [float(x) for x in future_map(one, batches)]
    async_s = time.perf_counter() - t0
    with use_nested_stack((spec("sequential"),)):
        want = [float(x) for x in future_map(one, batches)]
    return {"nested": nested, "async": got, "sequential": want,
            "compile_s": compile_s, "async_s": async_s,
            "device": device_report()}


def pod_round_digest(*args, **kwargs) -> dict:
    """``pod_round`` plus a digest of its delta bytes and this worker's
    device report, so equal calls on different workers can be compared
    without shipping every delta home."""
    import hashlib

    import numpy as np
    from repro.core.backends.cluster_worker import device_report
    from repro.launch.train import pod_round
    out = pod_round(*args, **kwargs)
    h = hashlib.sha256()
    for d in out["delta"]:
        h.update(np.ascontiguousarray(d).tobytes())
    return {"digest": h.hexdigest(), "loss": out["loss"],
            "device": device_report()}


# -- phases (this process) ---------------------------------------------------

def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"[chip_smoke] FAILED: {msg}")


def train(pods: int, smoke: bool, batch: int, seq: int):
    from repro.launch.train import MultiPodDriver, PodRunConfig
    cfg = PodRunConfig(arch="xlstm-125m", smoke=smoke, pods=pods, rounds=2,
                       local_steps=2, batch=batch, seq=seq, seed=0)
    log(f"MultiPodDriver: xlstm-125m {'reduced' if smoke else 'full width'}"
        f", pods={pods} rounds={cfg.rounds} local_steps={cfg.local_steps} "
        f"batch={batch} seq={seq}")
    t0 = time.perf_counter()
    driver = MultiPodDriver(cfg)
    n_params = sum(p.size for p in driver.params)
    log(f"initial parameters from pod 0: {n_params} in {len(driver.params)} "
        f"arrays, {time.perf_counter() - t0:.3f} s (worker start included)")
    hist = driver.run()
    for rec in hist:
        log(f"round {rec['round']}: loss={rec['loss']!r} "
            f"wall_s={rec['wall_s']!r} step_s={rec['step_s']!r}")
    losses = [rec["loss"] for rec in hist]
    check(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    check(losses[1] < losses[0], f"loss did not fall: {losses}")
    return driver


def phase_a(smoke: bool, batch: int, seq: int) -> dict:
    from repro.core import future, value
    train(1, smoke, batch, seq)
    mem = value(future(chip_memory, label="chip-memory"))
    log(f"pod device: platform={mem['platform']} kind={mem['kind']!r} "
        f"count={mem['count']} chip={mem['chip']} "
        f"files={mem['device_files']} peak_bytes={mem['peak_bytes']} "
        f"limit_bytes={mem['limit_bytes']}")
    return mem


def phase_b(smoke: bool, batch: int, seq: int) -> dict:
    from repro.core import future, plan, spec, value
    plan([spec("cluster", workers=1), "jax_async"])
    out = value(future(forward_losses, "xlstm-125m", smoke, 1, batch, seq, 4,
                       label="forward-losses"))
    log(f"forward losses on the worker's {out['nested']} plan: "
        f"{out['async']!r} ({out['async_s']!r} s; compile "
        f"{out['compile_s']!r} s)")
    log(f"same calls on sequential: {out['sequential']!r}")
    check(out["nested"] == "jax_async",
          f"nested plan was {out['nested']}, not jax_async")
    check(all(math.isfinite(x) for x in out["async"]), "loss not finite")
    check(out["async"] == out["sequential"],
          "jax_async and sequential losses differ")
    return out["device"]


def phase_c(smoke: bool, batch: int, seq: int, need_tpu: bool) -> dict:
    from repro.core import active_backend, future, value
    from repro.launch.train import PodRunConfig
    driver = train(4, smoke, batch, seq)
    devices = active_backend().worker_devices()
    log(f"worker chips from the hellos: {devices!r}")
    if need_tpu:
        check(len(devices) == 4 and all(
            d and d["platform"] == "tpu" and d["count"] == 1
            for d in devices), "not four one-chip TPU workers")
        check(len({d["chip"] for d in devices}) == 4
              and len({tuple(d["device_files"]) for d in devices}) == 4,
              "the four workers do not hold four distinct chips")
    c: PodRunConfig = driver.cfg
    args = (c.arch, c.smoke, driver.params, 0, 0, c.pods, c.local_steps,
            c.batch, c.seq, c.lr, c.seed)
    fs = [future(pod_round_digest, *args, label=f"same-args-{i}")
          for i in range(4)]
    outs = value(fs)
    for o in outs:
        log(f"pod_round on pid {o['device']['pid']} chip "
            f"{o['device']['chip']}: loss={o['loss']!r} delta "
            f"sha256={o['digest']}")
    check(len({o["device"]["pid"] for o in outs}) >= 2,
          "the equal pod_round calls did not spread over two workers")
    check(len({o["digest"] for o in outs}) == 1,
          "equal pod_round arguments gave different deltas")
    # the device kind as the workers report it; the count is every chip
    # the four workers hold together
    return dict(outs[0]["device"],
                count=sum(d["count"] for d in devices if d) or len(devices))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip multi-pod phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="run on CPU workers at the reduced config")
    args = ap.parse_args()

    def _deadline():
        log(f"FAILED: not done after {DEADLINE_S} s")
        os._exit(3)              # workers see their driver socket close
    timer = threading.Timer(DEADLINE_S, _deadline)
    timer.daemon = True
    timer.start()

    from repro.cache import enable_compile_cache
    from repro.core import shutdown
    from repro.core.backends.launchers import host_tpu_chips
    need = 4 if args.four_chips else 1
    if not args.rehearse:
        chips = host_tpu_chips()
        log(f"TPU chips this process may hand out for JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}: {chips}")
        check(len(chips) >= need,
              f"needs {need} TPU chip(s), found {len(chips)}")
    log(f"compile cache: {enable_compile_cache()}")
    smoke = args.rehearse
    batch, seq = ((REHEARSE_BATCH, REHEARSE_SEQ) if smoke else (BATCH, SEQ))
    try:
        if args.four_chips:
            device = phase_c(smoke, batch, seq, need_tpu=not args.rehearse)
        else:
            device = phase_a(smoke, batch, seq)
            shutdown()               # phase (b) needs the chip for its worker
            device_b = phase_b(smoke, batch, seq)
            check(device_b["kind"] == device["kind"],
                  "phases ran on different device kinds")
    finally:
        shutdown()
    from jax._src import xla_bridge
    check(not xla_bridge.backends_are_initialized(),
          "the driver process initialised a JAX backend")
    if not args.rehearse:
        check(device["platform"] == "tpu",
              f"work ran on {device['platform']}, not on a TPU")
    result = {"ok": True, "device": {"platform": device["platform"],
                                     "kind": device["kind"],
                                     "count": device["count"]}}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
