"""Multi-pod training driver: the Future API orchestrating pods.

This is the paper's programming model doing production work. Each *pod* is
a worker on the ``cluster`` backend; one training **round** dispatches one
future per pod. A pod runs H local optimizer steps on its data shard
(DiLoCo-style local updates — the cross-pod distributed-optimization trick
that replaces a per-step gradient all-reduce with one delta exchange per
round, matching slow inter-pod links), then returns its parameter delta.

The driver:
  * collects pod futures as they resolve, sleeping on one cross-backend
    ``Waiter`` per round — each pod backend *pushes* completion through
    ``add_done_callback`` (from the cluster driver's select loop) instead
    of the driver polling ``resolved()`` in a sleep loop;
  * re-dispatches on FutureError (node failure -> restart; the pod pool
    self-heals underneath), at most ``MAX_REDISPATCH`` times per pod per
    round, so a pod that dies every time raises instead of looping;
  * optionally races a speculative duplicate of the slowest pod
    (``future_either`` pattern = straggler mitigation);
  * compresses the delta exchange (int8 + error feedback per pod);
  * applies a Nesterov outer step and async-checkpoints via a future.

The driver never touches a device: the initial parameters come from a
future on pod 0 and their structure from ``jax.eval_shape``, and the
delta averaging runs in numpy. On a TPU host each pod worker owns one chip
(see ``core/backends/launchers.py``), so ``pods`` is at most the host's
chip count there.

Run: PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m --pods 2
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any

import numpy as np

from ..core import FutureError, Waiter, future, plan, value
from ..optim.compression import ErrorFeedback

#: re-dispatches of one pod within one round after a FutureError; a pod
#: that dies on every try (out of device memory, a runtime abort) raises
MAX_REDISPATCH = 3


@dataclasses.dataclass
class PodRunConfig:
    arch: str = "xlstm-125m"
    pods: int = 2
    rounds: int = 4
    local_steps: int = 5
    batch: int = 4
    seq: int = 64
    lr: float = 1e-3
    outer_lr: float = 0.7
    outer_momentum: float = 0.9
    compress: bool = True
    seed: int = 0
    ckpt_dir: str | None = None
    smoke: bool = True              # reduced configs on the CPU simulator
    straggler_timeout_s: float | None = None
    # fault injection (tests / examples)
    fail_marker: str | None = None  # kill one pod once, then recover
    straggle_pod: int | None = None
    straggle_s: float = 0.0


def _pod_state(arch: str, smoke: bool, lr: float, local_steps: int,
               seed: int) -> dict:
    """This pod worker's model, initial parameters and jitted step, built
    once and kept between rounds (pods are long-lived processes; re-jitting
    per round would dominate)."""
    import jax
    from repro.configs import get_arch
    from repro.models import Model
    from repro.optim import AdamWConfig
    from repro.train.step import make_train_step

    import repro.launch.train as _self
    cache = getattr(_self, "_POD_CACHE", None)
    ckey = (arch, smoke, lr, local_steps, seed)
    if cache is None or cache.get("key") != ckey:
        cfg = get_arch(arch, smoke=smoke)
        model = Model(cfg)
        template = jax.jit(model.init)(jax.random.PRNGKey(seed))
        # the state is rebuilt from the shipped parameters every round, so
        # the step may reuse its buffers
        step = jax.jit(make_train_step(
            model, AdamWConfig(lr=lr, warmup_steps=0,
                               total_steps=max(local_steps, 1))),
            donate_argnums=0)
        cache = {"key": ckey, "cfg": cfg, "template": template,
                 "step": step}
        _self._POD_CACHE = cache
    return cache


def init_params(arch: str, smoke: bool, lr: float, local_steps: int,
                seed: int) -> "list[np.ndarray]":
    """Executed inside a pod worker: the model's initial parameters as
    host float32 leaves, so the driver needs no device to get them."""
    import jax
    template = _pod_state(arch, smoke, lr, local_steps, seed)["template"]
    return [np.asarray(x, np.float32)
            for x in jax.tree_util.tree_leaves(template)]


def pod_round(arch: str, smoke: bool, params_flat: "list[np.ndarray]",
              round_idx: int, pod_id: int, n_pods: int,
              local_steps: int, batch: int, seq: int, lr: float,
              seed: int, fail_marker: str | None = None,
              straggle_s: float = 0.0) -> dict:
    """Executed inside a pod worker (shipped by the future machinery).

    ``fail_marker``: fault-injection hook — if set and the file does not
    exist yet, create it and kill this worker (simulated node failure; the
    retry path must converge). ``straggle_s``: artificial slowness.
    """
    import os as _os
    if fail_marker and not _os.path.exists(fail_marker):
        open(fail_marker, "w").close()
        _os._exit(43)                      # hard node failure
    if straggle_s:
        time.sleep(straggle_s)
    import jax
    import jax.numpy as jnp
    from repro.core import signal_progress
    from repro.data import synth_batch
    from repro.optim import adamw
    from repro.train.state import TrainState

    pod = _pod_state(arch, smoke, lr, local_steps, seed)
    cfg, step_fn = pod["cfg"], pod["step"]
    leaves, treedef = jax.tree_util.tree_flatten(pod["template"])
    params = jax.tree_util.tree_unflatten(
        treedef, [jnp.asarray(a, dtype=l.dtype)
                  for a, l in zip(params_flat, leaves)])
    state = TrainState(params, adamw.init_state(params))

    loss = float("nan")
    step_s = []                        # host clock; the first one compiles
    for i in range(local_steps):
        data = synth_batch(cfg, batch=batch, seq=seq, seed=seed,
                           step=round_idx * local_steps + i, shard=pod_id,
                           n_shards=n_pods)
        t0 = time.perf_counter()
        data = {k: jnp.asarray(v) for k, v in data.items()}
        state, metrics = step_fn(state, data)
        loss = float(metrics["loss"])          # waits for the device
        step_s.append(time.perf_counter() - t0)
    signal_progress(f"pod {pod_id} round {round_idx} loss={loss:.4f}")

    delta = [np.asarray(n, np.float32) - np.asarray(o, np.float32)
             for n, o in zip(jax.tree_util.tree_leaves(state.params),
                             params_flat)]
    return {"pod": pod_id, "round": round_idx, "loss": loss,
            "delta": delta, "tokens": local_steps * batch * seq,
            "step_s": step_s}


class MultiPodDriver:
    def __init__(self, cfg: PodRunConfig):
        self.cfg = cfg
        plan("cluster", workers=cfg.pods)
        import jax
        from repro.configs import get_arch
        from repro.models import Model
        self._model_cfg = get_arch(cfg.arch, smoke=cfg.smoke)
        shapes = jax.eval_shape(lambda: Model(self._model_cfg).init(
            jax.random.PRNGKey(cfg.seed)))
        self.treedef = jax.tree_util.tree_structure(shapes)
        self.params = value(future(
            init_params, cfg.arch, cfg.smoke, cfg.lr, cfg.local_steps,
            cfg.seed, label="init-params"))
        self.velocity = [np.zeros_like(p) for p in self.params]
        self.ef = [ErrorFeedback() for _ in range(cfg.pods)]
        self.history: list[dict] = []
        self.ckpt = None
        if cfg.ckpt_dir:
            from repro.checkpoint import CheckpointManager
            self.ckpt = CheckpointManager(cfg.ckpt_dir)

    # -- one communication round -------------------------------------------

    def _dispatch(self, pod: int, rnd: int, *, speculative: bool = False):
        c = self.cfg
        straggle = (c.straggle_s if (c.straggle_pod == pod
                                     and not speculative) else 0.0)
        return future(
            pod_round, c.arch, c.smoke, self.params, rnd, pod, c.pods,
            c.local_steps, c.batch, c.seq, c.lr, c.seed,
            fail_marker=c.fail_marker if pod == 0 else None,
            straggle_s=straggle,
            label=f"pod{pod}-round{rnd}{'+spec' if speculative else ''}")

    def run_round(self, rnd: int) -> dict:
        c = self.cfg
        # Each pod has a list of racing candidates (future_either pattern).
        # One Waiter spans the whole round: every candidate — initial,
        # re-dispatched after a node failure, or speculative — registers a
        # completion callback once, and the loop sleeps on one condition
        # variable until a pod backend pushes (select loop under cluster).
        fs: dict[int, list] = {pod: [self._dispatch(pod, rnd)]
                               for pod in range(c.pods)}
        owner = {id(f): pod for pod, cands in fs.items() for f in cands}
        waiter = Waiter(f for cands in fs.values() for f in cands)
        results: dict[int, dict] = {}
        redispatched = dict.fromkeys(fs, 0)
        t0 = time.time()
        speculated = False
        while len(results) < c.pods:
            # Before the speculation deadline, cap the wait so the straggler
            # check below fires on time; after it, block until a pod pushes.
            timeout = None
            if c.straggler_timeout_s and not speculated:
                timeout = max(0.0, c.straggler_timeout_s
                              - (time.time() - t0))
            done = waiter.wait(timeout)
            if c.straggler_timeout_s and not speculated and \
                    time.time() - t0 > c.straggler_timeout_s:
                # speculative duplicates for every unresolved pod
                for pod, cands in fs.items():
                    if pod not in results:
                        nf = self._dispatch(pod, rnd, speculative=True)
                        cands.append(nf)
                        owner[id(nf)] = pod
                        waiter.add(nf)
                speculated = True
            for f in done:
                pod = owner[id(f)]
                if pod in results:          # late loser: winner already in
                    continue
                try:
                    results[pod] = value(f)
                except FutureError as exc:
                    # node failure: pool self-healed; re-dispatch
                    redispatched[pod] += 1
                    if redispatched[pod] > MAX_REDISPATCH:
                        raise RuntimeError(
                            f"pod {pod} failed {redispatched[pod]} times in "
                            f"round {rnd}; last failure: {exc}") from exc
                    cands = fs[pod]
                    cands.remove(f)
                    nf = self._dispatch(pod, rnd)
                    cands.append(nf)
                    owner[id(nf)] = pod
                    waiter.add(nf)
                    continue
                for other in fs[pod]:       # first resolved wins
                    if other is not f:
                        other.cancel()

        # -- compressed delta averaging (int8 + EF), then outer Nesterov --
        deltas = []
        for pod in range(c.pods):
            d = {i: x for i, x in enumerate(results[pod]["delta"])}
            if c.compress:
                _, d = self.ef[pod].compress(d)
            deltas.append([np.asarray(d[i]) for i in range(len(d))])
        avg = [np.mean([d[i] for d in deltas], axis=0)
               for i in range(len(self.params))]
        m = self.cfg.outer_momentum
        for i, g in enumerate(avg):
            self.velocity[i] = m * self.velocity[i] + g
            self.params[i] = self.params[i] + c.outer_lr * (
                g + m * self.velocity[i])

        loss = float(np.mean([results[p]["loss"] for p in range(c.pods)]))
        rec = {"round": rnd, "loss": loss,
               "tokens": sum(results[p]["tokens"] for p in range(c.pods)),
               "wall_s": time.time() - t0,
               "step_s": [results[p]["step_s"] for p in range(c.pods)]}
        self.history.append(rec)
        return rec

    def run(self) -> list[dict]:
        for rnd in range(self.cfg.rounds):
            rec = self.run_round(rnd)
            print(f"round {rec['round']}: loss={rec['loss']:.4f} "
                  f"tokens={rec['tokens']}", flush=True)
            if self.ckpt:
                self.ckpt.save(rnd + 1,
                               {str(i): p for i, p in
                                enumerate(self.params)})
        if self.ckpt:
            self.ckpt.wait()
        return self.history

    def resize(self, pods: int) -> None:
        """Elastic scaling between rounds."""
        from ..core import active_backend
        backend = active_backend()
        backend.resize(pods)
        old = self.cfg.pods
        self.cfg.pods = pods
        if pods > old:
            self.ef.extend(ErrorFeedback() for _ in range(pods - old))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--pods", type=int, default=2)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=5)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--no-compress", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    from ..cache import enable_compile_cache
    enable_compile_cache()
    cfg = PodRunConfig(arch=args.arch, pods=args.pods, rounds=args.rounds,
                       local_steps=args.local_steps, batch=args.batch,
                       seq=args.seq, compress=not args.no_compress,
                       ckpt_dir=args.ckpt_dir)
    driver = MultiPodDriver(cfg)
    hist = driver.run()
    first, last = hist[0]["loss"], hist[-1]["loss"]
    print(f"loss {first:.4f} -> {last:.4f} over {len(hist)} rounds")


if __name__ == "__main__":
    main()
