"""Run one cell of the benchmark and print its result line.

    python3 bench/cell.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a
configuration under a traffic mix. Everything that belongs to one of them
is found by name: the configuration's sizes in ``bench/configs/<config>.json``
with its set-up, window, reference and comparison in
``bench/configs/<config>.py`` beside it; the traffic's parameters in
``bench/traffic/<cell>.json``; and each per-layer metric's reader in
``bench/layers/<metric>.py``. Adding one takes new files and new entries,
never an edit.

This process is the futures driver and never initialises a JAX backend:
the configuration plans ``cluster`` workers, one per TPU chip, and every
device operation, trace and reference runs in them. Without as many TPU
chips as the cell asks for, the run fails and prints no result.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, the device's busy and window seconds
and a breakdown. In both, the numbers compared with the plain reference
are printed with their limits: as the last lines on standard error and
under ``checks``, the last key of the result line.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: a run that compiles everything may take 1200 s; end before that
DEADLINE_S = 1150


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Context(types.SimpleNamespace):
    """What a configuration's ``run`` gets: ``seed``, ``seconds``,
    ``trace``, ``config`` (its JSON), ``traffic`` (the cell's JSON),
    ``workload`` (its entry), ``t0`` (process start, monotonic) and
    ``log``."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(name: str, path: str) -> types.ModuleType:
    """Import ``path`` as module ``name``. Configuration modules are named
    ``bench.configs.<config>``, so a worker with the checkout on its path
    imports the same file for the functions the driver ships to it."""
    if name in sys.modules and getattr(sys.modules[name], "__file__", None) \
            == os.path.abspath(path):
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


class Bench:
    """``BENCHMARK.json`` at ``root`` and the files it names."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.manifest = load_json(os.path.join(root, "BENCHMARK.json"))

    def workload(self, name: str) -> dict:
        for w in self.manifest["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config_entry(self, name: str) -> dict:
        for c in self.manifest["configs"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def _bench_dir(self, config: str) -> str:
        """The directory that holds ``configs/``, ``traffic/`` and
        ``layers/``: two levels above the configuration's file."""
        f = os.path.join(self.root, self.config_entry(config)["file"])
        return os.path.dirname(os.path.dirname(f))

    def config(self, name: str) -> dict:
        return load_json(os.path.join(self.root,
                                      self.config_entry(name)["file"]))

    def config_module(self, name: str) -> types.ModuleType:
        f = os.path.join(self.root, self.config_entry(name)["file"])
        return load_module(f"bench.configs.{name}",
                           os.path.splitext(f)[0] + ".py")

    def traffic(self, workload: dict) -> dict:
        return load_json(os.path.join(self._bench_dir(workload["config"]),
                                      "traffic", workload["name"] + ".json"))

    def reader(self, workload: dict, metric: str) -> types.ModuleType:
        return load_module(
            f"bench.layers.{metric}",
            os.path.join(self._bench_dir(workload["config"]), "layers",
                         metric + ".py"))

    def metrics(self, workload: dict, kind: str) -> "list[dict]":
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports:
        those that list it, and those that list no cell."""
        return [m for m in self.manifest[kind]
                if workload["name"] in m.get("workloads", [workload["name"]])]


def _finite(x) -> "float | None":
    return float(x) if x is not None and math.isfinite(float(x)) else None


def run(bench: Bench, workload: str, seed: int, seconds: float, trace: bool,
        *, config: "dict | None" = None, traffic: "dict | None" = None,
        t0: "float | None" = None) -> dict:
    """Run a cell and return its result line as a dict. ``config`` and
    ``traffic`` replace the files' contents (tests run at small sizes)."""
    w = bench.workload(workload)
    if bench.root not in os.environ.get("PYTHONPATH", "").split(os.pathsep):
        os.environ["PYTHONPATH"] = os.pathsep.join(
            p for p in (bench.root, os.environ.get("PYTHONPATH")) if p)
    ctx = Context(seed=seed, seconds=seconds, trace=trace, workload=w,
                  config=config or bench.config(w["config"]),
                  traffic=traffic or bench.traffic(w),
                  t0=T_START if t0 is None else t0, log=log)
    # the workers inherit the matrix product precision the configuration
    # states for float32
    if "matmul_precision" in ctx.config:
        os.environ["JAX_DEFAULT_MATMUL_PRECISION"] = \
            ctx.config["matmul_precision"]
    out = bench.config_module(w["config"]).run(ctx)

    device = dict(out["device"])
    line: dict = {"correct": None, "attempted": out["attempted"],
                  "failed": out["failed"], "metrics": {}, "device": device}
    if trace:
        t = out.get("trace")
        if not t:
            raise RuntimeError("a traced run produced no trace")
        device["busy_s"], device["window_s"] = t["busy_s"], t["window_s"]
        for m in bench.metrics(w, "per_layer"):
            v = _finite(bench.reader(w, m["name"]).read(out["layers"]))
            if v is not None:
                line["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        line["breakdown"] = {"device_ops": t["top_ops"][:10],
                             "idle_gaps": t["idle_gaps"][:10]}
    else:
        for m in bench.metrics(w, "end_to_end"):
            v = _finite(out["end_to_end"].get(m["name"]))
            if v is None:
                raise RuntimeError(f"end-to-end metric {m['name']} missing")
            line["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    checks = {n: {"value": _finite(v), "limit": lim}
              for n, (v, lim) in out["checks"].items()}
    line["correct"] = bool(checks) and out["failed"] == 0 and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def _deadline():
        log(f"FAILED: not done after {DEADLINE_S} s")
        os._exit(3)
    timer = threading.Timer(DEADLINE_S, _deadline)
    timer.daemon = True
    timer.start()

    bench = Bench(ROOT)
    w = bench.workload(args.workload)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    # one compile cache for this process and its workers, at a fixed path
    # inside the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    from repro.core import shutdown
    from repro.core.backends.launchers import host_tpu_chips
    chips = host_tpu_chips()
    if len(chips) < w["chips"]:
        log(f"FAILED: the cell needs {w['chips']} TPU chip(s); this host "
            f"hands out {len(chips)} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r})")
        return 2
    try:
        line = run(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace))
    finally:
        shutdown()
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        log("FAILED: the driver process initialised a JAX backend")
        return 2
    dev = line["device"]
    if dev["platform"] != "tpu" or dev["count"] != w["chips"]:
        log(f"FAILED: the cell ran on {dev['count']} {dev['platform']} "
            f"device(s), not on {w['chips']} TPU chip(s)")
        return 2
    for name, c in line["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
