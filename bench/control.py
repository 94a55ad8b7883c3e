"""Readings of a cell's control and planted faults, on the chip.

    python3 bench/control.py --workload <cell> --seeds <n>,<n>,<n>

Starts one ``cluster`` worker on a chip and runs there, for each seed,
the configuration's ``control``: the plain reference, the reference in the
nearest lower precision put in the program's place (the control), and the
reference with a fault planted. It prints, per seed, every number the
cell compares for each of them against the reference, with the cell's
limit, whether it fails one of the cell's limits, and ends with one JSON
line of all readings. The benchmark's own
runs never run this; the limits in the configuration's module are set
from its readings and the program's.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["PYTHONPATH"] = os.pathsep.join(
        p for p in (ROOT, os.environ.get("PYTHONPATH")) if p)
    from bench.cell import Bench
    bench = Bench(ROOT)
    w = bench.workload(args.workload)
    mod = bench.config_module(w["config"])
    c, t = bench.config(w["config"]), bench.traffic(w)
    # the workers compute as a run's do
    os.environ["JAX_DEFAULT_MATMUL_PRECISION"] = c["matmul_precision"]
    from repro.core import future, plan, shutdown, value
    from repro.core.backends.launchers import host_tpu_chips
    if not host_tpu_chips():
        print("control: no TPU chip", file=sys.stderr)
        return 2
    out = {}
    plan("cluster", workers=1)
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            r = value(future(mod.control, c, t, seed, label=f"control-{seed}"))
            out[seed] = r
            for variant in ("control", "half_batch"):
                over = [n for n, v in r[variant].items()
                        if not v <= mod.LIMITS[n]]
                r[variant + "_fails"] = over
                for name, v in r[variant].items():
                    print(f"seed {seed} {variant} {name}: {v!r} (limit "
                          f"{mod.LIMITS[name]!r})", flush=True)
                print(f"seed {seed} {variant}: "
                      + (f"fails {', '.join(over)}" if over
                         else "FAILS NO LIMIT"), flush=True)
    finally:
        shutdown()
    print(json.dumps({"workload": args.workload, "readings": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
