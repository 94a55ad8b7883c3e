"""Running the benchmark's cells at test size on the CPU.

The configurations are the program's reduced xLSTM preset
(``data/*-smoke.json``) and the traffic is cut to a few steps and short
sequences; the harness is driven past its look for a chip, on CPU
``cluster`` workers."""

from __future__ import annotations

import json
import os
import time

from bench import cell

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SHORT = "evalmap-xlstm125m.short"
#: a seed past 32 signed bits, as the driver's are
SEED = 2**31 + 12345

SIZES = {SHORT: ("evalmap-smoke.json", {"seq": 32, "checked_sample": 12,
                                        "traced_futures": [3, 10]})}


#: the sizes at which the control is kept as a test, as (configuration,
#: traffic) numbers replaced: the smallest at which it fails a limit, as
#: it does at the cells' own size on the chip
CONTROL_SIZES = {
    SHORT: ({"d_model": 512, "num_heads": 4, "mlstm_inner_dim": 1024,
             "slstm_ff_dim": 682, "num_blocks": 24, "slstm_at": [7, 15, 23],
             "vocab_size": 16384}, {"seq": 128, "checked_sample": 64})}


def small(workload: str, sizes: "tuple[dict, dict] | None" = None
          ) -> "tuple[cell.Bench, dict, dict]":
    """The cell's configuration and traffic at test size, or with the
    numbers in ``sizes`` replaced."""
    bench = cell.Bench(cell.ROOT)
    name, cut = SIZES[workload]
    config_cut, traffic_cut = sizes or ({}, {})
    with open(os.path.join(DATA, name)) as f:
        config = dict(json.load(f), **config_cut)
    traffic = dict(bench.traffic(bench.workload(workload)), **cut)
    traffic.update(traffic_cut)
    return bench, config, traffic


def run_small(workload: str, *, seconds: float = 1.0, trace: bool = False,
              seed: int = SEED, sizes: "tuple[dict, dict] | None" = None
              ) -> dict:
    """One run of ``workload`` at test size, or at ``sizes`` (see
    :func:`small`); the workers are shut down."""
    from repro.core import shutdown
    bench, config, traffic = small(workload, sizes)
    try:
        return cell.run(bench, workload, seed, seconds, trace, config=config,
                        traffic=traffic, t0=time.monotonic())
    finally:
        shutdown()


def module(workload: str):
    bench = cell.Bench(cell.ROOT)
    return bench.config_module(bench.workload(workload)["config"])
