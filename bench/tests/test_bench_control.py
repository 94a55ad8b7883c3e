"""The control of each cell, the reference one precision step down in the
program's place, and its half-request fault, on the CPU.

Each is held to the cell's own comparison: the configuration's
``numbers`` against its ``LIMITS``, the limits set from the chip's
readings at the cells' own size (PERF.md). The control is read at the
smallest size at which it fails a limit as it does on the chip, and it
reads at least three times what a sound run of the program reads there."""

import pytest

from bench.tests.helpers import (CONTROL_SIZES, SEED, SHORT, module,
                                 run_small, small)


@pytest.mark.parametrize("workload", [SHORT])
def test_control_fails_a_limit(workload):
    sizes = CONTROL_SIZES[workload]
    _, config, traffic = small(workload, sizes)
    mod = module(workload)
    got = mod.control(config, traffic, SEED)
    assert any(v > mod.LIMITS[n] for n, v in got["control"].items()), \
        (got["control"], mod.LIMITS)
    assert any(v > mod.LIMITS[n] for n, v in got["half_batch"].items()), \
        (got["half_batch"], mod.LIMITS)
    sound = run_small(workload, sizes=sizes)
    assert sound["correct"], sound["checks"]
    assert any(v >= 3 * sound["checks"][n]["value"]
               for n, v in got["control"].items()), (got, sound["checks"])
