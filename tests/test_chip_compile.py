"""Every Pallas kernel compiles for one TPU v5e chip at the repo's widths.

Ahead-of-time compiles against a described (not attached) v5e topology:
the TPU compiler runs here and refuses what the chip would refuse — block
shapes off the (8, 128) tiling, primitives Mosaic cannot lower — which the
interpret-mode tests in ``test_kernels.py`` cannot see. Nothing runs, so
these say nothing about results or times.

The topology is described inside a module-scoped fixture, never at import:
only the test process that is handed this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mlstm_scan import mlstm_scan
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.slstm_scan import slstm_scan


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()    # the Mosaic kernel
    return compiled


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


def test_flash_attention_yi_9b(one_chip):
    # yi-9b: 32 query heads over 4 kv heads, head_dim 128
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=True), one_chip,
             ((1, 32, 4096, 128), BF16), ((1, 4, 4096, 128), BF16),
             ((1, 4, 4096, 128), BF16))


def test_decode_attention_yi_9b(one_chip):
    _compile(decode_attention, one_chip,
             ((8, 32, 128), BF16), ((8, 4096, 4, 128), BF16),
             ((8, 4096, 4, 128), BF16), ((8,), I32))


def test_slstm_scan_xlstm_125m(one_chip):
    # xlstm-125m sLSTM: 4 heads of 768 / 4 = 192
    pre = ((4, 4, 1024, 192), F32)
    rec = ((4, 192, 192), F32)
    _compile(slstm_scan, one_chip, pre, pre, pre, pre, rec, rec, rec, rec)


def test_mlstm_scan_xlstm_125m(one_chip):
    # xlstm-125m mLSTM: 4 heads of the 2x up-projected 1536 = 384
    qkv = ((4, 4, 1024, 384), F32)
    gate = ((4, 4, 1024), F32)
    _compile(mlstm_scan, one_chip, qkv, qkv, qkv, gate, gate)


def test_rglru_scan_recurrentgemma_9b(one_chip):
    # recurrentgemma-9b: lru_width 4096
    x = ((2, 1024, 4096), F32)
    _compile(lambda x, a, i, lam: rglru_scan(x, a, i, lam), one_chip,
             x, x, x, ((4096,), F32))
