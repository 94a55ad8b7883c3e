"""RG-LRU linear-recurrence scan as a Pallas TPU kernel.

The recurrence h_t = a_t * h_{t-1} + b_t is elementwise over the width dim
(pure VPU work, HBM-bandwidth bound). TPU adaptation: tile (width) across
parallel grid cells and (time) across the sequential innermost grid axis;
the carried state h lives in VMEM scratch. Within a time chunk the scan
runs as a fori_loop over rows already resident in VMEM, so
HBM traffic is exactly one read of (x, a, b) and one write of y.

Layout: all operands (B, S, W) fp32. Grid: (B, NW, NS), NS sequential.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, xs_ref, h0_ref, y_ref, h_scr, *,
                  cs: int):
    isq = pl.program_id(2)

    @pl.when(isq == 0)
    def _init():
        h_scr[...] = h0_ref[0]                       # (1, bw) initial state

    def step(t, h):
        # one (1, bw) row of decay and scaled input, read straight from the
        # VMEM block: Mosaic has no dynamic_slice on loaded values
        row = pl.ds(t, 1)
        h = a_ref[0, row, :] * h + xs_ref[0, row, :]
        y_ref[0, row, :] = h
        return h

    h = jax.lax.fori_loop(0, cs, step, h_scr[...])
    h_scr[...] = h


@functools.partial(jax.jit, static_argnames=("cs", "bw", "interpret"))
def rglru_scan(x: jax.Array, a_gate: jax.Array, i_gate: jax.Array,
               lam: jax.Array, h0: jax.Array | None = None, *,
               c: float = 8.0, cs: int = 256, bw: int = 512,
               interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Fused RG-LRU: computes decay/input scaling then scans.

    x, a_gate, i_gate: (B,S,W) fp32; lam: (W,); h0: (B,W) or None.
    Returns (y (B,S,W), h_last (B,W)).
    """
    b, s, w = x.shape
    # gate algebra is elementwise & cheap: fuse outside the kernel, keep the
    # kernel a pure scan (XLA fuses these producers into one pass)
    log_a = a_gate * (-c * jax.nn.softplus(-lam))
    a = jnp.exp(log_a)
    xs = jnp.sqrt(jnp.maximum(1 - jnp.exp(2 * log_a), 1e-12)) * (i_gate * x)
    if h0 is None:
        h0 = jnp.zeros((b, w), x.dtype)

    cs = min(cs, s)
    bw = min(bw, w)
    ns = pl.cdiv(s, cs)
    nw = pl.cdiv(w, bw)
    assert s % cs == 0 and w % bw == 0, "pad sequence/width to block size"

    y = pl.pallas_call(
        functools.partial(_rglru_kernel, cs=cs),
        grid=(b, nw, ns),
        in_specs=[
            pl.BlockSpec((1, cs, bw), lambda ib, iw, isq: (ib, isq, iw)),
            pl.BlockSpec((1, cs, bw), lambda ib, iw, isq: (ib, isq, iw)),
            pl.BlockSpec((1, 1, bw), lambda ib, iw, isq: (ib, 0, iw)),
        ],
        out_specs=pl.BlockSpec((1, cs, bw), lambda ib, iw, isq: (ib, isq, iw)),
        out_shape=jax.ShapeDtypeStruct((b, s, w), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, xs, h0[:, None, :])
    return y, y[:, -1]
