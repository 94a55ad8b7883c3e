"""Chunkwise-parallel mLSTM as a Pallas TPU kernel.

The mLSTM recurrence C_t = f_t C_{t-1} + i_t v_t k_t^T admits a chunked
form: within a chunk of size Cs the output is an attention-like matmul
(MXU work), and across chunks only the (D x D) matrix memory, the (D,)
normalizer and the running max are carried — they live in VMEM scratch over
the sequential time-grid axis. This turns a sequential recurrence into
O(S/Cs) MXU-dense steps (the TPU-native adaptation of the xLSTM paper's
parallel training form).

Stabilization follows the paper: all exponentials are taken relative to a
running max ``m`` that is folded across chunks.

Layout: q,k,v (B,H,S,D) fp32; gates i,f (B,H,S). Grid: (B,H,NS) sequential.
The gates enter the kernel as rows (B,H,1,S) and columns (B,H,S,1), so
every block keeps the TPU's (8, 128) tiling.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mlstm_kernel(q_ref, k_ref, v_ref, brow_ref, irow_ref, bcol_ref,
                  icol_ref, h_ref, c_scr, n_scr, m_scr, *, cs: int, d: int):
    isq = pl.program_id(2)

    @pl.when(isq == 0)
    def _init():
        c_scr[...] = jnp.zeros_like(c_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        m_scr[...] = jnp.zeros_like(m_scr)

    q = q_ref[0, 0].astype(jnp.float32) * (d ** -0.5)   # (cs, d)
    k = k_ref[0, 0].astype(jnp.float32)                 # (cs, d)
    v = v_ref[0, 0].astype(jnp.float32)                 # (cs, d)
    # gates arrive twice, as a row (1, cs) and as a column (cs, 1): Mosaic
    # cannot transpose a vector, and the decay matrix needs both
    b_row, i_row = brow_ref[0, 0], irow_ref[0, 0]       # (1, cs)
    b_col, i_col = bcol_ref[0, 0], icol_ref[0, 0]       # (cs, 1)
    b_total = b_row[:, cs - 1:]                         # (1, 1)

    m_prev = m_scr[...]                                 # (1, 1)
    C_prev = c_scr[...]                                 # (d, d)
    n_prev = n_scr[...]                                 # (1, d)

    # intra-chunk decay matrix D_ts = b_t - b_s + i_s  (s <= t)
    dmat = b_col - b_row + i_row
    tri = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 1)
    dmat = jnp.where(tri, dmat, -jnp.inf)

    # stabilizer per row: max(inter decay, intra max)
    inter_log = b_col + m_prev                          # (cs, 1)
    m_row = jnp.maximum(jnp.max(dmat, axis=1, keepdims=True), inter_log)
    m_row = jnp.maximum(m_row, 0.0)

    dexp = jnp.exp(dmat - m_row)                        # (cs, cs)
    inter_sc = jnp.exp(inter_log - m_row)               # (cs, 1)

    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    w = scores * dexp                                   # (cs, cs)
    intra = jax.lax.dot_general(w, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    inter = jax.lax.dot_general(q, C_prev, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) \
        * inter_sc

    n_t = jnp.sum(q * n_prev, axis=1, keepdims=True) * inter_sc \
        + jnp.sum(w, axis=1, keepdims=True)             # (cs, 1)
    denom = jnp.maximum(jnp.abs(n_t), jnp.exp(-m_row))
    h_ref[0, 0] = ((intra + inter) / denom).astype(h_ref.dtype)

    # -- state update for the next chunk --
    # per-step log weight of each row in the carried state
    carry_log = b_total - b_col + i_col                 # (cs, 1)
    m_new = jnp.maximum(b_total + m_prev,
                        jnp.max(carry_log, axis=0, keepdims=True))
    # decay applied to previous state
    state_sc = jnp.exp(b_total + m_prev - m_new)        # (1, 1)
    kw = k * jnp.exp(carry_log - m_new)                 # (cs, d)
    c_scr[...] = state_sc * C_prev + jax.lax.dot_general(
        kw, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    n_scr[...] = state_sc * n_prev + jnp.sum(kw, axis=0, keepdims=True)
    m_scr[...] = m_new


@functools.partial(jax.jit, static_argnames=("cs", "interpret"))
def mlstm_scan(q: jax.Array, k: jax.Array, v: jax.Array,
               i_raw: jax.Array, f_raw: jax.Array, *,
               cs: int = 128, interpret: bool = False) -> jax.Array:
    """Chunkwise mLSTM. q,k,v: (B,H,S,D); i_raw,f_raw: (B,H,S).
    Returns h: (B,H,S,D). Initial state is zero (training form)."""
    b, h, s, d = q.shape
    cs = min(cs, s)
    assert s % cs == 0, "pad sequence to the chunk size"
    ns = s // cs

    # the within-chunk cumulative log forget gate is elementwise gate
    # algebra: XLA computes it once outside the kernel
    i_raw = i_raw.astype(jnp.float32)
    log_f = -jax.nn.softplus(-f_raw.astype(jnp.float32))
    cum_f = jnp.cumsum(log_f.reshape(b, h, ns, cs), axis=-1).reshape(b, h, s)

    row = pl.BlockSpec((1, 1, 1, cs), lambda ib, ih, isq: (ib, ih, 0, isq))
    col = pl.BlockSpec((1, 1, cs, 1), lambda ib, ih, isq: (ib, ih, isq, 0))
    tile = pl.BlockSpec((1, 1, cs, d), lambda ib, ih, isq: (ib, ih, isq, 0))
    return pl.pallas_call(
        functools.partial(_mlstm_kernel, cs=cs, d=d),
        grid=(b, h, ns),
        in_specs=[tile, tile, tile, row, row, col, col],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((d, d), jnp.float32),     # matrix memory C
            pltpu.VMEM((1, d), jnp.float32),     # normalizer n
            pltpu.VMEM((1, 1), jnp.float32),     # running max m
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, cum_f[:, :, None, :], i_raw[:, :, None, :],
      cum_f[..., None], i_raw[..., None])
