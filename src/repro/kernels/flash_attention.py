"""Flash attention (forward) as a Pallas TPU kernel, GQA-native.

TPU adaptation (not a CUDA port): the online-softmax accumulator lives in
VMEM scratch that persists across the *sequential* innermost grid axis
(TPU grids execute in order per core — the idiom replacing CUDA's
thread-block shared memory). Block shapes are MXU-aligned (multiples of
128 on the contracting/lane dims); K/V stream HBM->VMEM one block per grid
step, so VMEM holds O(bq*d + bk*d + bq*bk) regardless of sequence length.

Layout: q (B, H, S, D); k,v (B, KV, S, D); H = KV * G.
Grid: (B, H, NQ, NK) with NK innermost/sequential ("arbitrary").
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref,
               m_scr, l_scr, acc_scr, *,
               causal: bool, window: int | None,
               bq: int, bk: int, nk: int, scale: float, skv_real: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * bq
    k_start = ik * bk

    # block-level visibility test: skip fully-masked K blocks
    run = jnp.bool_(True)
    if causal:      # blocks strictly above the diagonal contribute nothing
        run = jnp.logical_and(run, k_start <= q_start + bq - 1)
    if window is not None:   # blocks entirely left of the window
        run = jnp.logical_and(run, k_start + bk - 1 > q_start - window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)               # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)               # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < skv_real            # padded tail keys excluded
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                               # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                            # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                   # (bq, 1)
        l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    bq: int = 128, bk: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B,H,S,D); k,v: (B,KV,S,D). Returns (B,H,S,D)."""
    b, h, sq, d = q.shape
    kv, skv = k.shape[1], k.shape[2]
    g = h // kv
    bq = min(bq, sq)
    bk = min(bk, skv)
    nq = pl.cdiv(sq, bq)
    nk = pl.cdiv(skv, bk)
    sq_pad, skv_pad = nq * bq - sq, nk * bk - skv
    if sq_pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_pad), (0, 0)))
    if skv_pad:
        # padded keys must never win the softmax: rely on the causal/window
        # masks plus an explicit NEG_INF mask for the tail
        k = jnp.pad(k, ((0, 0), (0, 0), (0, skv_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, skv_pad), (0, 0)))
    grid = (b, h, nq, nk)

    kernel = functools.partial(
        _fa_kernel, causal=causal, window=window,
        bq=bq, bk=bk, nk=nk, scale=d ** -0.5, skv_real=skv)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda ib, ih, iq, ik, _g=g: (ib, ih // _g, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda ib, ih, iq, ik, _g=g: (ib, ih // _g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq + sq_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max
            pltpu.VMEM((bq, 1), jnp.float32),     # running denom
            pltpu.VMEM((bq, d), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :sq] if sq_pad else out
