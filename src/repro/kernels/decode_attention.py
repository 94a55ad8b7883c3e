"""Single-token decode attention over a KV cache (Pallas TPU kernel).

Decode is HBM-bandwidth bound: the whole point is streaming the (B, S, KV,
D) cache through VMEM exactly once per step. One grid cell handles one
(batch, kv-head) pair and the *whole group* of G = H/KV query heads at
once — the GQA trick that amortizes each cache byte over G queries (the
TPU-side reason GQA exists). The cache axis is tiled over the sequential
innermost grid dim with online-softmax state in VMEM scratch.

Layout: q (B, H, D); k,v (B, KV, S, D); lengths (B,). Grid (B, KV, NS).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _dec_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                m_scr, l_scr, acc_scr, *,
                bs: int, ns: int, g: int, scale: float):
    ib = pl.program_id(0)
    isq = pl.program_id(2)

    @pl.when(isq == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[ib]
    s_start = isq * bs

    @pl.when(s_start < length)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)               # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)               # (bs, D)
        v = v_ref[0, 0].astype(jnp.float32)               # (bs, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = s_start + jax.lax.broadcasted_iota(jnp.int32, (g, bs), 1)
        s = jnp.where(kpos < length, s, NEG_INF)          # (G, bs)

        m_prev = m_scr[...]                               # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(isq == ns - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array, *, bs: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q: (B,H,D); k,v: (B,S,KV,D); lengths: (B,). Returns (B,H,D)."""
    b, h, d = q.shape
    s, kv = k.shape[1], k.shape[2]
    g = h // kv
    bs = min(bs, s)
    ns = pl.cdiv(s, bs)
    pad = ns * bs - s
    if pad:                             # zero-pad ragged tail (masked anyway)
        zeros = jnp.zeros((b, pad, kv, d), k.dtype)
        k = jnp.concatenate([k, zeros], axis=1)
        v = jnp.concatenate([v, zeros], axis=1)

    # (B,S,KV,D) -> (B,KV,S,D) cache-major layout for streaming
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    qg = q.reshape(b, kv, g, d)

    kernel = functools.partial(_dec_kernel, bs=bs, ns=ns, g=g,
                               scale=d ** -0.5)
    out = pl.pallas_call(
        kernel,
        grid=(b, kv, ns),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),        # lengths
            pl.BlockSpec((1, 1, g, d), lambda ib, ik, isq: (ib, ik, 0, 0)),
            pl.BlockSpec((1, 1, bs, d), lambda ib, ik, isq: (ib, ik, isq, 0)),
            pl.BlockSpec((1, 1, bs, d), lambda ib, ik, isq: (ib, ik, isq, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda ib, ik, isq: (ib, ik, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, qg, kt, vt)
    return out.reshape(b, h, d)