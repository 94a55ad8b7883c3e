"""sLSTM sequential scan as a Pallas TPU kernel.

The sLSTM recurrence is truly sequential (the recurrent matrices R_* feed
h_{t-1} into every gate — the xLSTM paper's point), so the only lever is
keeping the per-head state (c, n, h, m) and the four (hd x hd) recurrent
matrices RESIDENT IN VMEM across the whole sequence instead of
round-tripping a few-KB state through HBM 32k times — exactly the cost the
xlstm-125m prefill/long_500k roofline shows for the XLA lowering
(EXPERIMENTS.md §Perf xlstm notes). Heads are independent (block-diagonal
R), so the grid parallelizes (batch x head) and streams time chunks.

Layout: pre-activations z,i,f,o (B,NH,S,HD) fp32 (computed by the dense
projections outside — MXU work XLA already handles well); recurrent mats
(NH,HD,HD). Grid (B, NH, NS), NS sequential; out h (B,NH,S,HD).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _slstm_kernel(z_ref, i_ref, f_ref, o_ref, rz_ref, ri_ref, rf_ref,
                  ro_ref, h_out_ref, c_scr, n_scr, h_scr, m_scr, *,
                  cs: int):
    isq = pl.program_id(2)

    @pl.when(isq == 0)
    def _init():
        c_scr[...] = jnp.zeros_like(c_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        h_scr[...] = jnp.zeros_like(h_scr)
        m_scr[...] = jnp.zeros_like(m_scr)

    rz = rz_ref[0]                                  # (HD, HD) resident
    ri = ri_ref[0]
    rf = rf_ref[0]
    ro = ro_ref[0]

    def step(t, state):
        c, n, h, m = state
        # recurrent matvecs: (1,HD) @ (HD,HD)
        hz = jax.lax.dot_general(h, rz, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        hi = jax.lax.dot_general(h, ri, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        hf = jax.lax.dot_general(h, rf, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ho = jax.lax.dot_general(h, ro, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        z = jnp.tanh(z_ref[0, 0, t][None, :] + hz)
        i_log = i_ref[0, 0, t][None, :] + hi
        f_log = -jax.nn.softplus(-(f_ref[0, 0, t][None, :] + hf))
        o = jax.nn.sigmoid(o_ref[0, 0, t][None, :] + ho)
        m_new = jnp.maximum(f_log + m, i_log)
        i_sc = jnp.exp(i_log - m_new)
        f_sc = jnp.exp(f_log + m - m_new)
        c = f_sc * c + i_sc * z
        n = jnp.maximum(f_sc * n + i_sc, jnp.exp(-m_new))
        h_new = o * (c / n)
        h_out_ref[0, 0, t, :] = h_new[0]
        return (c, n, h_new, m_new)

    state = (c_scr[...], n_scr[...], h_scr[...], m_scr[...])
    c, n, h, m = jax.lax.fori_loop(0, cs, step, state)
    c_scr[...] = c
    n_scr[...] = n
    h_scr[...] = h
    m_scr[...] = m


@functools.partial(jax.jit, static_argnames=("cs", "interpret"))
def slstm_scan(z, i, f, o, rz, ri, rf, ro, *, cs: int = 512,
               interpret: bool = False) -> jax.Array:
    """z,i,f,o: (B,NH,S,HD) fp32 pre-activations; r*: (NH,HD,HD).
    Returns h: (B,NH,S,HD). Initial state zero."""
    b, nh, s, hd = z.shape
    cs = min(cs, s)
    assert s % cs == 0, "pad sequence to the chunk size"
    ns = s // cs

    seq_spec = pl.BlockSpec((1, 1, cs, hd),
                            lambda ib, ih, isq: (ib, ih, isq, 0))
    r_spec = pl.BlockSpec((1, hd, hd), lambda ib, ih, isq: (ih, 0, 0))

    return pl.pallas_call(
        functools.partial(_slstm_kernel, cs=cs),
        grid=(b, nh, ns),
        in_specs=[seq_spec] * 4 + [r_spec] * 4,
        out_specs=seq_spec,
        out_shape=jax.ShapeDtypeStruct((b, nh, s, hd), z.dtype),
        scratch_shapes=[pltpu.VMEM((1, hd), jnp.float32)] * 4,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(z, i, f, o, rz, ri, rf, ro)
