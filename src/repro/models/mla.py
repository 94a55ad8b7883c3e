"""Multi-head Latent Attention (DeepSeek-V2 style, used by MiniCPM3).

KV is compressed into a low-rank latent c_kv (d_c) plus a shared rotary key
k_rope; the decode cache stores only (c_kv, k_rope) — the paper-family's
memory saving. Queries come from their own low-rank latent, or straight
from x where ``q_lora_rank`` is ``None`` (DeepSeek-V2-Lite).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from .layers import (Params, Yarn, _he, apply_rope, rmsnorm, rmsnorm_init,
                     sdpa)


@dataclasses.dataclass(frozen=True)
class MLADims:
    d_model: int
    n_heads: int
    q_lora_rank: int | None = 768     # None: a direct query projection
    kv_lora_rank: int = 256
    qk_nope_dim: int = 64
    qk_rope_dim: int = 32
    v_head_dim: int = 64
    # rotary pairs (x[2i], x[2i+1]) instead of (x[i], x[i + D/2])
    rope_interleaved: bool = False
    yarn: Yarn | None = None

    @property
    def softmax_scale(self) -> float:
        s = (self.qk_nope_dim + self.qk_rope_dim) ** -0.5
        return s if self.yarn is None else s * self.yarn.softmax_scale


def mla_init(key, dims: MLADims, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 8)
    d, h = dims.d_model, dims.n_heads
    r_q, r_kv = dims.q_lora_rank, dims.kv_lora_rank
    dn, dr, dv = dims.qk_nope_dim, dims.qk_rope_dim, dims.v_head_dim
    s = d ** -0.5
    if r_q is None:
        q = {"wq": _he(ks[1], (d, h * (dn + dr)), s, dtype)}
    else:
        q = {"wq_a": _he(ks[0], (d, r_q), s, dtype),
             "q_a_norm": rmsnorm_init(r_q, dtype),
             "wq_b": _he(ks[1], (r_q, h * (dn + dr)), r_q ** -0.5, dtype)}
    return {
        **q,
        "wkv_a": _he(ks[2], (d, r_kv + dr), s, dtype),
        "kv_a_norm": rmsnorm_init(r_kv, dtype),
        "wkv_b": _he(ks[3], (r_kv, h * (dn + dv)), r_kv ** -0.5, dtype),
        "wo": _he(ks[4], (h * dv, d), (h * dv) ** -0.5, dtype),
    }


@jax.named_scope("mla")
def mla_apply(p: Params, x: jax.Array, dims: MLADims, *,
              positions: jax.Array | None = None,
              cache: Params | None = None,
              rope_theta: float = 1e6,
              norm_eps: float = 1e-6) -> tuple[jax.Array, Params | None]:
    b, s, _ = x.shape
    h = dims.n_heads
    dn, dr, dv = dims.qk_nope_dim, dims.qk_rope_dim, dims.v_head_dim
    r_kv = dims.kv_lora_rank
    apply_rope_ = functools.partial(apply_rope, theta=rope_theta,
                                    yarn=dims.yarn,
                                    interleaved=dims.rope_interleaved)

    if "wq" in p:
        q = jnp.einsum("bsd,de->bse", x, p["wq"])
    else:
        q_lat = rmsnorm(p["q_a_norm"],
                        jnp.einsum("bsd,dr->bsr", x, p["wq_a"]), norm_eps)
        q = jnp.einsum("bsr,re->bse", q_lat, p["wq_b"])
    q = q.reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]

    kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])             # (B,S,r+dr)
    c_kv = rmsnorm(p["kv_a_norm"], kv_a[..., :r_kv], norm_eps)
    k_rope = kv_a[..., r_kv:][:, :, None, :]                    # (B,S,1,dr)

    if cache is not None:
        # Decode with WEIGHT ABSORPTION (§Perf mla-1, DeepSeek-V2 trick):
        # instead of re-expanding the latent cache to per-head K/V
        # ((B,S,H,dn+dv) materialized, O(S*r*H*(dn+dv)) flops per token),
        # fold wkv_b into the query/output sides and attend directly in
        # the r-dim latent space — O(S*r*H) per token, no expansion.
        pos = cache["pos"]
        q_rope = apply_rope_(q_rope, pos[:, None])
        k_rope = apply_rope_(k_rope, pos[:, None])
        smax = cache["c_kv"].shape[1]
        bix = jnp.arange(b)
        new_ckv = cache["c_kv"].at[bix, pos].set(
            c_kv[:, 0].astype(cache["c_kv"].dtype))
        new_krope = cache["k_rope"].at[bix, pos].set(
            k_rope[:, 0].astype(cache["k_rope"].dtype))
        wkv = p["wkv_b"].reshape(r_kv, h, dn + dv)
        w_k, w_v = wkv[..., :dn], wkv[..., dn:]                 # (r,H,*)
        # absorbed query: (B,1,H,dn) x (r,H,dn) -> (B,1,H,r)
        q_abs = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_k)
        ckv_f = new_ckv.astype(x.dtype)
        scores = (jnp.einsum("bqhr,bsr->bhqs", q_abs, ckv_f)
                  + jnp.einsum("bqhd,bsd->bhqs", q_rope,
                               new_krope[:, :, 0].astype(x.dtype))) \
            * dims.softmax_scale
        valid = jnp.arange(smax)[None, :] < (pos + 1)[:, None]  # (B,S)
        scores = jnp.where(valid[:, None, None], scores.astype(jnp.float32),
                           -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        ctx = jnp.einsum("bhqs,bsr->bqhr", probs, ckv_f)        # latent ctx
        out = jnp.einsum("bqhr,rhd->bqhd", ctx, w_v)            # (B,1,H,dv)
        new_cache = {"c_kv": new_ckv, "k_rope": new_krope, "pos": pos + 1}
    else:
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(s)[None, :].astype(jnp.int32), (b, s))
        q_rope = apply_rope_(q_rope, positions)
        k_rope = apply_rope_(k_rope, positions)
        kv = jnp.einsum("bsr,re->bse", c_kv, p["wkv_b"]) \
                .reshape(b, s, h, dn + dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, s, h, dr))], -1)
        qq = jnp.concatenate([q_nope, q_rope], -1)
        out = sdpa(qq, k, v, causal=True, scale=dims.softmax_scale)
        new_cache = None

    out = out.reshape(b, s, h * dv)
    return jnp.einsum("bse,ed->bsd", out, p["wo"]), new_cache


def mla_cache_init(batch: int, max_seq: int, dims: MLADims,
                   dtype=jnp.bfloat16) -> Params:
    return {
        "c_kv": jnp.zeros((batch, max_seq, dims.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_seq, 1, dims.qk_rope_dim), dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }
