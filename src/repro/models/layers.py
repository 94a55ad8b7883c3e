"""Core model layers, pure JAX (params are plain pytrees of jnp arrays).

Everything here is written to lower cleanly under jit + GSPMD sharding:
einsum-based attention, no data-dependent python control flow, explicit
dtypes. Pallas twins of some hot paths live in ``repro.kernels``; the
models do not call them.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


Params = dict  # nested dict pytree


def _he(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * p["scale"].astype(jnp.float32)).astype(dt)


def layernorm_init(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(dt)


# --------------------------------------------------------------------------
# Rotary position embeddings (RoPE + multimodal M-RoPE)
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 1e6) -> jax.Array:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN rotary scaling (arXiv:2309.00071) as DeepSeek-V2 configures it
    (``rope_scaling`` of type ``yarn``)."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @staticmethod
    def get_mscale(factor: float, mscale: float) -> float:
        return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0

    def freqs(self, head_dim: int, theta: float) -> np.ndarray:
        """Inverse frequencies: the base ones on the fast dimensions, the
        interpolated ones (divided by ``factor``) on the slow, and a linear
        ramp between the correction range's ends."""
        def dim_of(rotations):
            return head_dim * math.log(
                self.original_max_position / (rotations * 2 * math.pi)) \
                / (2 * math.log(theta))
        low = max(math.floor(dim_of(self.beta_fast)), 0)
        high = min(math.ceil(dim_of(self.beta_slow)), head_dim - 1)
        base = rope_freqs(head_dim, theta)
        span = high - low if high != low else 0.001
        ramp = np.clip((np.arange(head_dim // 2) - low) / span, 0, 1)
        return base / self.factor * ramp + base * (1 - ramp)

    @property
    def cos_sin_scale(self) -> float:
        return self.get_mscale(self.factor, self.mscale) \
            / self.get_mscale(self.factor, self.mscale_all_dim)

    @property
    def softmax_scale(self) -> float:
        """What the attention logits' ``head_dim ** -0.5`` is multiplied by."""
        if not self.mscale_all_dim:
            return 1.0
        return self.get_mscale(self.factor, self.mscale_all_dim) ** 2


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 1e6, *, yarn: "Yarn | None" = None,
               interleaved: bool = False) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32. ``interleaved`` rotates
    the pairs (x[2i], x[2i+1]) and returns them de-interleaved (first
    members, then second members), as DeepSeek-V2 does; by default the
    pairs are (x[i], x[i + D/2])."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta) if yarn is None \
        else yarn.freqs(d, theta)                               # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs      # (B,S,D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if yarn is not None and yarn.cos_sin_scale != 1.0:
        cos, sin = cos * yarn.cos_sin_scale, sin * yarn.cos_sin_scale
    xf = x.astype(jnp.float32)
    if interleaved:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
    else:
        x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array,
                sections: tuple[int, ...], theta: float = 1e6) -> jax.Array:
    """Qwen2-VL multimodal RoPE. positions: (3, B, S) — (t, h, w) ids;
    ``sections`` partitions the half-dim, e.g. (16, 24, 24) for D=128."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                                # (D/2,)
    ang_thw = positions[..., None].astype(jnp.float32) * freqs  # (3,B,S,D/2)
    # per-dim selection of which axis (t/h/w) drives the rotation
    idx = np.concatenate([np.full((s,), i) for i, s in enumerate(sections)])
    sel = jax.nn.one_hot(idx, 3, dtype=jnp.float32).T            # (3, D/2)
    ang = jnp.einsum("tbsd,td->bsd", ang_thw, sel)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Attention (GQA / MQA / local / bidirectional) — XLA path
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnDims:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int


def attention_init(key, dims: AttnDims, dtype=jnp.float32,
                   qk_norm: bool = False) -> Params:
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, h, kvh, hd = dims.d_model, dims.n_heads, dims.n_kv_heads, dims.head_dim
    s = d ** -0.5
    p = {
        "wq": _he(kq, (d, h * hd), s, dtype),
        "wk": _he(kk, (d, kvh * hd), s, dtype),
        "wv": _he(kv, (d, kvh * hd), s, dtype),
        "wo": _he(ko, (h * hd, d), (h * hd) ** -0.5, dtype),
    }
    if qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def sdpa(q: jax.Array, k: jax.Array, v: jax.Array, *,
         causal: bool, window: int | None = None,
         q_offset: int = 0, kv_len: jax.Array | None = None,
         scale: float | None = None) -> jax.Array:
    """Grouped softmax attention. q: (B,Sq,H,D), k/v: (B,Skv,KV,D) with
    H = KV * G — KV heads are *never* materialized G times (a 1/G memory
    saving over the naive repeat_kv formulation). fp32 softmax.

    ``window``: local attention — key j visible to query i iff
    i - window < j <= i.  ``q_offset``: absolute position of q[0] (decode).
    ``kv_len``: optional (B,) active cache lengths (decode masking).
    ``scale``: what the logits are multiplied by (default ``D ** -0.5``).
    """
    b, sq, h, d = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    if scale is None:
        scale = d ** -0.5
    qg = q.reshape(b, sq, kvh, g, d)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    if kv_len is not None:
        valid = kpos[None] < kv_len[:, None, None]               # (B,1,Skv)
        logits = jnp.where(valid[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, sq, h, v.shape[-1])   # v dim may differ (MLA)


def attention_apply(p: Params, x: jax.Array, dims: AttnDims, *,
                    positions: jax.Array | None = None,
                    rope_kind: str = "rope",
                    mrope_sections: tuple[int, ...] = (16, 24, 24),
                    rope_theta: float = 1e6,
                    causal: bool = True,
                    window: int | None = None,
                    cache: Params | None = None,
                    norm_eps: float = 1e-6,
                    mesh=None,
                    ) -> tuple[jax.Array, Params | None]:
    """Full attention block. If ``cache`` is given, runs one decode step:
    x is (B, 1, d); cache = {"k": (B,Smax,KV,D), "v": ..., "pos": (B,)}.
    Returns (out, new_cache)."""
    b, s, _ = x.shape
    h, kvh, hd = dims.n_heads, dims.n_kv_heads, dims.head_dim
    q = jnp.einsum("bsd,de->bse", x, p["wq"]).reshape(b, s, h, hd)
    k = jnp.einsum("bsd,de->bse", x, p["wk"]).reshape(b, s, kvh, hd)
    v = jnp.einsum("bsd,de->bse", x, p["wv"]).reshape(b, s, kvh, hd)
    if "q_norm" in p:
        q = rmsnorm(p["q_norm"], q, norm_eps)
        k = rmsnorm(p["k_norm"], k, norm_eps)

    if cache is not None:
        pos = cache["pos"]                                       # (B,)
        if rope_kind == "rope":
            q = apply_rope(q, pos[:, None], rope_theta)
            k = apply_rope(k, pos[:, None], rope_theta)
        elif rope_kind == "mrope":
            p3 = jnp.broadcast_to(pos[None, :, None], (3, b, 1))
            q = apply_mrope(q, p3, mrope_sections, rope_theta)
            k = apply_mrope(k, p3, mrope_sections, rope_theta)
        smax = cache["k"].shape[1]
        # dec-2: when the KV cache shards head_dim over 'model' (GQA with
        # kv_heads < TP), q must adopt the same layout or GSPMD re-gathers
        # the whole cache to resolve the mismatch (EXPERIMENTS.md §Perf)
        if mesh is not None and "model" in getattr(mesh, "shape", {}):
            tp = mesh.shape["model"]
            if kvh % tp != 0 and hd % tp == 0:
                from jax.sharding import NamedSharding, PartitionSpec as _P
                shd_q = NamedSharding(mesh, _P(None, None, None, "model"))
                q = jax.lax.with_sharding_constraint(q, shd_q)
        # ring-buffer slot for local attention, plain slot otherwise
        slot = pos % smax if window is not None else pos
        batch_ix = jnp.arange(b)
        new_k = cache["k"].at[batch_ix, slot].set(
            k[:, 0].astype(cache["k"].dtype))
        new_v = cache["v"].at[batch_ix, slot].set(
            v[:, 0].astype(cache["v"].dtype))
        # window cache is permutation-safe (softmax); mask by fill level
        out = sdpa(q, new_k.astype(x.dtype), new_v.astype(x.dtype),
                   causal=False, kv_len=jnp.minimum(pos + 1, smax))
        new_cache = {"k": new_k, "v": new_v, "pos": pos + 1}
    else:
        if positions is None:
            positions = jnp.arange(s)[None, :].astype(jnp.int32)
            positions = jnp.broadcast_to(positions, (b, s))
        if rope_kind == "rope":
            q = apply_rope(q, positions, rope_theta)
            k = apply_rope(k, positions, rope_theta)
        elif rope_kind == "mrope":
            q = apply_mrope(q, positions, mrope_sections, rope_theta)
            k = apply_mrope(k, positions, mrope_sections, rope_theta)
        out = sdpa(q, k, v, causal=causal, window=window)
        new_cache = None
    out = out.reshape(b, s, h * hd)
    return jnp.einsum("bse,ed->bsd", out, p["wo"]), new_cache


def attention_cache_init(batch: int, max_seq: int, dims: AttnDims,
                         dtype=jnp.bfloat16) -> Params:
    return {
        "k": jnp.zeros((batch, max_seq, dims.n_kv_heads, dims.head_dim), dtype),
        "v": jnp.zeros((batch, max_seq, dims.n_kv_heads, dims.head_dim), dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------

def mlp_init(key, d: int, d_ff: int, kind: str, dtype=jnp.float32) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    s_in, s_out = d ** -0.5, d_ff ** -0.5
    if kind == "swiglu":
        return {"w_gate": _he(k1, (d, d_ff), s_in, dtype),
                "w_up": _he(k2, (d, d_ff), s_in, dtype),
                "w_down": _he(k3, (d_ff, d), s_out, dtype)}
    return {"w_up": _he(k1, (d, d_ff), s_in, dtype),
            "w_down": _he(k2, (d_ff, d), s_out, dtype)}


def mlp_apply(p: Params, x: jax.Array, kind: str) -> jax.Array:
    if kind == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
        u = jnp.einsum("bsd,df->bsf", x, p["w_up"])
        h = jax.nn.silu(g) * u
    elif kind == "squared_relu":                    # nemotron-4
        h = jax.nn.relu(jnp.einsum("bsd,df->bsf", x, p["w_up"])) ** 2
    elif kind == "gelu":
        h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["w_up"]))
    else:
        raise ValueError(f"unknown mlp kind {kind!r}")
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"])


# --------------------------------------------------------------------------
# Embedding / unembedding
# --------------------------------------------------------------------------

def embedding_init(key, vocab: int, d: int, dtype=jnp.float32) -> Params:
    return {"table": _he(key, (vocab, d), 1.0, dtype)}


def embed(p: Params, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["table"], tokens, axis=0)


def unembed(p: Params, x: jax.Array) -> jax.Array:
    return jnp.einsum("bsd,vd->bsv", x, p["table"],
                      preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# Conv positional encoding (HuBERT-style) — depthwise conv over time
# --------------------------------------------------------------------------

def convpos_init(key, d: int, kernel: int = 128, groups: int = 16,
                 dtype=jnp.float32) -> Params:
    per = d // groups
    return {"w": _he(key, (kernel, per, d), (kernel * per) ** -0.5, dtype),
            "b": jnp.zeros((d,), dtype)}


def convpos_apply(p: Params, x: jax.Array, groups: int = 16) -> jax.Array:
    kernel = p["w"].shape[0]
    y = jax.lax.conv_general_dilated(
        x, p["w"],
        window_strides=(1,), padding=[(kernel // 2, kernel // 2 - 1 + kernel % 2)],
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=groups)
    return jax.nn.gelu(y + p["b"])
