"""Gradient compression for cross-pod data parallelism.

Inter-pod ICI/DCN links are the slowest hop at 1000+ node scale, so the
cross-pod gradient all-reduce is the collective to compress:

* :func:`quantize_int8` / :func:`dequantize_int8` — per-tensor symmetric
  int8 with fp32 scale (4x over fp32, 2x over bf16).
* :class:`ErrorFeedback` — residual accumulation so compression error is
  re-injected next step (EF-SGD; keeps convergence), on the host.
* :func:`topk_sparsify` — magnitude top-k with index+value encoding.

Used by the multi-pod launcher (launch/train.py).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def quantize_int8_host(x) -> tuple[np.ndarray, np.float32]:
    """:func:`quantize_int8` in numpy, for processes that hold no device
    (a driver that only dispatches pod work)."""
    x = np.asarray(x, np.float32)
    scale = np.maximum(np.max(np.abs(x)), np.float32(1e-12)) / np.float32(127)
    q = np.clip(np.round(x / scale), -127, 127).astype(np.int8)
    return q, np.float32(scale)


def topk_sparsify(x: jax.Array, frac: float = 0.01,
                  ) -> tuple[jax.Array, jax.Array, tuple]:
    """Keep the top-``frac`` magnitude entries. Returns (values, flat_idx,
    original_shape)."""
    flat = x.reshape(-1)
    k = max(1, int(flat.size * frac))
    vals, idx = jax.lax.top_k(jnp.abs(flat), k)
    return flat[idx], idx, x.shape


def topk_restore(vals: jax.Array, idx: jax.Array, shape: tuple) -> jax.Array:
    out = jnp.zeros(int(jnp.prod(jnp.array(shape))), vals.dtype)
    return out.at[idx].set(vals).reshape(shape)


class ErrorFeedback:
    """Residual error feedback: compress(g + e); e' = (g + e) - decompressed.

    State lives host-side per pod (one pytree of numpy arrays), applied
    around the cross-pod reduce in the launcher; no device is touched.
    """

    def __init__(self):
        self.residual: Any | None = None

    def compress(self, grads: Any) -> tuple[Any, Any]:
        grads = jax.tree_util.tree_map(
            lambda g: np.asarray(g, np.float32), grads)
        if self.residual is not None:
            grads = jax.tree_util.tree_map(np.add, grads, self.residual)
        q = jax.tree_util.tree_map(quantize_int8_host, grads)
        deq = jax.tree_util.tree_map(
            lambda pair: pair[0].astype(np.float32) * pair[1], q,
            is_leaf=lambda t: isinstance(t, tuple) and len(t) == 2)
        self.residual = jax.tree_util.tree_map(np.subtract, grads, deq)
        return q, deq
