"""Plain reference of DeepSeek-V2-Lite, one chip's share of its expert
layers, and its per-request loss.

Written from the published equations (arXiv:2405.04434 and the public
``modeling_deepseek.py`` of deepseek-ai/DeepSeek-V2-Lite) for the
configuration's file, in straightforward ``jax.numpy`` with no kernels,
caches or sorting:

* MLA with a direct query projection (``q_lora_rank`` null): the latent
  ``c_kv`` RMS-normed and expanded to per-head keys and values, one rotary
  key shared by all heads, YaRN inverse frequencies and cos/sin scale, the
  rotary input de-interleaved (``view(d/2, 2).transpose``) before
  ``rotate_half``, and the softmax scale ``(nope + rope) ** -0.5`` times
  ``yarn_get_mscale(factor, mscale_all_dim) ** 2``;
* layer 0 a SwiGLU of ``intermediate_size``;
* every other layer: a float32 softmax router over all
  ``n_routed_experts_published`` experts, top ``num_experts_per_tok``
  gates left unnormalised and times ``routed_scaling_factor``; the held
  experts (``experts_held``) computed densely over every token with their
  gates as masks, their sum added to the shared experts' SwiGLU of width
  ``n_shared_experts * moe_intermediate_size``.

It imports nothing of the program under test and takes nothing the
program has made: weights come from the seed by the initialisation the
configuration states (its ``assumed.init``), data from the seed by the
traffic's generator. It computes one request at a time, layer by layer.
The configuration's ``departures`` lists where the model departs from the
published one; the reference follows the configuration.

``capacity`` and ``renorm`` plant routing faults for the controls: each
held expert keeps only its first ``capacity * T * k / E`` tokens, or the
top-k gates are renormalised to sum to 1.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.xlstm_ref import (HIGHEST, _matmul,  # noqa: F401
                             control_precision, synth_tokens)


def _dims(c: dict) -> dict:
    h, dn, dr = (c["num_attention_heads"], c["qk_nope_head_dim"],
                 c["qk_rope_head_dim"])
    first, count = c["experts_held"]
    return {"d": c["hidden_size"], "h": h, "dn": dn, "dr": dr,
            "dv": c["v_head_dim"], "r": c["kv_lora_rank"],
            "F": c["intermediate_size"], "f": c["moe_intermediate_size"],
            "E": c["n_routed_experts_published"], "k": c["num_experts_per_tok"],
            "held": range(first, first + count),
            "sh": c["n_shared_experts"] * c["moe_intermediate_size"],
            "V": c["vocab_size"], "L": c["num_hidden_layers"],
            "eps": c["rms_norm_eps"]}


# -- YaRN (modeling_deepseek.py) ---------------------------------------------

def _yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(c: dict) -> np.ndarray:
    """``DeepseekV2YarnRotaryEmbedding``'s inverse frequencies."""
    y, dim, base = c["rope_scaling"], c["qk_rope_head_dim"], c["rope_theta"]

    def correction_dim(rot):
        return dim * math.log(y["original_max_position_embeddings"]
                              / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(correction_dim(y["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / y["factor"]
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return inter * (1 - mask) + extra * mask


def softmax_scale(c: dict) -> float:
    y = c["rope_scaling"]
    m = _yarn_mscale(y["factor"], y["mscale_all_dim"]) \
        if y.get("mscale_all_dim") else 1.0
    return (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, inv_freq, cs_scale):
    """x: (B, S, [H,] D) at positions 0..S-1, de-interleaved then rotated
    as ``apply_rotary_pos_emb`` does."""
    s, d = x.shape[1], x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None]
    emb = jnp.concatenate([freqs, freqs], -1)             # (S, D)
    shape = (1, s) + (1,) * (x.ndim - 3) + (d,)
    cos = (jnp.cos(emb) * cs_scale).reshape(shape)
    sin = (jnp.sin(emb) * cs_scale).reshape(shape)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


# -- initialisation ----------------------------------------------------------

def _normal(key, shape, scale):
    return jax.random.normal(key, shape) * scale


def _mla_init(key, k: dict) -> dict:
    d, h, r = k["d"], k["h"], k["r"]
    ks = jax.random.split(key, 8)
    return {"wq": _normal(ks[1], (d, h * (k["dn"] + k["dr"])), d ** -0.5),
            "wkv_a": _normal(ks[2], (d, r + k["dr"]), d ** -0.5),
            "kv_norm": jnp.ones((r,)),
            "wkv_b": _normal(ks[3], (r, h * (k["dn"] + k["dv"])), r ** -0.5),
            "wo": _normal(ks[4], (h * k["dv"], d), (h * k["dv"]) ** -0.5)}


def _swiglu_init(key, d: int, f: int) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w_gate": _normal(k1, (d, f), d ** -0.5),
            "w_up": _normal(k2, (d, f), d ** -0.5),
            "w_down": _normal(k3, (f, d), f ** -0.5)}


def _moe_init(key, k: dict) -> dict:
    d, f = k["d"], k["f"]
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    return {"router": _normal(kr, (d, k["E"]), d ** -0.5),
            "experts": [{"w_gate": _normal(jax.random.fold_in(kg, e), (d, f),
                                           d ** -0.5),
                         "w_up": _normal(jax.random.fold_in(ku, e), (d, f),
                                         d ** -0.5),
                         "w_down": _normal(jax.random.fold_in(kd, e), (f, d),
                                           f ** -0.5)}
                        for e in k["held"]],
            "shared": _swiglu_init(ks, d, k["sh"])}


def init_params(key, c: dict) -> dict:
    """The configuration's initialisation (``assumed.init``): N(0, 1)
    embedding and head, N(0, 1/fan_in) projections, unit norm scales."""
    k = _dims(c)
    keys = jax.random.split(key, 4)
    kl = jax.random.split(jax.random.fold_in(keys[3], 0), 1)[0]
    layers = []
    for layer in range(k["L"]):
        ka, kf, _, _ = jax.random.split(jax.random.fold_in(kl, layer), 4)
        p = {"ln1": jnp.ones((k["d"],)), "ln2": jnp.ones((k["d"],)),
             "attn": _mla_init(ka, k)}
        if layer < c["first_k_dense_replace"]:
            p["mlp"] = _swiglu_init(kf, k["d"], k["F"])
        else:
            p["moe"] = _moe_init(kf, k)
        layers.append(p)
    return {"embed": _normal(keys[0], (k["V"], k["d"]), 1.0),
            "head": _normal(keys[2], (k["V"], k["d"]), 1.0),
            "final_norm": jnp.ones((k["d"],)), "layers": layers}


def initial_params(seed: int, c: dict) -> dict:
    """:func:`init_params` of ``PRNGKey(seed)``, in one jitted call."""
    return jax.jit(functools.partial(init_params, c=c))(
        jax.random.PRNGKey(seed))


# -- forward -----------------------------------------------------------------

def _rmsnorm(scale, x, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def _swiglu(p, x, mm):
    return mm("...f,fd->...d", jax.nn.silu(mm("...d,df->...f", x, p["w_gate"]))
              * mm("...d,df->...f", x, p["w_up"]), p["w_down"])


def _mla(p, x, c, mm):
    k = _dims(c)
    b, s, _ = x.shape
    h, dn, dr, dv, r = k["h"], k["dn"], k["dr"], k["dv"], k["r"]
    q = mm("bsd,de->bse", x, p["wq"]).reshape(b, s, h, dn + dr)
    kv_a = mm("bsd,de->bse", x, p["wkv_a"])
    c_kv = _rmsnorm(p["kv_norm"], kv_a[..., :r], k["eps"])
    kv = mm("bsr,re->bse", c_kv, p["wkv_b"]).reshape(b, s, h, dn + dv)
    y = c["rope_scaling"]
    cs = _yarn_mscale(y["factor"], y["mscale"]) \
        / _yarn_mscale(y["factor"], y["mscale_all_dim"])
    inv = yarn_inv_freq(c)
    q_pe = _rope(q[..., dn:], inv, cs)                    # (B,S,H,dr)
    k_pe = _rope(kv_a[..., r:], inv, cs)                  # (B,S,dr)
    scores = (mm("bqhd,bkhd->bhqk", q[..., :dn], kv[..., :dn])
              + mm("bqhd,bkd->bhqk", q_pe, k_pe)) * softmax_scale(c)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    out = mm("bhqk,bkhd->bqhd", probs, kv[..., dn:]).reshape(b, s, h * dv)
    return mm("bse,ed->bsd", out, p["wo"])


def _moe(p, x, c, mm, capacity=None, renorm=False):
    k = _dims(c)
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    probs = jax.nn.softmax(mm("td,de->te", xt, p["router"]), -1)
    gates, idx = jax.lax.top_k(probs, k["k"])
    if renorm:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    else:
        gates = gates * c["routed_scaling_factor"]
    out = _swiglu(p["shared"], xt, mm)
    routed = jnp.zeros_like(xt)
    for e, w in zip(k["held"], p["experts"]):
        gate = jnp.sum(jnp.where(idx == e, gates, 0.0), -1)   # (T,)
        if capacity is not None:
            cap = int(capacity * b * s * k["k"] / k["E"])
            hit = jnp.any(idx == e, -1)
            gate = jnp.where(hit & (jnp.cumsum(hit) <= cap), gate, 0.0)
        routed = routed + _swiglu(w, xt, mm) * gate[:, None]
    return (routed + out).reshape(b, s, d)


def _layer(p, x, c, mm, capacity=None, renorm=False):
    eps = c["rms_norm_eps"]
    x = x + _mla(p["attn"], _rmsnorm(p["ln1"], x, eps), c, mm)
    h = _rmsnorm(p["ln2"], x, eps)
    if "mlp" in p:
        return x + _swiglu(p["mlp"], h, mm)
    return x + _moe(p["moe"], h, c, mm, capacity, renorm)


def _token_nll(p, x, labels, c, mm):
    x = _rmsnorm(p["final_norm"], x, c["rms_norm_eps"])
    logp = jax.nn.log_softmax(mm("bsd,vd->bsv", x, p["head"]), -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]


def forward(c: dict, prec=HIGHEST, *, capacity=None, renorm=False):
    """``nll(params, tokens, labels)``: per-token negative log-likelihood,
    shape of ``labels``, computed layer by layer (one jitted program per
    kind of layer)."""
    mm = _matmul(prec)
    layer = jax.jit(functools.partial(_layer, c=c, mm=mm, capacity=capacity,
                                      renorm=renorm))
    head = jax.jit(functools.partial(_token_nll, c=c, mm=mm))

    def nll(params, tokens, labels):
        x = params["embed"][tokens]
        for p in params["layers"]:
            x = layer(p, x)
        return head(params, x, labels)
    return nll


# -- evaluation: per-request loss -------------------------------------------

def eval_losses(c: dict, t: dict, seed: int, indices, *, dtype=jnp.float32,
                prec=HIGHEST, half: bool = False, capacity=None,
                renorm: bool = False) -> "list[float]":
    """The loss of each request ``i`` in ``indices``: the mean next-token
    loss of ``synth_tokens(seed, step=i)`` at the traffic's batch and
    sequence length, one request at a time. ``half`` keeps the first
    half of each request's positions; ``capacity`` and ``renorm`` plant
    the routing faults (module docstring)."""
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype),
                                    initial_params(seed, c))
    keep = t["seq"] // 2 if half else t["seq"]
    nll = forward(c, prec, capacity=capacity, renorm=renorm)
    out: "list[float]" = []
    for i in indices:
        tok, lab = synth_tokens(c["vocab_size"], batch=t["batch"],
                                seq=t["seq"], seed=seed, step=i)
        got = nll(params, jnp.asarray(tok), jnp.asarray(lab))
        out.append(float(jnp.mean(got.astype(jnp.float32)[:, :keep])))
    return out
