"""Plain reference of xLSTM-125m and its per-request loss.

Written from the equations in the configuration's file and in
arXiv:2405.04517 (xLSTM), in straightforward
``jax.numpy`` with no kernels, caches or chunking: the mLSTM in its
quadratic parallel form, the sLSTM as a step-by-step recurrence. It
imports nothing of the program under test and takes nothing the program
has made: weights come from the seed by the initialisation the
configuration states, data from the seed by the generator the traffic
states.

Where the model as the configuration defines it departs from the paper,
the reference follows the configuration, so that it checks the program
against the model it claims to run:

* mLSTM heads are normalised by a per-head RMSNorm with a learnt scale
  (the paper uses a group norm), and the block has no learnable skip;
* the sLSTM normaliser state is floored, ``n_t = max(f n + i, exp(-m_t))``
  in the stabilised domain (the paper has ``n_t = f n + i``);
* the sLSTM feed-forward is a GeGLU with the tanh form of GELU.

``prec`` is the matmul precision (``HIGHEST`` for the reference) and
``dtype`` the type of parameters and activations (float32 for the
reference); :func:`control_precision` gives the lower-precision
control's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
DEFAULT = jax.lax.Precision.DEFAULT


#: three bfloat16 passes per float32 product, written out
#: (:func:`_three_pass`) so that it computes the same on every platform
THREE_PASS = "bf16_3x"


def control_precision(c: dict) -> dict:
    """The control's arguments: the nearest precision below the one the
    configuration states (``matmul_precision`` of float32 parameters):
    three bfloat16 passes below ``highest``, bfloat16 below the TPU's
    default single pass."""
    if c["matmul_precision"] == "highest":
        return {"dtype": jnp.float32, "prec": THREE_PASS}
    return {"dtype": jnp.bfloat16, "prec": DEFAULT}


def _three_pass(spec, a, b):
    """``einsum`` of float32 operands as three bfloat16 products
    (hi*hi + hi*lo + lo*hi) accumulated in float32, the TPU's ``high``
    matmul precision."""
    def split(x):
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)

    def mm(x, y):
        return jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)
    return mm(ah, bh) + mm(ah, bl) + mm(al, bh)


def _matmul(prec):
    if prec == THREE_PASS:
        return _three_pass
    return functools.partial(jnp.einsum, precision=prec)


# -- data: the traffic's token generator ------------------------------------

def synth_tokens(vocab: int, *, batch: int, seq: int, seed: int, step: int,
                 shard: int = 0) -> "tuple[np.ndarray, np.ndarray]":
    """Tokens and next-token labels for ``(seed, step, shard)``: Zipf(1.3)
    draws modulo the vocabulary from a counter-based numpy stream."""
    rng = np.random.default_rng(
        np.random.SeedSequence(entropy=seed, spawn_key=(step, shard)))
    toks = (rng.zipf(1.3, size=(batch, seq + 1)) % vocab).astype(np.int32)
    return toks[:, :seq], toks[:, 1:]


# -- initialisation ----------------------------------------------------------

def _normal(key, shape, scale):
    return jax.random.normal(key, shape) * scale


def _mlstm_init(key, c: dict) -> dict:
    d, di, nh = c["d_model"], c["mlstm_inner_dim"], c["num_heads"]
    hd, cw = di // nh, c["conv1d_kernel_size"]
    ks = jax.random.split(key, 8)
    s, si = d ** -0.5, di ** -0.5
    return {"w_up": _normal(ks[0], (d, 2 * di), s),
            "conv_w": _normal(ks[1], (cw, di), cw ** -0.5),
            "conv_b": jnp.zeros((di,)),
            "wq": _normal(ks[2], (di, di), si),
            "wk": _normal(ks[3], (di, di), si),
            "wv": _normal(ks[4], (di, di), si),
            "w_i": _normal(ks[5], (di, nh), si),
            "b_i": jnp.zeros((nh,)),
            "w_f": _normal(ks[6], (di, nh), si),
            "b_f": jnp.full((nh,), c["forget_bias_init"]),
            "out_norm": {"scale": jnp.ones((hd,))},
            "w_down": _normal(ks[7], (di, d), si)}


def _slstm_init(key, c: dict) -> dict:
    d, nh, dff = c["d_model"], c["num_heads"], c["slstm_ff_dim"]
    hd = d // nh
    ks = jax.random.split(key, 10)
    s = d ** -0.5
    p = {"norm": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
         "out_norm": {"scale": jnp.ones((hd,))},
         "w_ff_up": _normal(ks[8], (d, 2 * dff), s),
         "w_ff_down": _normal(ks[9], (dff, d), dff ** -0.5)}
    for i, g in enumerate("zifo"):
        p[f"w_{g}"] = _normal(ks[i], (d, d), s)
        p[f"r_{g}"] = _normal(ks[4 + i], (nh, hd, hd), hd ** -0.5)
        p[f"b_{g}"] = (jnp.full((d,), c["forget_bias_init"]) if g == "f"
                       else jnp.zeros((d,)))
    return p


def kinds(c: dict) -> "list[str]":
    return ["slstm" if i in c["slstm_at"] else "mlstm"
            for i in range(c["num_blocks"])]


def init_params(key, c: dict) -> dict:
    """The configuration's initialisation: N(0, 1) embedding,
    N(0, 1/fan_in) projections, unit norms, forget-gate bias
    ``forget_bias_init``, with the key tree ``split(key, 4)`` -> (embedding, -, -, blocks), block
    ``b`` from ``fold_in(split(fold_in(k_blocks, 0), 1)[0], b)``."""
    keys = jax.random.split(key, 4)
    d = c["d_model"]
    kb = jax.random.split(jax.random.fold_in(keys[3], 0), 1)[0]
    blocks = {}
    for b, kind in enumerate(kinds(c)):
        k1 = jax.random.split(jax.random.fold_in(kb, b), 4)[0]
        if kind == "mlstm":
            blocks[f"b{b}"] = {
                "ln1": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
                "cell": _mlstm_init(k1, c)}
        else:
            blocks[f"b{b}"] = {"cell": _slstm_init(k1, c)}
    return {"embed": {"table": _normal(keys[0], (c["vocab_size"], d), 1.0)},
            "final_norm": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
            "stages": [blocks]}


def initial_params(seed: int, c: dict) -> dict:
    """:func:`init_params` of ``PRNGKey(seed)``, in one jitted call."""
    return jax.jit(functools.partial(init_params, c=c))(
        jax.random.PRNGKey(seed))


# -- forward -----------------------------------------------------------------

def _layernorm(p, x, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _rmsnorm(p, x, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * p["scale"]


def _mlstm(p, x, c, mm):
    """mLSTM block, parallel form: h_t = sum_s w_ts v_s / max(|sum_s w_ts|,
    exp(-m_t)), w_ts = (q_t . k_s / sqrt(D)) exp(F_t - F_s + i_s - m_t),
    F the cumulative log forget gate, m_t = max_s (F_t - F_s + i_s)."""
    b, s, _ = x.shape
    di, nh = c["mlstm_inner_dim"], c["num_heads"]
    hd = di // nh
    up = mm("bsd,de->bse", x, p["w_up"])
    main, gate = up[..., :di], up[..., di:]
    cw = p["conv_w"].shape[0]
    xp = jnp.pad(main, ((0, 0), (cw - 1, 0), (0, 0)))
    conv = sum(xp[:, j:j + s] * p["conv_w"][j] for j in range(cw)) \
        + p["conv_b"]
    conv = jax.nn.silu(conv)

    def heads(t):
        return t.reshape(b, s, nh, hd).transpose(0, 2, 1, 3)

    q = heads(mm("bse,ef->bsf", conv, p["wq"]))
    k = heads(mm("bse,ef->bsf", conv, p["wk"]))
    v = heads(mm("bse,ef->bsf", main, p["wv"]))
    i_raw = (mm("bse,eh->bsh", conv, p["w_i"]) + p["b_i"]).transpose(0, 2, 1)
    f_raw = (mm("bse,eh->bsh", conv, p["w_f"]) + p["b_f"]).transpose(0, 2, 1)
    cum = jnp.cumsum(jax.nn.log_sigmoid(f_raw), -1)             # (B,H,S)
    logd = cum[..., :, None] - cum[..., None, :] + i_raw[..., None, :]
    causal = jnp.tril(jnp.ones((s, s), bool))
    logd = jnp.where(causal, logd, -jnp.inf)
    m = jnp.max(logd, -1, keepdims=True)
    w = mm("bhtd,bhsd->bhts", q, k) * (hd ** -0.5) * jnp.exp(logd - m)
    norm = jnp.maximum(jnp.abs(jnp.sum(w, -1, keepdims=True)), jnp.exp(-m))
    h = mm("bhts,bhsd->bhtd", w / norm, v)
    h = _rmsnorm(p["out_norm"], h, c["norm_eps"])
    h = h.transpose(0, 2, 1, 3).reshape(b, s, di)
    return mm("bse,ed->bsd", h * jax.nn.silu(gate), p["w_down"])


def _slstm(p, x, c, mm):
    """sLSTM block: layer norm, the exponentially gated recurrence with
    block-diagonal recurrent weights, head-wise RMSNorm, GeGLU."""
    b, s, d = x.shape
    nh = c["num_heads"]
    hd = d // nh
    xin = _layernorm(p["norm"], x, c["norm_eps"])
    pre = [(mm("bsd,de->bse", xin, p[f"w_{g}"]) + p[f"b_{g}"])
           .reshape(b, s, nh, hd).transpose(1, 0, 2, 3) for g in "zifo"]

    def step(state, t):
        cc, n, h, m = state
        zt, it, ft, ot = t
        rec = {g: mm("bhd,hde->bhe", h, p[f"r_{g}"]) for g in "zifo"}
        z = jnp.tanh(zt + rec["z"])
        i_log = it + rec["i"]
        f_log = jax.nn.log_sigmoid(ft + rec["f"])
        o = jax.nn.sigmoid(ot + rec["o"])
        m_new = jnp.maximum(f_log + m, i_log)
        i_g = jnp.exp(i_log - m_new)
        f_g = jnp.exp(f_log + m - m_new)
        cc = f_g * cc + i_g * z
        n = jnp.maximum(f_g * n + i_g, jnp.exp(-m_new))
        h = o * cc / n
        return (cc, n, h, m_new), h

    zero = jnp.zeros((b, nh, hd), x.dtype)
    _, hs = jax.lax.scan(step, (zero, zero, zero, zero), tuple(pre))
    h = _rmsnorm(p["out_norm"], hs.transpose(1, 0, 2, 3), c["norm_eps"])
    up = mm("bsd,de->bse", h.reshape(b, s, d), p["w_ff_up"])
    dff = up.shape[-1] // 2
    y = jax.nn.gelu(up[..., :dff], approximate=True) * up[..., dff:]
    return mm("bsf,fd->bsd", y, p["w_ff_down"])


def token_nll(params, tokens, labels, c: dict, prec=HIGHEST):
    """Per-token negative log-likelihood, shape of ``labels``."""
    mm = _matmul(prec)
    x = params["embed"]["table"][tokens]
    for b, kind in enumerate(kinds(c)):
        p = params["stages"][0][f"b{b}"]
        if kind == "mlstm":
            x = x + _mlstm(p["cell"], _layernorm(p["ln1"], x, c["norm_eps"]),
                           c, mm)
        else:
            x = x + _slstm(p["cell"], x, c, mm)
    x = _layernorm(params["final_norm"], x, c["norm_eps"])
    logits = mm("bsd,vd->bsv", x, params["embed"]["table"])
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]


# -- evaluation: per-request loss -------------------------------------------

def eval_losses(c: dict, t: dict, seed: int, indices, *, dtype=jnp.float32,
                prec=HIGHEST, block: int = 32, half: bool = False
                ) -> "list[float]":
    """The loss of each request ``i`` in ``indices``: the mean next-token
    loss of ``synth_tokens(seed, step=i)`` at the traffic's batch and
    sequence length, computed ``block`` requests at a time. ``half``
    keeps the first half of each request's positions (a fault reading)."""
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype),
                                    initial_params(seed, c))
    keep = t["seq"] // 2 if half else t["seq"]

    @jax.jit
    def per_request(p, tok, lab):
        nll = token_nll(p, tok, lab, c, prec).astype(jnp.float32)
        nll = nll.reshape(-1, t["batch"], t["seq"])[..., :keep]
        return jnp.mean(nll, axis=(1, 2))

    out: "list[float]" = []
    idx = list(indices)
    for lo in range(0, len(idx), block):
        part = idx[lo:lo + block]
        padded = part + [part[-1]] * (block - len(part))
        toks, labs = zip(*(synth_tokens(c["vocab_size"], batch=t["batch"],
                                        seq=t["seq"], seed=seed, step=i)
                           for i in padded))
        got = per_request(params, jnp.asarray(np.concatenate(toks)),
                          jnp.asarray(np.concatenate(labs)))
        out.extend(float(x) for x in np.asarray(got)[:len(part)])
    return out
