"""Mixture-of-Experts layer (GShard/Switch-style capacity dispatch).

Covers qwen2-moe (4 shared + 60 routed, top-4) and deepseek-moe
(2 shared + 64 fine-grained routed, top-6). Expert weights carry a leading
expert axis that is sharded over the ``model`` mesh axis (expert
parallelism); the one-hot dispatch einsums lower to all-to-alls under GSPMD.

A layer told which experts it holds (``MoEDims.experts_held``) is dropless
instead: it routes over all ``n_experts``, holds the weights of its own
share only, and computes that share's part of the result for every
(token, expert) pair routed to it (:func:`moe_apply_held`) — one chip's
part of an expert-parallel layer, without the exchange.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .layers import Params, _he, mlp_apply, mlp_init


@dataclasses.dataclass(frozen=True)
class MoEDims:
    d_model: int
    n_experts: int           # routed experts
    top_k: int
    d_expert: int            # per-expert FFN width
    n_shared: int = 0        # shared (always-on) experts
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    # expert-weight padding so the expert axis divides the TP degree
    # (qwen2-moe: 60 -> 64 over model=16; the pad experts are never routed)
    n_experts_padded: int = 0
    # top-k gates renormalised to sum to 1 (deepseek-moe, qwen2-moe), or
    # left as softmax probabilities (DeepSeek-V2)
    norm_topk: bool = True
    # (first, count): the routed experts this layer holds, dropless
    experts_held: tuple[int, int] | None = None

    @property
    def e_pad(self) -> int:
        return max(self.n_experts_padded, self.n_experts)


#: rows of one expert matmul block on the dropless path: the MXU's side
BLOCK_ROWS = 128


def moe_init(key, dims: MoEDims, dtype=jnp.float32) -> Params:
    kr, ke1, ke2, ke3, ks = jax.random.split(key, 5)
    d, e, f = dims.d_model, dims.e_pad, dims.d_expert
    s_in, s_out = d ** -0.5, f ** -0.5
    p: Params = {"router": _he(kr, (d, dims.n_experts), s_in, jnp.float32)}
    if dims.experts_held is None:
        p.update(w_gate=_he(ke1, (e, d, f), s_in, dtype),
                 w_up=_he(ke2, (e, d, f), s_in, dtype),
                 w_down=_he(ke3, (e, f, d), s_out, dtype))
    else:
        # a held share: one set of weights per expert, expert e's own from
        # fold_in(key, e), so that they do not depend on the share
        first, count = dims.experts_held
        p["experts"] = [
            {"w_gate": _he(jax.random.fold_in(ke1, ex), (d, f), s_in, dtype),
             "w_up": _he(jax.random.fold_in(ke2, ex), (d, f), s_in, dtype),
             "w_down": _he(jax.random.fold_in(ke3, ex), (f, d), s_out,
                           dtype)}
            for ex in range(first, first + count)]
    if dims.n_shared:
        # shared experts fused into one wider MLP (mathematically identical
        # to n_shared parallel experts summed).
        p["shared"] = mlp_init(ks, d, dims.n_shared * f, "swiglu", dtype)
    return p


def moe_apply(p: Params, x: jax.Array, dims: MoEDims,
              ) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d). Returns (y, aux_loss).

    Scatter/gather dispatch with per-group capacity (one group per batch
    row): tokens are scattered into a (B, E, Cg, d) buffer at their
    (expert, position) slot — O(T*k*d) dispatch work instead of the naive
    one-hot-einsum dispatch whose (T,E,C) mask is O(cf*k*T^2/...) and
    intractable at T = 1M tokens (§Perf iteration moe-1). Tokens beyond an
    expert's per-group capacity are dropped (their routed contribution is
    0 — the residual stream still carries them; shared experts always
    apply). Under GSPMD the scatter lowers to the EP all-to-all: groups
    are data-sharded, the expert axis is model-sharded.
    """
    b, s, d = x.shape
    e, k = dims.n_experts, dims.top_k
    # per-group (= per batch row) expert capacity
    capacity = max(1, int(dims.capacity_factor * s * k / e))

    probs, gate_vals, gate_idx = _route(x, p["router"], dims)
    aux = _balance_loss(probs, gate_idx, dims)

    # per-group position of each (token, k) within its expert's buffer
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)       # (B,S,K,E)
    cnt = jnp.cumsum(onehot.reshape(b, s * k, e), axis=1) \
        .reshape(b, s, k, e)
    pos = jnp.sum(cnt * onehot, axis=-1) - 1                    # (B,S,K)
    within = pos < capacity
    pp = jnp.clip(pos, 0, capacity - 1)

    # scatter tokens into per-group expert buffers (B, E_pad, Cg, d) —
    # buffers use the padded expert count so weights always line up
    bb = jnp.broadcast_to(jnp.arange(b)[:, None, None], (b, s, k))
    contrib = x[:, :, None, :] * within[..., None].astype(x.dtype)
    expert_in = jnp.zeros((b, dims.e_pad, capacity, d), x.dtype) \
        .at[bb, gate_idx, pp].add(contrib)

    gate_h = jnp.einsum("becd,edf->becf", expert_in, p["w_gate"])
    up_h = jnp.einsum("becd,edf->becf", expert_in, p["w_up"])
    h = jax.nn.silu(gate_h) * up_h
    expert_out = jnp.einsum("becf,efd->becd", h, p["w_down"])   # (B,E,C,d)

    # combine: gather each token's k slots back and mix with its gates
    out_tok = expert_out[bb, gate_idx, pp]                      # (B,S,K,d)
    w = (gate_vals * within.astype(jnp.float32))[..., None]
    y = jnp.sum(out_tok.astype(jnp.float32) * w, axis=2).astype(x.dtype)

    if "shared" in p:
        y = y + mlp_apply(p["shared"], x, "swiglu")
    return y, aux


def _route(x: jax.Array, router: jax.Array, dims: MoEDims):
    """Softmax router over all ``n_experts`` and its top-k: (probs,
    gate_vals, gate_idx), the gates renormalised where ``dims`` says."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, dims.top_k)
    if dims.norm_topk:
        gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True)
                                 + 1e-9)
    return probs, gate_vals, gate_idx


def _balance_loss(probs: jax.Array, gate_idx: jax.Array,
                  dims: MoEDims) -> jax.Array:
    """Switch load-balancing loss: E * sum_e f_e * P_e."""
    e = dims.n_experts
    me = jnp.mean(probs.reshape(-1, e), axis=0)
    ce = jnp.mean(jax.nn.one_hot(gate_idx[..., 0].reshape(-1), e), axis=0)
    return dims.router_aux_weight * e * jnp.sum(me * ce)


def moe_apply_held(p: Params, x: jax.Array, dims: MoEDims,
                   ) -> tuple[jax.Array, jax.Array, dict]:
    """Dropless MoE over the held share of the routed experts, plus the
    shared experts. x: (B, S, d). Returns (y, aux_loss, stats), stats
    holding ``moe_routed`` (int32 (count,): pairs routed to each held
    expert) and ``moe_rows`` (int32: rows the expert matmuls computed).

    The (token, held expert) pairs are laid out expert by expert, each
    expert's rows padded to a whole number of :data:`BLOCK_ROWS`-row
    blocks. Each held expert has a loop over the blocks one expert can
    need, of which those in use multiply by the expert's weights and add
    the gated result to their tokens (reverse-differentiable: the loops
    have fixed lengths, and a block past those in use does nothing). The
    layout holds every pair a batch can route to the share, so none is
    dropped.
    """
    b, s, d = x.shape
    first, count = dims.experts_held
    k, blk = dims.top_k, BLOCK_ROWS
    t = b * s
    xf = x.reshape(t, d)
    n_rows = -(-(t * min(k, count) + count * (blk - 1)) // blk) * blk
    with jax.named_scope("moe.route"):
        probs, gate_vals, gate_idx = _route(xf, p["router"], dims)
        aux = _balance_loss(probs, gate_idx, dims)
        local = gate_idx.reshape(-1) - first                   # (T*K,)
        held = (local >= 0) & (local < count)
        ex = jnp.where(held, local, count)
        onehot = jax.nn.one_hot(ex, count + 1, dtype=jnp.int32)
        routed = jnp.sum(onehot, 0)[:count]
        rank = jnp.sum(jnp.cumsum(onehot, 0) * onehot, -1) - 1
        used = -(-routed // blk)                               # blocks
        row0 = (jnp.cumsum(used) - used) * blk
        dest = jnp.where(held, row0[jnp.minimum(ex, count - 1)] + rank,
                         n_rows)
        # rows past an expert's pairs point at token t, a row of zeros
        token_of_row = jnp.full((n_rows,), t, jnp.int32).at[dest].set(
            jnp.arange(t * k, dtype=jnp.int32) // k, mode="drop")
        gate_of_row = jnp.zeros((n_rows,), jnp.float32).at[dest].set(
            gate_vals.reshape(-1), mode="drop")
    with jax.named_scope("moe.experts"):
        xpad = jnp.concatenate([xf, jnp.zeros((1, d), x.dtype)])
        acc = jnp.zeros((t + 1, d), x.dtype)
        for j, w in enumerate(p["experts"]):
            def block(i, acc, j=j, w=w):
                at = row0[j] + i * blk
                rows = jax.lax.dynamic_slice_in_dim(token_of_row, at, blk)
                gate = jax.lax.dynamic_slice_in_dim(gate_of_row, at, blk)
                xb = xpad[rows]
                h = jax.nn.silu(xb @ w["w_gate"]) * (xb @ w["w_up"])
                return acc.at[rows].add((h @ w["w_down"])
                                        * gate[:, None].astype(x.dtype))

            def step(i, acc, j=j, block=block):
                return jax.lax.cond(i < used[j], block, lambda i, a: a,
                                    i, acc)
            acc = jax.lax.fori_loop(0, -(-t // blk), step, acc)
        y = acc[:t].reshape(b, s, d)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            y = y + mlp_apply(p["shared"], x, "swiglu")
    return y, aux, {"moe_routed": routed, "moe_rows": jnp.sum(used) * blk}


# --------------------------------------------------------------------------
# Manual expert-parallel MoE (shard_map) — §Perf iteration moe-2
# --------------------------------------------------------------------------

def moe_apply_manual(p: Params, x: jax.Array, dims: MoEDims, mesh,
                     *, dp_axis: str = "data",
                     ) -> tuple[jax.Array, jax.Array]:
    """Expert parallelism with explicit communication.

    Observation driving the design (EXPERIMENTS.md §Perf moe-2): under the
    auto path GSPMD cannot shard a scatter whose scattered dim is the
    expert axis, so it materializes the full (B,E,C,d) buffer with an
    all-reduce (TB-scale). But the residual stream is *already replicated
    across the model axis* inside a TP block — every model shard holds all
    tokens. So each shard can locally scatter the tokens routed to ITS
    experts, run its expert FFNs, and the only cross-shard communication
    for the whole MoE layer is one psum of the (B,S,d) output — the same
    collective a dense TP block pays for its down projection.

    Expert weights must carry an expert axis divisible by the TP degree
    (MoEDims.n_experts_padded pads them; pad experts are never routed).
    """
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    e_real, k = dims.n_experts, dims.top_k
    tp = mesh.shape["model"]
    e_pad = dims.e_pad
    assert e_pad % tp == 0, "pad experts to the TP degree (n_experts_padded)"
    epp = e_pad // tp
    # per-shard capacity: tokens-per-device-group x k / experts, padded up
    t_loc = b * s
    capacity = max(1, int(dims.capacity_factor * t_loc * k / e_real))

    compute_dtype = x.dtype

    def body(xl, router, wg, wu, wd):
        # xl: (B_loc, S, d) — replicated over 'model'; w*: (epp, d, f).
        # Boundary tensors arrive f32 (cotangents crossing the shard_map
        # boundary psum in f32 — the XLA CPU AllReducePromotion pass
        # crashes on bf16 all-reduce; TPU lowerings don't need this).
        wg = wg.astype(compute_dtype)
        wu = wu.astype(compute_dtype)
        wd = wd.astype(compute_dtype)
        xl = xl.astype(compute_dtype)
        bl = xl.shape[0]
        tl = bl * s
        m_idx = jax.lax.axis_index("model")
        cap = max(1, int(dims.capacity_factor * tl * k / e_real))

        probs, gate_vals, gate_idx = _route(xl, router, dims)  # (B,S,K)
        # aux loss (identical on every model shard; averaged over data
        # shards — each sees only its local tokens)
        aux = jax.lax.pmean(_balance_loss(probs, gate_idx, dims), dp_axis)

        # global position of each (token,k) within its expert's buffer
        onehot = jax.nn.one_hot(gate_idx, e_real, dtype=jnp.int32)
        cnt = jnp.cumsum(onehot.reshape(tl * k, e_real), axis=0) \
            .reshape(bl, s, k, e_real)
        pos = jnp.sum(cnt * onehot, axis=-1) - 1               # (B,S,K)
        within = pos < cap
        pp_ = jnp.clip(pos, 0, cap - 1)

        # which assignments belong to THIS shard's experts
        local_e = gate_idx - m_idx * epp                       # (B,S,K)
        mine = (local_e >= 0) & (local_e < epp) & within
        le = jnp.clip(local_e, 0, epp - 1)

        contrib = (xl[:, :, None, :]
                   * mine[..., None].astype(xl.dtype)).reshape(tl * k, d)
        buf = jnp.zeros((epp, cap, d), xl.dtype) \
            .at[le.reshape(-1), pp_.reshape(-1)].add(contrib)

        g = jnp.einsum("ecd,edf->ecf", buf, wg)
        u = jnp.einsum("ecd,edf->ecf", buf, wu)
        h = jax.nn.silu(g) * u
        out = jnp.einsum("ecf,efd->ecd", h, wd)                # (epp,C,d)

        out_tok = out[le.reshape(-1), pp_.reshape(-1)] \
            .reshape(bl, s, k, d)
        w = (gate_vals * mine.astype(jnp.float32))[..., None]
        y = jnp.sum(out_tok.astype(jnp.float32) * w, axis=2)
        y = jax.lax.psum(y, "model")           # f32 psum (see note above)
        return y, aux

    manual = {dp_axis, "model"}
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_axis, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(dp_axis, None, None), P()),
        axis_names=manual, check_vma=False)
    y, aux = fn(x.astype(jnp.float32), p["router"],
                p["w_gate"].astype(jnp.float32),
                p["w_up"].astype(jnp.float32),
                p["w_down"].astype(jnp.float32))
    y = y.astype(x.dtype)

    if "shared" in p:
        y = y + mlp_apply(p["shared"], x, "swiglu")
    return y, aux
