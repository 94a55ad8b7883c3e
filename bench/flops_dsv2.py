"""Operations, bytes and parameters of DeepSeek-V2-Lite's cut (one chip's
share of its expert layers) from a configuration's file.

Counts are of the matrix products the model requires, 2 operations per
multiply-add; element-wise work (norms, rotary, softmax, gating) is not
counted. Attention is counted in its causal form: token ``t`` of a
sequence of ``S`` meets ``t + 1`` positions in ``q k^T`` and again in the
product with ``v``. The held experts' share of a token's work is the
expected one, ``k * held / E`` experts a token, in :func:`forward_flops`,
and the routed counts themselves in :func:`expert_work`.
"""

from __future__ import annotations


def _dims(c: dict) -> dict:
    h = c["num_attention_heads"]
    return {"d": c["hidden_size"], "h": h, "dn": c["qk_nope_head_dim"],
            "dr": c["qk_rope_head_dim"], "dv": c["v_head_dim"],
            "r": c["kv_lora_rank"], "F": c["intermediate_size"],
            "f": c["moe_intermediate_size"],
            "E": c["n_routed_experts_published"],
            "held": c["experts_held"][1], "k": c["num_experts_per_tok"],
            "sh": c["n_shared_experts"] * c["moe_intermediate_size"],
            "V": c["vocab_size"], "L": c["num_hidden_layers"],
            "dense": c["first_k_dense_replace"]}


def _mla_params(k: dict) -> int:
    d, h = k["d"], k["h"]
    return (d * h * (k["dn"] + k["dr"]) + d * (k["r"] + k["dr"]) + k["r"]
            + k["r"] * h * (k["dn"] + k["dv"]) + h * k["dv"] * d)


def param_count(c: dict) -> int:
    k = _dims(c)
    d = k["d"]
    moe = (d * k["E"] + 3 * k["held"] * d * k["f"] + 3 * d * k["sh"])
    layer = 2 * d + _mla_params(k)
    return (2 * k["V"] * d + d + k["L"] * layer + k["dense"] * 3 * d * k["F"]
            + (k["L"] - k["dense"]) * moe)


def forward_flops(c: dict, batch: int, seq: int) -> int:
    """Matmul operations of one forward pass and its loss, with each token
    meeting the expected ``k * held / E`` held experts."""
    k = _dims(c)
    d, h = k["d"], k["h"]
    tokens = batch * seq
    mla_tok = 2 * (_mla_params(k) - k["r"])
    mix_seq = 2 * h * (k["dn"] + k["dr"] + k["dv"]) * (seq * (seq + 1) // 2)
    dense_tok = 2 * 3 * d * k["F"]
    moe_tok = (2 * d * k["E"] + 2 * 3 * d * k["sh"]
               + 2 * 3 * d * k["f"] * k["k"] * k["held"] / k["E"])
    n_moe = k["L"] - k["dense"]
    return int(tokens * (k["L"] * mla_tok + k["dense"] * dense_tok
                         + n_moe * moe_tok + 2 * d * k["V"])
               + batch * k["L"] * mix_seq)


def expert_work(c: dict, routed) -> "tuple[int, int]":
    """Operations and float32 bytes of the held experts' matmuls for
    ``routed``, the (token, expert) pairs routed to each held expert of
    each layer (``[[n, ...], ...]``): three products of ``d x f`` a pair;
    each expert's three matrices read once where it has a pair, and each
    pair's input row read and output row written."""
    k = _dims(c)
    d, f = k["d"], k["f"]
    pairs = sum(sum(layer) for layer in routed)
    experts = sum(1 for layer in routed for n in layer if n)
    return 2 * 3 * d * f * pairs, 4 * (3 * d * f * experts + 2 * d * pairs)
