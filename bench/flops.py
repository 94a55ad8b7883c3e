"""Operations and parameters of xLSTM from a configuration's shapes.

Counts are of the matrix products the model requires, 2 operations per
multiply-add, from the sizes in the configuration's file alone; element-wise
work (gates, norms, softmax) is not counted. The mLSTM's mixing is counted
in its causal parallel form: token ``t`` of a sequence of ``S`` meets
``t + 1`` positions in ``q k^T`` and again in the product with ``v``.
"""

from __future__ import annotations

import json
import os


def _dims(c: dict) -> dict:
    d, nh, di = c["d_model"], c["num_heads"], c["mlstm_inner_dim"]
    return {"d": d, "nh": nh, "di": di, "hd_m": di // nh, "hd_s": d // nh,
            "dff": c["slstm_ff_dim"], "cw": c["conv1d_kernel_size"],
            "V": c["vocab_size"]}


def param_count(c: dict) -> int:
    k = _dims(c)
    d, nh, di, dff = k["d"], k["nh"], k["di"], k["dff"]
    mlstm = (2 * d                           # ln1 scale, bias
             + d * 2 * di + k["cw"] * di + di     # up, conv w, conv b
             + 3 * di * di                        # q, k, v
             + 2 * (di * nh + nh)                 # input, forget gates
             + k["hd_m"] + di * d)                # head norm, down
    slstm = (2 * d + k["hd_s"]                  # norm, head norm
             + 4 * (d * d + nh * k["hd_s"] ** 2 + d)  # gates z, i, f, o
             + d * 2 * dff + dff * d)             # GeGLU up, down
    n_s = len(c["slstm_at"])
    n_m = c["num_blocks"] - n_s
    return k["V"] * d + 2 * d + n_m * mlstm + n_s * slstm


def forward_flops(c: dict, batch: int, seq: int) -> int:
    """Matmul operations of one forward pass and its loss."""
    k = _dims(c)
    d, nh, di, dff = k["d"], k["nh"], k["di"], k["dff"]
    tokens = batch * seq
    mlstm_tok = 2 * (d * 2 * di + 3 * di * di + 2 * di * nh + di * d)
    # causal mixing: sum over t of (t + 1) positions, twice, per head
    mix_seq = 2 * 2 * nh * k["hd_m"] * (seq * (seq + 1) // 2)
    slstm_tok = 2 * (4 * d * d + 4 * nh * k["hd_s"] ** 2
                     + d * 2 * dff + dff * d)
    head_tok = 2 * d * k["V"]
    n_s = len(c["slstm_at"])
    n_m = c["num_blocks"] - n_s
    return (tokens * (n_m * mlstm_tok + n_s * slstm_tok + head_tok)
            + batch * n_m * mix_seq)


def peak(kind: str) -> dict:
    """The published peaks of a device, by its JAX ``device_kind``, from
    ``peaks.json``. A device that is not in the table is an error."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]
