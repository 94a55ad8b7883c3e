"""evalmap-dsv2lite: DeepSeek-V2-Lite's per-request loss mapped as
futures, one per request, on one chip's share of its expert layers.

The run is ``bench/evalmap.py``'s: ``stream(requests).map(score,
chunk=1).as_completed()`` on ``plan("cluster")``, one worker per chip,
then a sample scored again by the plain reference (``bench/dsv2_ref.py``)
on the worker's chip and the median relative gap of the losses compared.

The program is the repository's ``Model`` built from the configuration's
file (:func:`arch`): MLA with YaRN in front of a dense layer 0 and of
dropless MoE layers that hold experts 0-7 of 64. The jitted loss returns
the cross entropy with each MoE layer's pairs routed to each held expert
and the rows its expert matmuls computed; in a traced run the body
records them as counters (``repro.core.tracing.count``), and the trace
gives the device time of each named scope (``bench/scopes.py``).
"""

from __future__ import annotations

import sys
import time

#: the jitted per-request loss, as the device trace names it
PROGRAMS = ("jit_eval_loss",)

#: the program's named scopes whose device time a traced run reports
SCOPES = ("mla", "moe.route", "moe.experts", "moe.shared")

#: limit of the median relative loss gap, between the program's largest
#: reading and the controls' smallest; PERF.md gives the readings
LIMITS = {"loss_gap_median": 1e-6}

#: what the program supports of the published configuration's choices
_FIXED = {"hidden_act": "silu", "scoring_func": "softmax",
          "topk_method": "greedy", "n_group": 1, "topk_group": 1,
          "moe_layer_freq": 1, "attention_bias": False,
          "tie_word_embeddings": False, "routed_scaling_factor": 1}


def arch(c: dict):
    """The program's ``ArchConfig`` of the configuration's file."""
    from repro.configs import base
    from repro.models.layers import Yarn
    from repro.models.mla import MLADims
    from repro.models.moe import MoEDims

    for key, want in _FIXED.items():
        if c[key] != want:
            raise ValueError(f"{c['name']}: {key} {c[key]!r} is not "
                             f"supported (only {want!r})")
    first, count = c["experts_held"]
    if count != c["n_routed_experts"] \
            or first + count > c["n_routed_experts_published"]:
        raise ValueError(f"{c['name']}: experts_held {c['experts_held']} "
                         "disagrees with the expert counts")
    d, h, y = c["hidden_size"], c["num_attention_heads"], c["rope_scaling"]
    if y["type"] != "yarn":
        raise ValueError(f"{c['name']}: rope_scaling {y['type']!r}")
    return base.ArchConfig(
        name=f"bench.{c['name']}", family="moe",
        n_layers=c["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=c["num_key_value_heads"],
        d_ff=c["moe_intermediate_size"], vocab_size=c["vocab_size"],
        scan_layers=False,
        mla=MLADims(d_model=d, n_heads=h, q_lora_rank=c["q_lora_rank"],
                    kv_lora_rank=c["kv_lora_rank"],
                    qk_nope_dim=c["qk_nope_head_dim"],
                    qk_rope_dim=c["qk_rope_head_dim"],
                    v_head_dim=c["v_head_dim"], rope_interleaved=True,
                    yarn=Yarn(factor=y["factor"],
                              original_max_position=y[
                                  "original_max_position_embeddings"],
                              beta_fast=y["beta_fast"],
                              beta_slow=y["beta_slow"], mscale=y["mscale"],
                              mscale_all_dim=y["mscale_all_dim"])),
        moe=MoEDims(d_model=d, n_experts=c["n_routed_experts_published"],
                    top_k=c["num_experts_per_tok"],
                    d_expert=c["moe_intermediate_size"],
                    n_shared=c["n_shared_experts"],
                    norm_topk=c["norm_topk_prob"],
                    experts_held=(first, count)),
        moe_first_dense=c["first_k_dense_replace"],
        moe_dense_ff=c["intermediate_size"], rope_theta=c["rope_theta"],
        norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"], source=c["source"])


# -- task bodies: run on the worker ------------------------------------------

def warm(c: dict, seed: int, batch: int, seq: int,
         traced: "list | None") -> dict:
    """Build the model's weights from the seed and compile the loss at the
    request shape; ``traced`` = ``[first, count]`` requests to trace."""
    import jax
    import jax.numpy as jnp
    from repro.core.backends.cluster_worker import device_report
    from repro.models import Model

    from bench import scopes, worker

    cfg = arch(c)
    model = Model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))

    def eval_loss(params, tokens, labels):
        m = model.loss(params, {"tokens": tokens, "labels": labels})[1]
        return m["ce"], m["moe_routed"], m["moe_rows"]

    tok = jnp.zeros((batch, seq), jnp.int32)
    loss = jax.jit(eval_loss).lower(params, tok, tok).compile()
    float(loss(params, tok, tok)[0])
    worker.STATE.clear()
    worker.STATE.update(cfg=cfg, params=params, loss=loss, traced=traced,
                        trace=None,
                        ops=scopes.hlo_ops(loss.as_text(), SCOPES)
                        if traced else None)
    return device_report()


def score(index: int, *, seed: int, batch: int, seq: int) -> tuple:
    """One request: its tokens from ``(seed, index)``, its loss, and the
    seconds this body took. The request that opens the traced range
    starts the profiler; the one that ends it stops the profiler and
    carries the trace's numbers. Where counters are recorded, the body
    records the loss's routed pairs and expert rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import tracing
    from repro.data import synth_batch

    from bench import scopes, trace, worker

    st = worker.STATE
    tr = None
    traced = st["traced"]
    if traced and index == traced[0]:
        trace.start()
        st["trace"] = "on"
    elif traced and index == traced[0] + traced[1] \
            and st["trace"] == "on":
        tr = scopes.stop(PROGRAMS, st["ops"])
        st["trace"] = "done"
    with jax.profiler.TraceAnnotation("body"):
        t0 = time.perf_counter()
        b = synth_batch(st["cfg"], batch=batch, seq=seq, seed=seed,
                        step=index)
        ce, routed, rows = st["loss"](st["params"], jnp.asarray(b["tokens"]),
                                      jnp.asarray(b["labels"]))
        val = float(ce)
        elapsed = time.perf_counter() - t0
    if tracing.recording():
        tracing.count("moe.routed", np.asarray(routed).tolist())
        tracing.count("moe.rows", np.asarray(rows).tolist())
    return index, val, elapsed, tr


def finish_trace() -> "dict | None":
    """Stop a trace the window left running."""
    from bench import scopes, worker
    if worker.STATE.get("trace") != "on":
        return None
    worker.STATE["trace"] = "done"
    return scopes.stop(PROGRAMS, worker.STATE["ops"])


def release_program() -> None:
    import gc

    import jax

    from bench import worker
    worker.STATE.clear()
    jax.clear_caches()
    gc.collect()


def reference(c: dict, t: dict, seed: int, indices: list) -> list:
    from bench import dsv2_ref
    return dsv2_ref.eval_losses(c, t, seed, indices)


def numbers(got: list, ref: list) -> dict:
    """The compared number of losses ``got`` against the reference's."""
    from bench import compare
    return {"loss_gap_median": compare.median_rel_gap(got, ref)}


#: the controls and faults :func:`control` reads, as ``eval_losses``
#: arguments beside the lower precision's
FAULTS = {"half_batch": {"half": True}, "capacity_1.25": {"capacity": 1.25},
          "renormalised": {"renorm": True}}


def control(c: dict, t: dict, seed: int) -> dict:
    """Readings on this worker's chip, against the reference, over as many
    requests as a run compares: the control (the reference at three
    bfloat16 passes, in the program's place) and each of :data:`FAULTS`
    (half of each request's positions left out; routing that drops tokens
    over a capacity factor of 1.25; top-k gates renormalised)."""
    from bench import compare
    from bench import dsv2_ref as R
    idx = list(range(t["checked_sample"]))
    ref = R.eval_losses(c, t, seed, idx)
    out = {"control": R.eval_losses(c, t, seed, idx,
                                    **R.control_precision(c))}
    for name, kw in FAULTS.items():
        out[name] = R.eval_losses(c, t, seed, idx, **kw)
    got = {n: numbers(v, ref) for n, v in out.items()}
    got["widest"] = {n: compare.max_rel_gap(v, ref) for n, v in out.items()}
    return got


# -- the cell: runs in the driver --------------------------------------------

def run(ctx) -> dict:
    from bench import evalmap, flops_dsv2
    t = ctx.traffic
    return evalmap.run(ctx, sys.modules[__name__],
                       flops_dsv2.forward_flops(ctx.config, t["batch"],
                                                t["seq"]))
