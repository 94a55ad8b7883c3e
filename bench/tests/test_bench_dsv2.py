"""The DeepSeek-V2-Lite cell at test size on the CPU: the program's loss
agrees with the plain reference, the parameter count of
``bench/flops_dsv2.py`` is the program's, a sound run is correct and a
traced one reports the expert layer's counters, and each routing fault
fails the cell's limit."""

from __future__ import annotations

import time

import pytest

from bench import cell, flops_dsv2
from bench import dsv2_ref as R

CELL = "evalmap-dsv2lite.doc2k"
#: a seed past 32 signed bits, as the driver's are
SEED = 2**31 + 4242
#: the published widths cut to test size; everything else as the file has
SMALL = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 4, "kv_lora_rank": 16,
         "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
         "intermediate_size": 128, "moe_intermediate_size": 32,
         "n_routed_experts_published": 16, "n_routed_experts": 4,
         "experts_held": [0, 4], "vocab_size": 256, "num_hidden_layers": 3}
TRAFFIC = {"seq": 64, "checked_sample": 6, "traced_futures": [2, 6]}


def _bench():
    return cell.Bench(cell.ROOT)


def _small() -> "tuple[dict, dict]":
    bench = _bench()
    w = bench.workload(CELL)
    return (dict(bench.config(w["config"]), **SMALL),
            dict(bench.traffic(w), **TRAFFIC))


def _module():
    bench = _bench()
    return bench.config_module(bench.workload(CELL)["config"])


def _program_losses(c: dict, t: dict, seed: int, indices) -> list:
    import jax
    import jax.numpy as jnp
    from repro.models import Model
    model = Model(_module().arch(c))
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    loss = jax.jit(lambda p, tok, lab: model.loss(
        p, {"tokens": tok, "labels": lab})[1]["ce"])
    out = []
    for i in indices:
        tok, lab = R.synth_tokens(c["vocab_size"], batch=t["batch"],
                                  seq=t["seq"], seed=seed, step=i)
        out.append(float(loss(params, jnp.asarray(tok), jnp.asarray(lab))))
    return out


def test_param_count_is_the_programs():
    from repro.models import Model
    for c in (_bench().config("evalmap-dsv2lite"), _small()[0]):
        assert flops_dsv2.param_count(c) \
            == Model(_module().arch(c)).param_count()
    assert flops_dsv2.param_count(_bench().config("evalmap-dsv2lite")) \
        == 1_805_714_432


def test_loss_matches_the_reference_and_routing_faults_do_not():
    """The program's losses against the reference's, and the routing
    faults the chip's control reads (dropping tokens over a capacity
    factor of 1.25; renormalised gates): each fails the limit."""
    import jax
    mod = _module()
    c, t = _small()
    idx = range(2)
    with jax.default_matmul_precision("highest"):
        got = _program_losses(c, t, SEED, idx)
    params = R.initial_params(SEED, c)

    def losses(**fault):
        nll = R.forward(c, **fault)
        return [float(nll(params, *R.synth_tokens(
            c["vocab_size"], batch=t["batch"], seq=t["seq"], seed=SEED,
            step=i)).mean()) for i in idx]
    ref = losses()
    assert mod.numbers(got, ref)["loss_gap_median"] \
        <= mod.LIMITS["loss_gap_median"] / 4
    for fault in ("capacity_1.25", "renormalised"):
        bad = losses(**mod.FAULTS[fault])
        assert mod.numbers(bad, ref)["loss_gap_median"] \
            > mod.LIMITS["loss_gap_median"], fault


def _run(trace: bool) -> dict:
    """One run at test size; the workers are shut down."""
    from repro.core import shutdown
    c, t = _small()
    try:
        return cell.run(_bench(), CELL, SEED, 1.0, trace, config=c,
                        traffic=t, t0=time.monotonic())
    finally:
        shutdown()


def test_traced_run_is_correct_and_counts_the_expert_rows():
    line = _run(trace=True)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0
    share = line["metrics"]["moe_padded_rows_share"]["value"]
    assert 0 <= share < 100
    assert {"future_overhead_ms", "worker_start_s"} <= set(line["metrics"])
    assert list(line)[-1] == "checks"


def _warm_renormalised(c, *args):
    import importlib
    mod = importlib.import_module("bench.configs.evalmap-dsv2lite")
    return mod.warm(dict(c, norm_topk_prob=True), *args)


@pytest.mark.parametrize("fault", ["renormalised"])
def test_fault_in_the_program_is_caught(fault, monkeypatch):
    monkeypatch.setattr(_module(), "warm", _warm_renormalised)
    line = _run(trace=False)
    assert line["correct"] is False, line["checks"]
