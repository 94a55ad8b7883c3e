"""Device time by named scope (``bench/scopes.py``) and the expert layer's
readers: each gives what its definition says from a trace and counters
built by hand, and nothing where the program recorded none."""

import pytest

from bench import cell, counters, scopes

CELL = "evalmap-dsv2lite.doc2k"


def test_hlo_ops_of_a_compiled_program():
    import jax
    import jax.numpy as jnp

    def f(x, w):
        with jax.named_scope("moe.experts"):
            y = jax.lax.fori_loop(0, 3, lambda i, a: jnp.tanh(a @ w[i]), x)
        with jax.named_scope("moe.shared"):
            return y + jnp.sin(x)

    text = jax.jit(f).lower(jnp.ones((4, 4)),
                            jnp.ones((3, 4, 4))).compile().as_text()
    got = scopes.hlo_ops(text, ("moe.experts", "moe.shared", "mla"))
    assert got["mla"] == []
    assert any(n.startswith("while") for n in got["moe.experts"])
    assert got["moe.shared"] and not set(got["moe.shared"]) \
        & set(got["moe.experts"])


def _hand() -> dict:
    return {"host": [["trace_open", 0, 10], ["trace_close", 200, 201]],
            "modules": [["jit_eval_loss(1)", 20, 100],
                        ["jit_other(2)", 120, 150]],
            "ops": [["%while.3 = (s32[]) while(...)", 30, 60],
                    ["%fusion.7 = f32[8]{0} fusion(...)", 40, 50],
                    ["%fusion.8", 70, 80],
                    ["%fusion.7", 130, 140],        # another program's
                    ["%fusion.9", 90, 95]]}


def test_scope_seconds_of_a_hand_trace():
    got = scopes.scope_seconds(_hand(), ("jit_eval_loss",),
                               {"moe.experts": ["while.3", "fusion.7",
                                                "fusion.8"],
                                "mla": ["fusion.9"], "none": []})
    # 30..60 (fusion.7 inside it) and 70..80; not 130..140
    assert got["moe.experts"] == pytest.approx(40e-9)
    assert got["mla"] == pytest.approx(5e-9)
    assert got["none"] == 0
    # ops tied to a scope by their own stats count too
    got = scopes.scope_seconds(_hand(), ("jit_eval_loss",),
                               {"moe.experts": []},
                               {"%fusion.8": ("moe.experts",)})
    assert got["moe.experts"] == pytest.approx(10e-9)


def _obs(secs=1e-3, n=2):
    bench = cell.Bench(cell.ROOT)
    w = bench.workload(CELL)
    return {"trace": {"scopes": {"moe.experts": secs},
                      "programs": {"jit_eval_loss": {"s": 1.0, "n": n}}},
            "device_kind": "TPU v5 lite", "config": bench.config(w["config"])}


def _read(name, obs):
    bench = cell.Bench(cell.ROOT)
    return bench.reader(bench.workload(CELL), name).read(obs)


def test_expert_readers_follow_their_definitions(monkeypatch):
    routed = [[[100, 0] + [10] * 6] * 13, [[50] * 8] * 13]
    rows = [[256] * 13, [512] * 13]
    monkeypatch.setattr(counters, "values", lambda name: {
        "moe.routed": routed, "moe.rows": rows}[name])
    pairs = 13 * (160 + 400)
    assert _read("moe_padded_rows_share", {}) == pytest.approx(
        100 * (1 - pairs / (13 * 256 + 13 * 512)))
    # per request: work of its pairs, weights of its experts with pairs
    d, f = 2048, 1408
    roof = 0.0
    for r in routed:
        p = sum(map(sum, r))
        experts = sum(1 for layer in r for n in layer if n)
        roof += max(6 * d * f * p / 197e12,
                    4 * (3 * d * f * experts + 2 * d * p) / 819e9)
    assert _read("moe_experts_roofline", _obs(secs=0.05, n=2)) \
        == pytest.approx(100 * roof / 2 * 2 / 0.05)


def test_no_counters_no_reading(monkeypatch):
    monkeypatch.setattr(counters, "values", lambda name: [])
    assert _read("moe_padded_rows_share", {}) is None
    assert _read("moe_experts_roofline", _obs()) is None
    assert _read("moe_experts_roofline", {"trace": None}) is None
    # a program without the counters
    from repro.core import tracing
    monkeypatch.undo()
    monkeypatch.delattr(tracing, "counters")
    assert counters.values("moe.routed") == []
