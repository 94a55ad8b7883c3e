"""DeepSeek-V2-Lite's layers against the plain reference
(``bench/dsv2_ref.py``) at a small size: MLA with a direct query
projection and YaRN, the dropless held-experts MoE, and the share test of
an expert-parallel layer (the parts of all shares, with the shared experts
counted once, add up to the uncut layer)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import dsv2_ref as R
from repro.models import moe
from repro.models.layers import Yarn
from repro.models.mla import MLADims, mla_apply, mla_init
from repro.models.moe import MoEDims, moe_apply_held, moe_init

D, H, T = 64, 4, 48
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}


def _config(**kw) -> dict:
    c = {"hidden_size": D, "num_attention_heads": H, "qk_nope_head_dim": 8,
         "qk_rope_head_dim": 16, "v_head_dim": 8, "kv_lora_rank": 16,
         "intermediate_size": 96, "moe_intermediate_size": 24,
         "n_routed_experts_published": 16, "experts_held": [0, 16],
         "num_experts_per_tok": 6, "n_shared_experts": 2,
         "vocab_size": 64, "num_hidden_layers": 2, "rms_norm_eps": 1e-6,
         "rope_theta": 10000, "rope_scaling": YARN,
         "routed_scaling_factor": 1, "first_k_dense_replace": 1}
    c.update(kw)
    return c


def _mm():
    return R._matmul(R.HIGHEST)


def _x(seed=1, t=T):
    return jax.random.normal(jax.random.PRNGKey(seed), (1, t, D))


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Blocks of 8 rows, so that an expert's pairs span several."""
    monkeypatch.setattr(moe, "BLOCK_ROWS", 8)


def _moe_dims(c, held) -> MoEDims:
    return MoEDims(d_model=D, n_experts=c["n_routed_experts_published"],
                   top_k=c["num_experts_per_tok"],
                   d_expert=c["moe_intermediate_size"],
                   n_shared=c["n_shared_experts"], norm_topk=False,
                   experts_held=held)


def _held(p, x, dims):
    return jax.jit(moe_apply_held, static_argnums=2)(p, x, dims)


def _ref_moe(p, x, c, **kw):
    # the held layer's parameters are named as the reference names them
    return jax.jit(lambda p, x: R._moe(p, x, c, _mm(), **kw))(p, x)


def test_yarn_frequencies_and_scale():
    """The published correction range (dims 10..23 of 32) and mscale."""
    y = Yarn(factor=40.0, original_max_position=4096, mscale=0.707,
             mscale_all_dim=0.707)
    f = y.freqs(64, 1e4)
    base = 1.0 / 1e4 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(f[:11], base[:11])
    np.testing.assert_allclose(f[23:], base[23:] / 40)
    assert np.all(f[11:23] < base[11:23]) and np.all(f[11:23] > base[11:23]
                                                     / 40)
    np.testing.assert_allclose(f, R.yarn_inv_freq(_config(
        qk_rope_head_dim=64)))
    assert y.cos_sin_scale == 1.0
    assert y.softmax_scale == pytest.approx(
        (0.1 * 0.707 * np.log(40) + 1) ** 2)
    assert y.softmax_scale == pytest.approx(1.26080 ** 2, rel=1e-4)


def test_mla_direct_query_with_yarn_matches_reference():
    c = _config()
    dims = MLADims(d_model=D, n_heads=H, q_lora_rank=None,
                   kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=16,
                   v_head_dim=8, rope_interleaved=True,
                   yarn=Yarn(factor=40.0, original_max_position=4096,
                             mscale=0.707, mscale_all_dim=0.707))
    p = mla_init(jax.random.PRNGKey(0), dims)
    assert "wq" in p and "wq_a" not in p
    x = _x()
    with jax.default_matmul_precision("highest"):
        got, _ = jax.jit(lambda p, x: mla_apply(p, x, dims,
                                                rope_theta=1e4))(p, x)
    ref = jax.jit(lambda p, x: R._mla(
        {"wq": p["wq"], "wkv_a": p["wkv_a"],
         "kv_norm": p["kv_a_norm"]["scale"], "wkv_b": p["wkv_b"],
         "wo": p["wo"]}, x, c, _mm()))(p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("held", [(0, 16), (4, 4)])
def test_held_experts_layer_matches_reference(held):
    c = _config(experts_held=list(held))
    p = moe_init(jax.random.PRNGKey(2), _moe_dims(c, held))
    x = _x()
    with jax.default_matmul_precision("highest"):
        got, _, stats = _held(p, x, _moe_dims(c, held))
    ref = _ref_moe(p, x, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)
    routed = np.asarray(stats["moe_routed"])
    assert routed.shape == (held[1],)
    rows = int(stats["moe_rows"])
    assert rows % 8 == 0 and routed.sum() <= rows \
        <= routed.sum() + 7 * held[1]


def test_dropless_under_the_worst_skew():
    """Every token routes to the same held experts: a capacity layer would
    drop most of them; this one computes them all."""
    c = _config(experts_held=[0, 8])
    dims = _moe_dims(c, (0, 8))
    p = moe_init(jax.random.PRNGKey(3), dims)
    boost = jnp.zeros((16,)).at[:6].set(50.0)
    p["router"] = p["router"] * 0.0 + boost[None, :] / D
    x = jnp.abs(_x(4)) + 1.0                   # every token favours 0..5
    with jax.default_matmul_precision("highest"):
        got, _, stats = _held(p, x, dims)
    assert np.asarray(stats["moe_routed"]).tolist() == [T] * 6 + [0, 0]
    ref = _ref_moe(p, x, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)
    dropped = _ref_moe(p, x, c, capacity=1.25)
    assert not np.allclose(np.asarray(dropped), np.asarray(ref), atol=1e-3)


def test_shares_sum_to_the_uncut_layer():
    """Eight chips of an expert-parallel layer, two experts each: the sum
    of their parts, the shared experts counted once, is the uncut layer
    of the reference."""
    c = _config()
    x = _x(5)
    key = jax.random.PRNGKey(6)
    parts, routed = [], []
    with jax.default_matmul_precision("highest"):
        for share in range(8):
            held = (2 * share, 2)
            p = moe_init(key, _moe_dims(c, held))
            y, _, stats = _held(p, x, _moe_dims(c, held))
            parts.append(np.asarray(y))
            routed += np.asarray(stats["moe_routed"]).tolist()
        shared = np.asarray(jax.jit(
            lambda p, x: R._swiglu(p, x, _mm()))(p["shared"], x))
    whole = moe_init(key, _moe_dims(c, (0, 16)))
    ref = np.asarray(_ref_moe(whole, x, c))
    np.testing.assert_allclose(sum(parts) - 7 * shared, ref, rtol=1e-4,
                               atol=1e-4)
    assert sum(routed) == T * 6
