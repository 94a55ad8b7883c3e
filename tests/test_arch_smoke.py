"""Per-architecture smoke tests: reduced config, one forward + train-ish
step on CPU, asserting output shapes and no NaNs (deliverable f)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, all_archs, get_arch
from repro.models import Model

B, S = 2, 32


def _batch(cfg, key):
    kt, kl, kv, kf = jax.random.split(key, 4)
    batch = {}
    if cfg.frontend == "audio":
        batch["frames"] = jax.random.normal(kf, (B, S, cfg.frontend_dim))
    else:
        batch["tokens"] = jax.random.randint(kt, (B, S), 0, cfg.vocab_size)
    batch["labels"] = jax.random.randint(kl, (B, S), 0, cfg.vocab_size)
    if cfg.rope_kind == "mrope":
        pos = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S)).astype(jnp.int32)
        batch["positions"] = jnp.stack([pos, pos, pos])
        batch["vision_embeds"] = jax.random.normal(kv, (B, 4, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", all_archs())
def test_forward_and_grad_step(arch):
    cfg = get_arch(arch, smoke=True)
    model = Model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    batch = _batch(cfg, jax.random.PRNGKey(1))

    logits, aux = jax.jit(model.apply)(params, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all(), "NaN/inf in logits"

    (loss, metrics), grads = jax.jit(
        jax.value_and_grad(model.loss, has_aux=True))(params, batch)
    assert np.isfinite(float(loss))
    gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                         for g in jax.tree_util.tree_leaves(grads)))
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0

    # one SGD step must reduce nothing catastrophic (loss finite after)
    params2 = jax.tree_util.tree_map(lambda p, g: p - 1e-3 * g, params, grads)
    loss2, _ = jax.jit(model.loss)(params2, batch)
    assert np.isfinite(float(loss2))


@pytest.mark.parametrize("arch", [a for a in all_archs()
                                  if get_arch(a).decode_capable])
def test_decode_step_matches_prefill(arch):
    """Greedy decode consistency: running S tokens through decode_step one
    at a time must match the full-sequence forward (same final logits)."""
    cfg = get_arch(arch, smoke=True)
    if cfg.rope_kind == "mrope":
        pytest.skip("mrope decode uses text-position fast path; covered by "
                    "shape test below")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, 8), 0,
                              cfg.vocab_size)
    full_logits, _ = model.apply(params, {"tokens": toks})

    cache = model.init_cache(B, max_seq=16, dtype=jnp.float32)
    step = jax.jit(model.decode_step)
    for t in range(8):
        logits, cache = step(params, cache, toks[:, t:t + 1])
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(full_logits[:, -1]),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("arch", [a for a in all_archs()
                                  if get_arch(a).decode_capable])
def test_decode_step_shapes(arch):
    cfg = get_arch(arch, smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache = model.init_cache(B, max_seq=16, dtype=jnp.float32)
    logits, new_cache = jax.jit(model.decode_step)(
        params, cache, jnp.zeros((B, 1), jnp.int32))
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    # cache structure preserved
    assert jax.tree_util.tree_structure(cache) \
        == jax.tree_util.tree_structure(new_cache)


def test_cell_applicability_matrix():
    """34 runnable cells of 44 (DESIGN.md §6)."""
    runnable = 0
    for arch in all_archs():
        cfg = get_arch(arch)
        for shape in SHAPES.values():
            ok, why = cfg.supports(shape)
            runnable += ok
            if not ok:
                assert why
    assert runnable == 34
