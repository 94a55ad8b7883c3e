"""Manual (shard_map) expert parallelism == auto GSPMD path (subprocess
with fake devices). This is the correctness evidence for §Perf moe-2."""

import subprocess
import sys

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")   # no TPU probing in the sandbox
import jax, jax.numpy as jnp, numpy as np
from repro.models.moe import MoEDims, moe_init, moe_apply, moe_apply_manual

# capacity high enough that neither path drops tokens -> exact equality
dims = MoEDims(d_model=64, n_experts=8, top_k=2, d_expert=32, n_shared=2,
               capacity_factor=16.0)
key = jax.random.PRNGKey(0)
p = moe_init(key, dims, jnp.float32)
x = jax.random.normal(jax.random.fold_in(key, 1), (4, 16, 64))

y_auto, aux_auto = moe_apply(p, x, dims)

mesh = jax.make_mesh((2, 4), ("data", "model"))
with jax.set_mesh(mesh):
    y_man, aux_man = jax.jit(
        lambda p, x: moe_apply_manual(p, x, dims, mesh))(p, x)

np.testing.assert_allclose(np.asarray(y_man), np.asarray(y_auto),
                           rtol=2e-5, atol=2e-5)
# manual path computes the balance loss per data shard (the standard EP
# choice: balances per-device load); equal in expectation, not exactly
np.testing.assert_allclose(float(aux_man), float(aux_auto), atol=2e-3)
print("manual == auto OK")

# gradients flow through the manual path (psum + scatter transpose)
def loss(p):
    y, aux = moe_apply_manual(p, x, dims, mesh)
    return jnp.sum(y ** 2) + aux
with jax.set_mesh(mesh):
    g = jax.jit(jax.grad(loss))(p)
gn = sum(float(jnp.sum(jnp.abs(l))) for l in jax.tree_util.tree_leaves(g))
assert np.isfinite(gn) and gn > 0
print("grad-ok", gn)

# padded experts (10 -> 12 over tp=4): pads must never contribute
dims_pad = MoEDims(d_model=64, n_experts=10, top_k=2, d_expert=32,
                   capacity_factor=16.0, n_experts_padded=12)
p2 = moe_init(jax.random.fold_in(key, 2), dims_pad, jnp.float32)
y2_auto, _ = moe_apply(p2, x, dims_pad)
with jax.set_mesh(mesh):
    y2_man, _ = jax.jit(
        lambda p, x: moe_apply_manual(p, x, dims_pad, mesh))(p2, x)
np.testing.assert_allclose(np.asarray(y2_man), np.asarray(y2_auto),
                           rtol=2e-5, atol=2e-5)
print("padding OK")
"""


def test_manual_moe_matches_auto():
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root"},
        cwd="/root/repo")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "manual == auto OK" in proc.stdout
    assert "padding OK" in proc.stdout
