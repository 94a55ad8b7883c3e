"""Pipeline parallelism over the ``pod`` mesh axis (GPipe-style).

Why: cross-pod links are the slowest hop. Data parallelism over pods moves
a full gradient set per step (O(params)); a pipeline moves only microbatch
activations between adjacent stages (O(M * mb * S * d)), which for
param-heavy models (nemotron-4-340b: 680 GB of bf16 grads vs ~40 GB of
activation traffic) is the better trade — and it also shards the model
states across pods (halving per-device bytes). This module implements it
TPU-natively: ``shard_map`` manual over ``pod`` with ``data``/``model``
left on auto (GSPMD keeps the in-pod sharding), ``jax.lax.ppermute``
carrying stage outputs, GPipe clock schedule with M microbatches, and
autodiff straight through the schedule (ppermute transposes to the
reverse permute) with per-stage remat.

Restrictions: uniform single-stage architectures (the dense/MoE/MLA
families — pattern == one repeated unit) whose layer count divides the
pod count; frontends with extra inputs (vlm) keep the embed on stage 0.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models import layers as L
from ..models.model import Model, block_apply, _maybe_remat, _norm
from ..optim import adamw
from .state import TrainState


def split_stage_params(params: Any, n_stages: int) -> Any:
    """Reshape the scanned stage's stacked params (L, ...) ->
    (n_stages, L/n_stages, ...). Leaves embed/unembed/norms untouched."""
    def resplit(leaf):
        l = leaf.shape[0]
        assert l % n_stages == 0, "layers must divide pipeline stages"
        return leaf.reshape(n_stages, l // n_stages, *leaf.shape[1:])
    out = dict(params)
    assert len(params["stages"]) == 1, "pipeline needs a uniform stack"
    out["stages"] = [jax.tree_util.tree_map(resplit, params["stages"][0])]
    return out


def stage_param_specs(specs: Any) -> Any:
    """Prepend the 'pod' axis to the stage params' layer axis."""
    def respec(spec):
        return P("pod", *tuple(spec))
    out = dict(specs)
    out["stages"] = [jax.tree_util.tree_map(
        respec, specs["stages"][0],
        is_leaf=lambda x: isinstance(x, P))]
    return out


def make_pipeline_loss(model: Model, mesh, *, microbatches: int,
                       remat: str = "full") -> Callable:
    """Returns loss_fn(params, batch) running the layer stack as a
    ``pod``-axis pipeline. ``params['stages'][0]`` leaves must carry a
    leading (n_stages, L/stage) shape (see split_stage_params)."""
    cfg = model.cfg
    n_stages = mesh.shape["pod"]
    (pattern, repeat), = cfg.stages
    assert repeat % n_stages == 0

    def run_stage(stage_params, x):
        def body(carry, layer_params):
            xx = carry
            for bi, kind in enumerate(pattern):
                xx, _, _ = block_apply(layer_params[f"b{bi}"], xx, cfg, kind)
            return xx, None
        x, _ = jax.lax.scan(
            lambda c, lp: _maybe_remat(
                lambda cc, lpp: body(cc, lpp), remat
            )(c, lp) if remat != "none" else body(c, lp),
            x, stage_params)
        return x

    def mb_split(x):
        return x.reshape(microbatches, x.shape[0] // microbatches,
                         *x.shape[1:])

    def pipelined(params, batch):
        """Runs inside shard_map: manual over 'pod', auto data/model."""
        stage_params = jax.tree_util.tree_map(
            lambda x: x[0], params["stages"][0])      # local (L/P, ...)
        pod = jax.lax.axis_index("pod")
        m = microbatches
        ticks = m + n_stages - 1

        tokens_mb = mb_split(batch["tokens"])          # (M, mb, S)
        labels_mb = mb_split(batch["labels"])
        mb, s = tokens_mb.shape[1], tokens_mb.shape[2]
        d = cfg.d_model

        # pod-replicated leaves are used in f32: their grads cross pods via
        # psum, and XLA CPU's AllReducePromotion pass crashes on the bf16
        # variant (compiler bug workaround; on TPU bf16 would be fine)
        table = params["embed"]["table"].astype(jnp.float32)
        out_table = (params["embed"] if cfg.tie_embeddings
                     else params["unembed"])["table"].astype(jnp.float32)

        def tick(carry, t):
            boundary, acc_loss, acc_cnt = carry
            # stage 0 ingests microbatch t (if any); others take the
            # neighbour's output from the previous tick
            mb_idx = jnp.clip(t, 0, m - 1)
            toks = jax.lax.dynamic_index_in_dim(tokens_mb, mb_idx, 0,
                                                keepdims=False)
            x0 = L.embed({"table": table}, toks).astype(x0_dtype(params))
            x_in = jnp.where((pod == 0) & (t < m), x0, boundary)
            x_out = run_stage(stage_params, x_in)
            # last stage computes the loss for its arrived microbatch
            arr_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            labs = jax.lax.dynamic_index_in_dim(labels_mb, arr_idx, 0,
                                                keepdims=False)
            fn_params = jax.tree_util.tree_map(
                lambda v: v.astype(jnp.float32), params["final_norm"])
            h = _norm(cfg, fn_params, x_out).astype(jnp.float32)
            logits = jnp.einsum("bsd,vd->bsv", h, out_table,
                                preferred_element_type=jnp.float32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(
                logp, labs[..., None].astype(jnp.int32), axis=-1)[..., 0]
            msk = (labs >= 0).astype(jnp.float32)
            mb_loss = jnp.sum(nll * msk)
            mb_cnt = jnp.sum(msk)
            take = (pod == n_stages - 1) & (t >= n_stages - 1)
            acc_loss = acc_loss + jnp.where(take, mb_loss, 0.0)
            acc_cnt = acc_cnt + jnp.where(take, mb_cnt, 0.0)
            # hand my output to the next stage for the next tick
            boundary = jax.lax.ppermute(
                x_out, "pod",
                [(i, i + 1) for i in range(n_stages - 1)])
            return (boundary, acc_loss, acc_cnt), None

        b0 = jnp.zeros((mb, s, d), x0_dtype(params))
        (boundary, loss_sum, cnt), _ = jax.lax.scan(
            tick, (b0, jnp.zeros(()), jnp.zeros(())), jnp.arange(ticks))
        total = jax.lax.psum(loss_sum, "pod") \
            / jnp.maximum(jax.lax.psum(cnt, "pod"), 1.0)
        return total

    def x0_dtype(params):
        return params["embed"]["table"].dtype

    def loss_fn(params, batch):
        # pod-replicated leaves enter the shard_map in f32: their cotangent
        # psum (inserted by the shard_map transpose) must not be bf16 — the
        # XLA CPU AllReducePromotion pass crashes on bf16 all-reduce
        # (compiler bug workaround; semantics unchanged, grads cast back)
        params = dict(params)
        for name in ("embed", "unembed", "final_norm"):
            if name in params:
                params[name] = jax.tree_util.tree_map(
                    lambda v: v.astype(jnp.float32), params[name])
        pspecs = jax.tree_util.tree_map(lambda x: P(*([None] * x.ndim)),
                                        params)
        # stage params are pod-sharded on their leading axis
        pspecs["stages"] = [jax.tree_util.tree_map(
            lambda x: P("pod", *([None] * (x.ndim - 1))),
            params["stages"][0])]
        bspecs = jax.tree_util.tree_map(
            lambda x: P(*([None] * x.ndim)), batch)
        # manual over 'pod' only; data/model stay auto (GSPMD in-pod)
        fn = jax.shard_map(pipelined, mesh=mesh,
                       in_specs=(pspecs, bspecs), out_specs=P(),
                       axis_names={"pod"}, check_vma=False)
        return fn(params, batch)

    return loss_fn


def make_pipeline_train_step(model: Model, opt_cfg: adamw.AdamWConfig,
                             mesh, *, microbatches: int,
                             remat: str = "full") -> Callable:
    loss_fn = make_pipeline_loss(model, mesh, microbatches=microbatches,
                                 remat=remat)

    def train_step(state: TrainState, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        new_params, new_opt, metrics = adamw.apply_updates(
            opt_cfg, state.params, grads, state.opt)
        metrics["loss"] = loss
        return TrainState(new_params, new_opt), metrics

    return train_step
