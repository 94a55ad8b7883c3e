"""Model assembly: builds any assigned architecture from an ArchConfig.

Layer kinds ("blocks"):
  attn    pre-norm GQA/MQA attention + pre-norm MLP
  moe     pre-norm attention + pre-norm MoE (shared + routed experts)
  dense   like attn but with a dedicated dense-FFN width (deepseek layer 0)
  mla     pre-norm Multi-head Latent Attention + pre-norm MLP
  lattn   local (windowed) attention + MLP (recurrentgemma)
  rglru   RG-LRU recurrent temporal mixing + MLP (recurrentgemma)
  mlstm   self-contained mLSTM block (xLSTM)
  slstm   self-contained sLSTM block (xLSTM)

The attention of attn, moe, dense and mla blocks is MLA where the config
has MLA dims (MiniCPM3, DeepSeek-V2), and GQA otherwise.

The stack is described by *stages*: ``(pattern, repeat)`` pairs. A stage
with repeat>1 has its parameters stacked on a leading axis and is executed
with ``jax.lax.scan`` so compile time and HLO size are depth-independent —
essential for 80-96-layer configs on the dry-run host.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Any

import jax
import jax.numpy as jnp

from . import layers as L
from . import mla as MLA
from . import moe as MOE
from . import rglru as RG
from . import xlstm as XL

if TYPE_CHECKING:                      # avoid circular import (configs -> models)
    from ..configs.base import ArchConfig
else:
    ArchConfig = Any

Params = dict


# --------------------------------------------------------------------------
# Per-block init / apply / cache dispatch
# --------------------------------------------------------------------------

def _attn_dims(cfg: ArchConfig, kind: str) -> L.AttnDims:
    return L.AttnDims(cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)


def block_init(key, cfg: ArchConfig, kind: str, dtype) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    norm_init = (L.layernorm_init if cfg.norm == "layernorm"
                 else L.rmsnorm_init)
    d = cfg.d_model
    if kind in ("attn", "lattn", "dense", "moe", "mla"):
        assert kind != "mla" or cfg.mla is not None
        attn = (MLA.mla_init(k1, cfg.mla, dtype) if cfg.mla is not None
                else L.attention_init(k1, _attn_dims(cfg, kind), dtype,
                                      qk_norm=cfg.qk_norm))
        p = {"ln1": norm_init(d, dtype), "attn": attn,
             "ln2": norm_init(d, dtype)}
        if kind == "moe":
            assert cfg.moe is not None
            p["moe"] = MOE.moe_init(k2, cfg.moe, dtype)
        elif kind == "dense":
            p["mlp"] = L.mlp_init(k2, d, cfg.moe_dense_ff or cfg.d_ff,
                                  cfg.mlp_kind, dtype)
        else:
            p["mlp"] = L.mlp_init(k2, d, cfg.d_ff, cfg.mlp_kind, dtype)
        return p
    if kind == "rglru":
        assert cfg.rglru is not None
        return {"ln1": norm_init(d, dtype),
                "rec": RG.rglru_block_init(k1, cfg.rglru, dtype),
                "ln2": norm_init(d, dtype),
                "mlp": L.mlp_init(k2, d, cfg.d_ff, cfg.mlp_kind, dtype)}
    if kind == "mlstm":
        assert cfg.xlstm is not None
        return {"ln1": norm_init(d, dtype),
                "cell": XL.mlstm_block_init(k1, cfg.xlstm, dtype)}
    if kind == "slstm":
        assert cfg.xlstm is not None
        return {"cell": XL.slstm_block_init(k1, cfg.xlstm, dtype)}
    raise ValueError(f"unknown block kind {kind!r}")


def _norm(cfg: ArchConfig, p: Params, x):
    if cfg.norm == "layernorm":
        return L.layernorm(p, x, cfg.norm_eps)
    return L.rmsnorm(p, x, cfg.norm_eps)


def block_apply(p: Params, x, cfg: ArchConfig, kind: str, *,
                positions=None, cache=None, mesh=None):
    """Returns (x_out, aux, new_cache, stats): ``stats`` maps names to
    the block's counts (a held-experts MoE's, ``moe.moe_apply_held``),
    empty for every other block."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attn", "lattn", "dense", "moe", "mla"):
        xn = _norm(cfg, p["ln1"], x)
        if cfg.mla is not None:
            h, new_cache = MLA.mla_apply(
                p["attn"], xn, cfg.mla, positions=positions, cache=cache,
                rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps)
        else:
            window = cfg.attn_window if kind == "lattn" else None
            h, new_cache = L.attention_apply(
                p["attn"], xn, _attn_dims(cfg, kind),
                positions=positions, rope_kind=cfg.rope_kind,
                mrope_sections=cfg.mrope_sections,
                rope_theta=cfg.rope_theta, causal=cfg.causal,
                window=window, cache=cache, norm_eps=cfg.norm_eps,
                mesh=mesh)
        x = x + h
        h2 = _norm(cfg, p["ln2"], x)
        stats: dict = {}
        if kind == "moe":
            manual = (mesh is not None and "model" in mesh.axis_names
                      and cfg.moe.e_pad % mesh.shape["model"] == 0)
            if cfg.moe.experts_held is not None:
                y, aux, stats = MOE.moe_apply_held(p["moe"], h2, cfg.moe)
            elif manual:
                y, aux = MOE.moe_apply_manual(p["moe"], h2, cfg.moe, mesh)
            else:
                y, aux = MOE.moe_apply(p["moe"], h2, cfg.moe)
        else:
            y = L.mlp_apply(p["mlp"], h2, cfg.mlp_kind)
        return x + y, aux, new_cache, stats
    if kind == "rglru":
        h, new_cache = RG.rglru_block_apply(
            p["rec"], _norm(cfg, p["ln1"], x), cfg.rglru, cache=cache)
        x = x + h
        y = L.mlp_apply(p["mlp"], _norm(cfg, p["ln2"], x), cfg.mlp_kind)
        return x + y, aux, new_cache, {}
    if kind == "mlstm":
        h, new_cache = XL.mlstm_block_apply(
            p["cell"], _norm(cfg, p["ln1"], x), cfg.xlstm, cache=cache)
        return x + h, aux, new_cache, {}
    if kind == "slstm":
        h, new_cache = XL.slstm_block_apply(p["cell"], x, cfg.xlstm,
                                            cache=cache)
        return x + h, aux, new_cache, {}
    raise ValueError(kind)


def block_cache_init(cfg: ArchConfig, kind: str, batch: int, max_seq: int,
                     dtype) -> Params | None:
    if kind in ("attn", "dense", "moe", "mla") and cfg.mla is not None:
        return MLA.mla_cache_init(batch, max_seq, cfg.mla, dtype)
    if kind in ("attn", "dense", "moe"):
        return L.attention_cache_init(batch, max_seq,
                                      _attn_dims(cfg, kind), dtype)
    if kind == "lattn":
        return L.attention_cache_init(
            batch, min(max_seq, cfg.attn_window or max_seq),
            _attn_dims(cfg, kind), dtype)
    if kind == "rglru":
        return RG.rglru_cache_init(batch, cfg.rglru, jnp.float32)
    if kind == "mlstm":
        return XL.mlstm_cache_init(batch, cfg.xlstm, jnp.float32)
    if kind == "slstm":
        return XL.slstm_cache_init(batch, cfg.xlstm, jnp.float32)
    raise ValueError(kind)


# --------------------------------------------------------------------------
# Model
# --------------------------------------------------------------------------

def _stack(trees: list):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _maybe_remat(fn, remat: str):
    """Activation-checkpoint policies: none | full | dots."""
    if remat == "none":
        return fn
    if remat == "full":
        return jax.checkpoint(fn)
    if remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    raise ValueError(f"unknown remat policy {remat!r}")


class Model:
    def __init__(self, cfg: ArchConfig, remat: str = "none", mesh=None):
        self.cfg = cfg
        self.remat = remat
        # when a production mesh is bound, MoE blocks use the manual
        # expert-parallel path (shard_map; see moe.moe_apply_manual)
        self.mesh = mesh

    # -- params ---------------------------------------------------------------

    def init(self, key, dtype=jnp.float32) -> Params:
        cfg = self.cfg
        keys = jax.random.split(key, 4)
        p: Params = {"embed": L.embedding_init(keys[0], cfg.vocab_size,
                                               cfg.d_model, dtype)}
        if cfg.frontend == "audio":
            p["frontend"] = {
                "proj": L._he(keys[1], (cfg.frontend_dim, cfg.d_model),
                              cfg.frontend_dim ** -0.5, dtype),
                "convpos": L.convpos_init(jax.random.fold_in(keys[1], 1),
                                          cfg.d_model, dtype=dtype)}
        norm_init = (L.layernorm_init if cfg.norm == "layernorm"
                     else L.rmsnorm_init)
        p["final_norm"] = norm_init(cfg.d_model, dtype)
        if not cfg.tie_embeddings:
            p["unembed"] = L.embedding_init(keys[2], cfg.vocab_size,
                                            cfg.d_model, dtype)
        stages = []
        kb = keys[3]
        for si, (pattern, repeat) in enumerate(cfg.stages):
            ks = jax.random.split(jax.random.fold_in(kb, si), repeat)
            reps = [
                {f"b{bi}": block_init(jax.random.fold_in(ks[r], bi), cfg,
                                      kind, dtype)
                 for bi, kind in enumerate(pattern)}
                for r in range(repeat)
            ]
            stages.append(_stack(reps) if repeat > 1 else reps[0])
        p["stages"] = stages
        return p

    # -- forward --------------------------------------------------------------

    def _frontend(self, params: Params, batch: dict) -> jax.Array:
        cfg = self.cfg
        if cfg.frontend == "audio":
            x = jnp.einsum("bsf,fd->bsd", batch["frames"],
                           params["frontend"]["proj"])
            return x + L.convpos_apply(params["frontend"]["convpos"], x)
        x = L.embed(params["embed"], batch["tokens"])
        if cfg.frontend == "vision" and "vision_embeds" in batch:
            ve = batch["vision_embeds"].astype(x.dtype)    # (B, P, d)
            npatch = ve.shape[1]
            mask = (jnp.arange(x.shape[1]) < npatch)[None, :, None]
            pad = jnp.zeros((x.shape[0], x.shape[1] - npatch, x.shape[2]),
                            x.dtype)
            x = jnp.where(mask, jnp.concatenate([ve, pad], 1), x)
        return x

    def apply(self, params: Params, batch: dict,
              ) -> tuple[jax.Array, jax.Array]:
        """Full-sequence forward. Returns (logits fp32, aux_loss)."""
        return self.forward(params, batch)[:2]

    def forward(self, params: Params, batch: dict,
                ) -> tuple[jax.Array, jax.Array, dict]:
        """Full-sequence forward. Returns (logits fp32, aux_loss, stats):
        ``stats`` stacks each block's counts (``block_apply``) over
        the blocks that have them, in layer order; empty for most
        models."""
        cfg = self.cfg
        x = self._frontend(params, batch)
        positions = batch.get("positions")
        aux = jnp.zeros((), jnp.float32)
        stats: list = []              # per stage: name -> (layers, ...)

        def unit(xx, lp, pattern):
            acc = jnp.zeros((), jnp.float32)
            st: list = []
            for bi, kind in enumerate(pattern):
                xx, a, _, s = block_apply(lp[f"b{bi}"], xx, cfg, kind,
                                          positions=positions,
                                          mesh=self.mesh)
                acc = acc + a
                if s:
                    st.append(s)
            return xx, acc, st

        for (pattern, repeat), sp in zip(cfg.stages, params["stages"]):
            if repeat == 1:
                x, a, st = _maybe_remat(
                    lambda xx, _sp=sp, _pattern=pattern:
                    unit(xx, _sp, _pattern), self.remat)(x)
                aux = aux + a
                stats += [jax.tree_util.tree_map(lambda v: v[None], s)
                          for s in st]
            else:
                def body(carry, layer_params, _pattern=pattern):
                    xx, acc0 = carry
                    xx, a, st = _maybe_remat(
                        lambda xx, lp: unit(xx, lp, _pattern),
                        self.remat)(xx, layer_params)
                    return (xx, acc0 + a), (st or None)
                (x, aux), st = jax.lax.scan(body, (x, aux), sp)
                if st is not None:
                    # (repeat, ...) per block of the unit -> layer order
                    stats.append(jax.tree_util.tree_map(
                        lambda *v: jnp.stack(v, 1).reshape(-1, *v[0].shape[1:]),
                        *st))
        x = _norm(cfg, params["final_norm"], x)
        table = (params["embed"] if cfg.tie_embeddings
                 else params["unembed"])["table"]
        logits = jnp.einsum("bsd,vd->bsv", x, table,
                            preferred_element_type=jnp.float32)
        if cfg.logits_softcap:
            c = cfg.logits_softcap
            logits = jnp.tanh(logits / c) * c
        stats = (jax.tree_util.tree_map(
            lambda *v: jnp.concatenate(v), *stats) if stats else {})
        return logits, aux, stats

    def loss(self, params: Params, batch: dict) -> tuple[jax.Array, dict]:
        """(ce + aux, {"ce", "aux"} and the forward's ``stats``)."""
        logits, aux, stats = self.forward(params, batch)
        labels = batch["labels"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32),
                                   axis=-1)[..., 0]
        mask = (labels >= 0).astype(jnp.float32)
        ce = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        total = ce + aux
        return total, {"ce": ce, "aux": aux, **stats}

    # -- decode ---------------------------------------------------------------

    def init_cache(self, batch: int, max_seq: int,
                   dtype=jnp.bfloat16) -> list:
        cfg = self.cfg
        caches = []
        for pattern, repeat in cfg.stages:
            reps = [
                {f"b{bi}": block_cache_init(cfg, kind, batch, max_seq, dtype)
                 for bi, kind in enumerate(pattern)}
                for _ in range(repeat)
            ]
            caches.append(_stack(reps) if repeat > 1 else reps[0])
        return caches

    def decode_step(self, params: Params, cache: list, tokens: jax.Array,
                    ) -> tuple[jax.Array, list]:
        """One token for every sequence. tokens: (B, 1) int32."""
        cfg = self.cfg
        x = L.embed(params["embed"], tokens)
        new_caches = []
        for (pattern, repeat), sp, sc in zip(cfg.stages, params["stages"],
                                             cache):
            if repeat == 1:
                nc = {}
                for bi, kind in enumerate(pattern):
                    x, _, c, _ = block_apply(sp[f"b{bi}"], x, cfg, kind,
                                          cache=sc[f"b{bi}"],
                                          mesh=self.mesh)
                    nc[f"b{bi}"] = c
                new_caches.append(nc)
            else:
                def body(xx, slice_, _pattern=pattern):
                    layer_params, layer_cache = slice_
                    nc = {}
                    for bi, kind in enumerate(_pattern):
                        xx, _, c, _ = block_apply(layer_params[f"b{bi}"], xx,
                                               cfg, kind,
                                               cache=layer_cache[f"b{bi}"],
                                               mesh=self.mesh)
                        nc[f"b{bi}"] = c
                    return xx, nc
                x, nc = jax.lax.scan(body, x, (sp, sc))
                new_caches.append(nc)
        x = _norm(cfg, params["final_norm"], x)
        table = (params["embed"] if cfg.tie_embeddings
                 else params["unembed"])["table"]
        logits = jnp.einsum("bsd,vd->bsv", x, table,
                            preferred_element_type=jnp.float32)
        if cfg.logits_softcap:
            c = cfg.logits_softcap
            logits = jnp.tanh(logits / c) * c
        return logits, new_caches

    def param_count(self, dtype=jnp.float32) -> int:
        shapes = jax.eval_shape(lambda k: self.init(k, dtype),
                                jax.random.PRNGKey(0))
        return sum(int(np.prod(l.shape)) for l in
                   jax.tree_util.tree_leaves(shapes))


import numpy as np  # noqa: E402  (used by param_count)
