"""Step-function builders for every cell family.

``build_step(arch_id, cell, mesh)`` returns
``(step_fn, state_spec_or_None, batch_sharding_overrides)``:

* ``lsr_train``   — SPLADE-style contrastive train step: backbone +
  Sparton head (vocab-sharded via shard_map when a mesh is given),
  InfoNCE + FLOPS regularizers, AdamW with ZeRO-sharded moments,
  gradient accumulation.
* ``lsr_prefill`` — document/query encoding forward (serving).
* ``decode``      — one autoregressive step with a KV cache.
* ``gnn_train``   — DimeNet MSE training step.
* ``recsys_train``— pointwise CTR training (BCE, Adagrad).
* ``recsys_serve``— CTR forward.
* ``retrieval``   — query embedding + streaming top-k over candidates.

The steps are pure (state, batch) -> (state, metrics) functions ready
for jax.jit with explicit shardings (launch/dryrun.py, launch/train.py).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.configs.base import (DimeNetConfig, RecSysConfig,
                                TransformerConfig)
from repro.configs.specs import CellSpec
from repro.core.head_api import make_head
from repro.core.sharded import (sharded_flops_reg, sharded_infonce,
                                sharded_l1_reg, sharded_row_dots)
from repro.launch.mesh import batch_axes
from repro.launch.sharding import (batch_axes_for, batch_spec,
                                   dimenet_param_specs, recsys_param_specs,
                                   state_shardings, transformer_param_specs)
from repro.losses.contrastive import margin_mse_loss, splade_loss
from repro.models import dimenet as dimenet_model
from repro.models import recsys as recsys_model
from repro.models import transformer as tfm
from repro.optim.accumulation import microbatch_grads
from repro.optim.optimizers import adagrad, adamw, apply_updates
from repro.optim.schedules import linear_warmup_cosine

Array = jax.Array
PyTree = Any


# ---------------------------------------------------------------------------
# LM / LSR
# ---------------------------------------------------------------------------

def _moe_shard(cfg: TransformerConfig, mesh: Optional[Mesh]):
    if mesh is None or not cfg.is_moe:
        return None
    if cfg.experts_held % mesh.shape["model"] != 0:
        return None
    return (batch_axes(mesh), "model")


def _held_fixed(path) -> bool:
    """A leaf that steers expert selection only (``router_bias``): no
    gradient reaches it, and the train step leaves it out of AdamW's
    update, decay included."""
    return getattr(path[-1], "key", None) == "router_bias"


def _encode_fn(cfg: TransformerConfig, mesh: Optional[Mesh],
               n_batch: int, unroll: bool = False) -> Callable:
    """(params, tokens, mask) -> (Y, aux). Vocab-sharded when mesh.

    The head — any registered backend, Pallas kernel included — comes
    from the unified factory: ``make_head`` puts the selected impl
    inside the vocab-sharded shard_map body when a mesh is given (with
    kernel blocks resolved per *local* vocab shard) and pads a vocab
    the model axis does not divide.
    """
    moe_shard = _moe_shard(cfg, mesh)
    layer_unroll = cfg.n_layers if unroll else 1
    spec = cfg.head_spec()
    if mesh is not None:
        head = make_head(spec, mesh=mesh,
                         batch_axes=batch_axes_for(mesh, n_batch))
    else:
        head = make_head(spec)

    def encode(params, tokens, mask):
        Hs, aux = tfm.forward_hidden(params, cfg, tokens, mask,
                                     moe_shard=moe_shard, mesh=mesh,
                                     unroll=layer_unroll)
        E, b = tfm.head_weights(params, cfg)
        y = head(Hs, E.astype(Hs.dtype), b, mask)
        return y, aux
    return encode


def build_lsr_train_step(
    cfg: TransformerConfig,
    mesh: Optional[Mesh],
    *,
    n_micro: int = 1,
    n_pairs: int,
    lr: float = 2e-5,
    total_steps: int = 100_000,
    unroll: bool = False,
    param_specs: Any = None,
    zero_specs: Any = None,
) -> Callable:
    shard_fn = None
    if zero_specs is not None:
        shard_fn = lambda t: jax.lax.with_sharding_constraint(t, zero_specs)
    opt = adamw(linear_warmup_cosine(lr, 1000, total_steps),
                shard_fn=shard_fn)
    # the head/loss shard_maps see the *micro* batch
    micro_pairs = max(1, n_pairs // n_micro)
    encode = _encode_fn(cfg, mesh, micro_pairs, unroll)

    if mesh is not None and cfg.vocab_size % mesh.shape["model"] == 0:
        # vocab-sharded reps never gather, so the objective is
        # composed from the sharded primitives (same math as
        # losses.splade_loss / margin_mse_loss on the full arrays)
        baxes = batch_axes_for(mesh, micro_pairs)
        infonce = sharded_infonce(mesh, batch_axes=baxes)
        flops = sharded_flops_reg(mesh, batch_axes=baxes)
        l1 = sharded_l1_reg(mesh, batch_axes=baxes)
        row_dots = sharded_row_dots(mesh, batch_axes=baxes)

        def mb_loss(params, mb):
            yq, aux_q = encode(params, mb["q_tokens"], mb["q_mask"])
            yd, aux_d = encode(params, mb["d_tokens"], mb["d_mask"])
            loss = infonce(yq, yd)
            loss = loss + cfg.lambda_q * flops(yq) \
                + cfg.lambda_d * flops(yd)
            if cfg.l1_weight:
                loss = loss + cfg.l1_weight * (l1(yq) + l1(yd))
            if cfg.distill_weight and "neg_tokens" in mb:
                yn, _ = encode(params, mb["neg_tokens"], mb["neg_mask"])
                margin = row_dots(yq, yd) - row_dots(yq, yn)
                mse = jnp.mean((margin - mb["teacher_margin"]) ** 2)
                loss = loss + cfg.distill_weight * mse
            return loss + cfg.aux_weight * (aux_q + aux_d)
    else:
        def mb_loss(params, mb):
            yq, aux_q = encode(params, mb["q_tokens"], mb["q_mask"])
            yd, aux_d = encode(params, mb["d_tokens"], mb["d_mask"])
            loss = splade_loss(yq, yd,
                               lambda_q=cfg.lambda_q,
                               lambda_d=cfg.lambda_d,
                               l1_weight=cfg.l1_weight,
                               aux_loss=aux_q + aux_d,
                               aux_weight=cfg.aux_weight)
            if cfg.distill_weight and "neg_tokens" in mb:
                yn, _ = encode(params, mb["neg_tokens"], mb["neg_mask"])
                loss = loss + cfg.distill_weight * margin_mse_loss(
                    yq, yd, yn, mb["teacher_margin"])
            return loss

    grad_fn = jax.value_and_grad(mb_loss)

    micro_unroll = n_micro if unroll else 1

    def step(state, batch):
        # ZeRO-2 boundary: per-micro grads reduce-scatter to the
        # optimizer sharding inside the accumulation scan, so the fp32
        # accumulator AND every fp32 update temp live batch-sharded
        loss, grads = microbatch_grads(
            grad_fn, state["params"], batch, n_micro=n_micro,
            unroll=micro_unroll, grad_specs=zero_specs)
        with jax.named_scope("optimizer"):
            updates, opt_state = opt.update(
                grads, state["opt"], state["params"], state["step"])
            updates = jax.tree_util.tree_map_with_path(
                lambda path, u: jnp.zeros_like(u) if _held_fixed(path)
                else u, updates)
            # cast at the ZeRO sharding, THEN all-gather in param dtype
            updates = jax.tree.map(lambda u, p: u.astype(p.dtype),
                                   updates, state["params"])
            if param_specs is not None:
                updates = jax.lax.with_sharding_constraint(updates,
                                                           param_specs)
            params = apply_updates(state["params"], updates)
        new_state = {"params": params, "opt": opt_state,
                     "step": state["step"] + 1}
        return new_state, {"loss": loss}

    return step


def build_lsr_prefill_step(cfg: TransformerConfig, mesh: Optional[Mesh],
                           n_batch: int, unroll: bool = False) -> Callable:
    encode = _encode_fn(cfg, mesh, n_batch, unroll)

    def serve(params, batch):
        y, _ = encode(params, batch["tokens"], batch["mask"])
        return y
    return serve


def build_decode_step(cfg: TransformerConfig,
                      mesh: Optional[Mesh]) -> Callable:
    moe_shard = _moe_shard(cfg, mesh)

    def serve(params, batch):
        cache = {"k": batch["cache_k"], "v": batch["cache_v"]}
        logits, cache = tfm.decode_step(
            params, cfg, cache, batch["tokens"], batch["positions"],
            moe_shard=moe_shard)
        return logits, cache["k"], cache["v"]
    return serve


# ---------------------------------------------------------------------------
# GNN
# ---------------------------------------------------------------------------

def build_gnn_train_step(cfg: DimeNetConfig, cell: CellSpec,
                         *, lr: float = 1e-4,
                         shard_axes: Optional[Tuple[str, ...]] = None
                         ) -> Callable:
    opt = adamw(lr)

    def loss_fn(params, batch):
        if cell.n_graphs:
            pred = dimenet_model.forward_graph(
                params, cfg, batch, cell.n_graphs,
                shard_axes=shard_axes)
            err = pred - batch["target"]
            return jnp.mean(err * err)
        pred = dimenet_model.forward(params, cfg, batch,
                                     shard_axes=shard_axes)
        if "seed_ids" in batch:
            pred = jnp.take(pred, batch["seed_ids"], axis=0)
            err = pred - batch["target"]
            return jnp.mean(err * err)
        err = (pred - batch["target"]) \
            * batch["node_mask"].astype(pred.dtype)[:, None]
        return jnp.sum(err * err) / jnp.maximum(
            jnp.sum(batch["node_mask"]), 1.0)

    grad_fn = jax.value_and_grad(loss_fn)

    def step(state, batch):
        loss, grads = grad_fn(state["params"], batch)
        updates, opt_state = opt.update(
            grads, state["opt"], state["params"], state["step"])
        params = apply_updates(state["params"], updates)
        return ({"params": params, "opt": opt_state,
                 "step": state["step"] + 1}, {"loss": loss})
    return step


# ---------------------------------------------------------------------------
# RecSys
# ---------------------------------------------------------------------------

def build_recsys_train_step(cfg: RecSysConfig,
                            *, lr: float = 1e-2,
                            param_specs: Any = None,
                            zero_specs: Any = None) -> Callable:
    opt = adagrad(lr)

    def loss_fn(params, batch):
        logits = recsys_model.forward(params, cfg, batch)
        label = batch["label"]
        # numerically-stable BCE with logits
        loss = jnp.maximum(logits, 0) - logits * label \
            + jnp.log1p(jnp.exp(-jnp.abs(logits)))
        return jnp.mean(loss)

    grad_fn = jax.value_and_grad(loss_fn)

    def step(state, batch):
        loss, grads = grad_fn(state["params"], batch)
        if zero_specs is not None:
            grads = jax.lax.with_sharding_constraint(grads, zero_specs)
        updates, opt_state = opt.update(
            grads, state["opt"], state["params"], state["step"])
        if param_specs is not None:
            updates = jax.lax.with_sharding_constraint(updates, param_specs)
        params = apply_updates(state["params"], updates)
        return ({"params": params, "opt": opt_state,
                 "step": state["step"] + 1}, {"loss": loss})
    return step


def build_recsys_serve_step(cfg: RecSysConfig) -> Callable:
    def serve(params, batch):
        return jax.nn.sigmoid(recsys_model.forward(params, cfg, batch))
    return serve


def streaming_topk(q: Array, C: Array, *, k: int,
                   tile: int = 65536,
                   vary_axes: Optional[Tuple[str, ...]] = None
                   ) -> Tuple[Array, Array]:
    """Pure-JAX streaming top-k over candidate tiles (same algorithm as
    kernels/topk_score.py; the SPMD-lowerable path for the dry-run).

    ``vary_axes``: when called inside shard_map over sharded candidates,
    the scan carry must be marked device-varying over those axes."""
    B, D = q.shape
    N = C.shape[0]
    pad = (-N) % tile
    Cp = jnp.pad(C, ((0, pad), (0, 0)))
    n_tiles = Cp.shape[0] // tile
    C_t = Cp.reshape(n_tiles, tile, D)

    from repro.kernels.topk_score import merge_topk

    def body(carry, xs):
        vals, idx = carry
        c_tile, t = xs
        scores = jnp.einsum("bd,nd->bn", q, c_tile,
                            preferred_element_type=jnp.float32)
        ids = t * tile + jnp.arange(tile, dtype=jnp.int32)[None]
        ids = jnp.broadcast_to(ids, scores.shape)
        # padded rows score q.0 = 0 and would beat real negatives
        scores = jnp.where(ids < N, scores, -1e30)
        return merge_topk(vals, idx, scores, ids, k), None

    init = (jnp.full((B, k), -1e30, jnp.float32),
            jnp.zeros((B, k), jnp.int32))
    if vary_axes:
        init = jax.tree.map(
            lambda x: jax.lax.pcast(x, vary_axes, to="varying"), init)
    (vals, idx), _ = jax.lax.scan(
        body, init, (C_t, jnp.arange(n_tiles, dtype=jnp.int32)))
    return vals, idx


def build_retrieval_step(cfg: RecSysConfig, mesh: Optional[Mesh],
                         *, k: int = 100) -> Callable:
    """Query trunk + fused streaming top-k over 1M candidates.

    With a mesh the candidates are row-sharded over every axis: each
    device streams its local rows (shard_map), then the per-shard
    winners (n_shards × k) are gathered and merged — the (B, N) score
    matrix never exists anywhere (Sparton's memory story transferred)."""

    if mesh is None:
        def serve(params, batch):
            qv = recsys_model.user_embedding(params, cfg, batch)
            return streaming_topk(qv, batch["candidates"], k=k)
        return serve

    axes = tuple(mesh.axis_names)

    def sharded_body(qv, cand):
        rows_local = cand.shape[0]
        vals, idx = streaming_topk(qv, cand, k=k,
                                   tile=min(65536, rows_local),
                                   vary_axes=axes)
        # local ids -> global ids
        offset = jax.lax.axis_index(axes) * rows_local
        idx = idx + offset
        # merge across shards: gather (n_shards*k) winners, re-top-k
        all_v = jax.lax.all_gather(vals, axes, axis=1, tiled=True)
        all_i = jax.lax.all_gather(idx, axes, axis=1, tiled=True)
        v2, pos = jax.lax.top_k(all_v, k)
        i2 = jnp.take_along_axis(all_i, pos, axis=1)
        return v2, i2
    merged = jax.shard_map(
        sharded_body, mesh=mesh,
        in_specs=(P(), P(axes, None)),
        out_specs=(P(), P()),
        # the final top_k after the full all_gather IS replicated, but
        # the vma system cannot prove it — skip the check
        check_vma=False,
    )

    def serve(params, batch):
        qv = recsys_model.user_embedding(params, cfg, batch)
        return merged(qv, batch["candidates"])
    return serve


# ---------------------------------------------------------------------------
# unified builder
# ---------------------------------------------------------------------------

def init_state(arch_id: str, key: jax.Array,
               smoke: bool = False) -> Tuple[PyTree, str]:
    """(state pytree, opt layout) for the arch's train family."""
    mod = get_config(arch_id)
    cfg = mod.SMOKE if smoke else mod.CONFIG
    if isinstance(cfg, TransformerConfig):
        params = tfm.init_params(key, cfg)
        opt = adamw(1e-4)
        layout = "adamw"
    elif isinstance(cfg, DimeNetConfig):
        params = dimenet_model.init_params(key, cfg)
        opt = adamw(1e-4)
        layout = "adamw"
    else:
        params = recsys_model.init_params(key, cfg)
        opt = adagrad(1e-2)
        layout = "adagrad"
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    return state, layout


def arch_config_for_cell(arch_id: str, cell: CellSpec):
    """Per-cell config adaptation: DimeNet's input width is a property
    of the *shape* (atom types vs node-feature vectors)."""
    cfg = get_config(arch_id).CONFIG
    if isinstance(cfg, DimeNetConfig) and cfg.d_feat != cell.d_feat:
        cfg = dataclasses.replace(cfg, d_feat=cell.d_feat)
    return cfg


def build_step(arch_id: str, cell: CellSpec,
               mesh: Optional[Mesh], *, unroll: bool = False,
               param_specs: Any = None, zero_specs: Any = None
               ) -> Callable:
    cfg = arch_config_for_cell(arch_id, cell)
    kind = cell.step_kind
    if kind == "lsr_train":
        n_pairs = cell.batch["q_tokens"].shape[0]
        return build_lsr_train_step(cfg, mesh, n_micro=cell.n_micro,
                                    n_pairs=n_pairs, unroll=unroll,
                                    param_specs=param_specs,
                                    zero_specs=zero_specs)
    if kind == "lsr_prefill":
        return build_lsr_prefill_step(
            cfg, mesh, cell.batch["tokens"].shape[0], unroll=unroll)
    if kind == "decode":
        return build_decode_step(cfg, mesh)
    if kind == "gnn_train":
        shard_axes = None
        if mesh is not None:
            n_dev = mesh.devices.size
            if (cell.n_edges % n_dev == 0
                    and cell.n_triplets % n_dev == 0
                    and cell.n_nodes % n_dev == 0):
                shard_axes = tuple(mesh.axis_names)
        return build_gnn_train_step(cfg, cell, shard_axes=shard_axes)
    if kind == "recsys_train":
        return build_recsys_train_step(cfg, param_specs=param_specs,
                                       zero_specs=zero_specs)
    if kind == "recsys_serve":
        return build_recsys_serve_step(cfg)
    if kind == "retrieval":
        return build_retrieval_step(cfg, mesh)
    raise ValueError(f"unknown step kind {kind}")
