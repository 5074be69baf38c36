"""Per-architecture sharding rules (DESIGN.md §5).

Conventions:
* activations / token batches — sharded over the batch axes
  (``pod`` × ``data``), or the largest prefix that divides the batch;
* attention heads, ffn hidden, experts, vocabulary rows, embedding
  table rows — sharded over ``model``;
* decode KV caches — batch over batch axes, sequence over ``model``
  (flash-decoding style; the 500k cell additionally spreads sequence
  over ``pod``);
* optimizer moments — param sharding *plus* one extra large dim over
  the batch axes (ZeRO-1): XLA turns the gradient reshard into a
  reduce-scatter and the param update into an all-gather.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import (DimeNetConfig, RecSysConfig,
                                TransformerConfig)
from repro.launch.mesh import batch_axes

PyTree = Any


def _ns(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def batch_axes_for(mesh: Mesh, n: int) -> Tuple[str, ...]:
    """Largest contiguous batch-axis combination whose product divides
    n (prefers more shards: ("pod","data") > ("data",) > ("pod",))."""
    baxes = batch_axes(mesh)
    candidates = []
    for i in range(len(baxes)):
        for j in range(i + 1, len(baxes) + 1):
            sub = baxes[i:j]
            prod = 1
            for ax in sub:
                prod *= mesh.shape[ax]
            candidates.append((prod, sub))
    candidates.sort(key=lambda t: -t[0])
    for prod, sub in candidates:
        if n % prod == 0:
            return sub
    return ()


def batch_spec(mesh: Mesh, n: int, rank: int) -> P:
    """P((batch axes), None, ...) for an (n, ...) batch array."""
    axes = batch_axes_for(mesh, n)
    lead = axes if axes else None
    return P(lead, *([None] * (rank - 1)))


# ---------------------------------------------------------------------------
# transformer params
# ---------------------------------------------------------------------------

def _divisible(dim: int, mesh: Mesh, axis: str) -> bool:
    return dim % mesh.shape[axis] == 0


def transformer_param_specs(cfg: TransformerConfig, mesh: Mesh
                            ) -> Dict[str, Any]:
    """PartitionSpec pytree matching models.transformer.init_params."""
    m = "model"

    def tp(dim_ok: bool, spec: P, fallback: P) -> P:
        return spec if dim_ok else fallback

    # §Perf (llama/phi3.5: kv=8 < model=16): shard k/v projections only
    # when KV HEADS divide the axis — a flat KV*dh split crosses head
    # boundaries and forces GSPMD reshards around the attention einsum.
    # Replicating the (small) k/v projections instead measured
    # -46% per-layer wire for +26% per-device flops on llama train
    # (EXPERIMENTS.md §Perf C.6).
    kv_aligned = cfg.n_kv_heads % mesh.shape[m] == 0
    if cfg.router == "sigmoid":
        return _latent_moe_specs(cfg, mesh, tp)
    attn = {
        "wq": tp(_divisible(cfg.n_heads * cfg.d_head, mesh, m),
                 P(None, None, m), P(None, None, None)),
        "wk": tp(kv_aligned, P(None, None, m), P(None, None, None)),
        "wv": tp(kv_aligned, P(None, None, m), P(None, None, None)),
        "wo": tp(_divisible(cfg.n_heads * cfg.d_head, mesh, m),
                 P(None, m, None), P(None, None, None)),
    }
    if cfg.is_moe:
        mlp = {
            "router": P(None, None, None),
            "w_gate": tp(_divisible(cfg.n_experts, mesh, m),
                         P(None, m, None, None), P(None, None, None, None)),
            "w_up": tp(_divisible(cfg.n_experts, mesh, m),
                       P(None, m, None, None), P(None, None, None, None)),
            "w_down": tp(_divisible(cfg.n_experts, mesh, m),
                         P(None, m, None, None), P(None, None, None, None)),
        }
    else:
        mlp = {
            "w_gate": tp(_divisible(cfg.d_ff, mesh, m),
                         P(None, None, m), P(None, None, None)),
            "w_up": tp(_divisible(cfg.d_ff, mesh, m),
                       P(None, None, m), P(None, None, None)),
            "w_down": tp(_divisible(cfg.d_ff, mesh, m),
                         P(None, m, None), P(None, None, None)),
        }
    vocab_ok = _divisible(cfg.vocab_size, mesh, m)
    specs: Dict[str, Any] = {
        "embed": P(m, None) if vocab_ok else P(None, None),
        "layers": {
            "attn": attn,
            "mlp": mlp,
            "ln1": P(None, None),
            "ln2": P(None, None),
        },
        "final_norm": P(None),
    }
    if cfg.tie_embeddings:
        specs["lm_head"] = {"b": P(m) if vocab_ok else P(None)}
    else:
        specs["lm_head"] = {
            "E": P(m, None) if vocab_ok else P(None, None),
            "b": P(m) if vocab_ok else P(None),
        }
    return specs


def _latent_moe_specs(cfg: TransformerConfig, mesh: Mesh, tp
                      ) -> Dict[str, Any]:
    """Specs for ``models.transformer._init_latent_moe``: MLA's head
    projections over heads (the latent and its norm replicated), the
    dense layers' FFN and the shared experts over their width, the
    routed experts over ``model`` (EP), router and bias replicated."""
    m = "model"
    heads = _divisible(cfg.n_heads, mesh, m)
    rep2, rep3 = P(None, None), P(None, None, None)
    attn = {"wq": tp(heads, P(None, None, m), rep3),
            "wkv_a": rep3, "kv_norm": rep2,
            "wkv_b": tp(heads, P(None, None, m), rep3),
            "wo": tp(heads, P(None, m, None), rep3)}

    def ffn(width):
        ok = _divisible(width, mesh, m)
        return {"w_gate": tp(ok, P(None, None, m), rep3),
                "w_up": tp(ok, P(None, None, m), rep3),
                "w_down": tp(ok, P(None, m, None), rep3)}

    experts = tp(_divisible(cfg.experts_held, mesh, m),
                 P(None, m, None, None), P(None, None, None, None))
    group = lambda mlp: {"attn": attn, "mlp": mlp, "ln1": rep2, "ln2": rep2}
    vocab_ok = _divisible(cfg.vocab_size, mesh, m)
    specs: Dict[str, Any] = {
        "embed": P(m, None) if vocab_ok else rep2,
        "layers": group({"router": rep3, "router_bias": rep2,
                         "w_gate": experts, "w_up": experts,
                         "w_down": experts,
                         "shared": ffn(cfg.n_shared_experts
                                       * cfg.expert_width)}),
        "final_norm": P(None),
        "lm_head": {"E": P(m, None) if vocab_ok else rep2,
                    "b": P(m) if vocab_ok else P(None)},
    }
    if cfg.n_dense_layers:
        specs["dense_layers"] = group(ffn(cfg.d_ff))
    return specs


# ---------------------------------------------------------------------------
# GNN / recsys params
# ---------------------------------------------------------------------------

def dimenet_param_specs(cfg: DimeNetConfig, mesh: Mesh) -> Any:
    """DimeNet params are small (<10M) — replicate everything."""
    from repro.models import dimenet as dn
    params = jax.eval_shape(
        lambda k: dn.init_params(k, cfg),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    return jax.tree.map(lambda l: P(*([None] * l.ndim)), params)


def recsys_param_specs(cfg: RecSysConfig, mesh: Mesh) -> Any:
    """Embedding-table rows shard over model (+data when huge);
    MLPs replicate."""
    from repro.models.recsys import padded_rows

    m = "model"
    baxes = batch_axes(mesh)
    row_shards_model = mesh.shape[m]

    def table_spec(raw_rows: int) -> P:
        # §Perf hillclimb (wide-deep/serve_bulk): REPLICATE small tables
        # (< ~128k rows -> < 16 MB at dim 32) so their lookups are
        # local and collective-free; only genuinely large tables shard
        # rows (model, +data when huge). Before: every lookup on a
        # sharded table costs a (batch, dim) psum — 40 psums/step of
        # 1.3 GB total on serve_bulk. After: 3 psums.
        rows = padded_rows(raw_rows)
        if rows < 131_072:
            return P(None, None)
        total = row_shards_model
        for ax in baxes:
            total *= mesh.shape[ax]
        if rows >= 1_000_000 and rows % total == 0:
            return P((m,) + baxes, None)
        if rows % row_shards_model == 0:
            return P(m, None)
        return P(None, None)

    def mlp_spec(layers):
        return [{"w": P(None, None), "b": P(None)} for _ in layers]

    if cfg.interaction == "dot":
        return {
            "tables": [table_spec(r) for r in cfg.table_sizes],
            "bot_mlp": mlp_spec(cfg.bot_mlp[:-1]),
            "top_mlp": mlp_spec(cfg.top_mlp),
        }
    if cfg.interaction == "cin":
        return {
            "tables": [table_spec(r) for r in cfg.table_sizes],
            "linear": [table_spec(r) for r in cfg.table_sizes],
            "cin": [P(None, None) for _ in cfg.cin_layers],
            "dnn": mlp_spec(cfg.mlp),
            "out": mlp_spec((1,)),
        }
    if cfg.interaction == "augru":
        gru = {"w": P(None, None), "u": P(None, None), "b": P(None)}
        return {
            "item_table": table_spec(cfg.table_sizes[0]),
            "gru1": dict(gru),
            "augru": dict(gru),
            "att": mlp_spec((1, 2)),
            "item_proj": mlp_spec((1,)),
            "mlp": mlp_spec(cfg.mlp + (1,)),
        }
    if cfg.interaction == "concat":
        return {
            "tables": [table_spec(r) for r in cfg.table_sizes],
            "wide": [table_spec(r) for r in cfg.table_sizes],
            "deep": mlp_spec(cfg.mlp + (1,)),
        }
    raise ValueError(cfg.interaction)


# ---------------------------------------------------------------------------
# optimizer state (ZeRO-1) + state assembly
# ---------------------------------------------------------------------------

def zero_spec(param_spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Param spec + the first free large dim sharded over the batch axes.

    Applied to optimizer moments: grads arrive param-sharded, XLA
    reshards to this with a reduce-scatter; params come back with an
    all-gather — ZeRO-1 without manual collectives.
    """
    baxes = batch_axes_for(mesh, 1 << 30)  # all batch axes
    if not baxes:
        return param_spec
    n_shards = 1
    for ax in baxes:
        n_shards *= mesh.shape[ax]
    entries = list(param_spec) + [None] * (len(shape) - len(param_spec))
    for i, (cur, dim) in enumerate(zip(entries, shape)):
        if cur is None and dim % n_shards == 0 and dim >= 512:
            entries[i] = baxes if len(baxes) > 1 else baxes[0]
            return P(*entries)
    return param_spec


def opt_state_specs(param_specs: PyTree, params_shape: PyTree,
                    mesh: Mesh) -> PyTree:
    """Map a param-spec pytree to moment specs (same treedef per moment
    dict level is handled by the caller wrapping in the opt layout)."""
    return jax.tree.map(
        lambda spec, leaf: zero_spec(spec, leaf.shape, mesh),
        param_specs, params_shape,
        is_leaf=lambda x: isinstance(x, P))


def state_shardings(param_specs: PyTree, params_shape: PyTree,
                    opt_layout: str, mesh: Mesh) -> Dict[str, Any]:
    """Build NamedShardings for the full train state
    {params, opt, step}."""
    zspecs = opt_state_specs(param_specs, params_shape, mesh)
    p_sh = jax.tree.map(lambda s: _ns(mesh, s), param_specs,
                        is_leaf=lambda x: isinstance(x, P))
    z_sh = jax.tree.map(lambda s: _ns(mesh, s), zspecs,
                        is_leaf=lambda x: isinstance(x, P))
    if opt_layout == "adamw":
        opt = {"mu": z_sh, "nu": z_sh}
    elif opt_layout == "adagrad":
        opt = {"acc": z_sh}
    elif opt_layout == "sgd":
        opt = {"v": z_sh}
    else:
        raise ValueError(opt_layout)
    return {
        "params": p_sh,
        "opt": opt,
        "step": _ns(mesh, P()),
    }


def batch_shardings(mesh: Mesh, batch_specs: Dict[str, Any],
                    overrides: Optional[Dict[str, P]] = None
                    ) -> Dict[str, NamedSharding]:
    """Default: shard dim 0 over the divisible batch-axis prefix."""
    out = {}
    for name, sds in batch_specs.items():
        if overrides and name in overrides:
            out[name] = _ns(mesh, overrides[name])
        else:
            out[name] = _ns(mesh, batch_spec(mesh, sds.shape[0], sds.ndim))
    return out
