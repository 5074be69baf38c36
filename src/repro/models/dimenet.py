"""DimeNet — directional message passing GNN [arXiv:2003.03123].

Kernel regime: *triplet gather* (B.3 of the kernel taxonomy) — messages
live on directed edges and are updated by aggregating over (k->j->i)
triplets with a joint radial x angular basis. Message passing is
expressed as ``jnp.take`` + ``jax.ops.segment_sum`` over index lists
(JAX has no CSR SpMM; see repro/sparse/segment.py).

Structure per the paper: radial Bessel basis with polynomial envelope,
spherical (distance x angle) basis on triplets, embedding block, 6
interaction blocks with an ``n_bilinear``-rank bilinear sbf layer, and
per-block output projections summed into node outputs. The spherical
basis uses sin-Bessel x cos(l*angle) products (structurally matching
n_spherical x n_radial; exact spherical Bessel roots are a tabulated
detail with no systems impact — noted in DESIGN.md).

Graph regimes supported (the assigned shapes):
* molecules (batched small graphs; graph-level targets, exact triplets)
* full-graph (cora-size and ogb-products-size; node-level targets,
  synthetic coordinates, capped triplets per edge)
* sampled minibatch (fanout sampler; flattened hop-block edges)
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import DimeNetConfig
from repro.sparse.segment import segment_sum

Array = jax.Array
Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# bases
# ---------------------------------------------------------------------------

def envelope(d_scaled: Array, p: int) -> Array:
    """Polynomial cutoff envelope u(d) from the paper (eq. 8)."""
    a = -(p + 1) * (p + 2) / 2
    b = p * (p + 2)
    c = -p * (p + 1) / 2
    e = (1.0 / jnp.maximum(d_scaled, 1e-9)
         + a * d_scaled ** (p - 1) + b * d_scaled ** p
         + c * d_scaled ** (p + 1))
    return jnp.where(d_scaled < 1.0, e, 0.0)


def radial_basis(d: Array, cfg: DimeNetConfig) -> Array:
    """(E,) distances -> (E, n_radial) enveloped sin-Bessel basis."""
    ds = d / cfg.cutoff
    n = jnp.arange(1, cfg.n_radial + 1, dtype=jnp.float32)
    env = envelope(ds, cfg.envelope_exponent)
    return (env[:, None] * jnp.sqrt(2.0 / cfg.cutoff)
            * jnp.sin(n[None, :] * jnp.pi * ds[:, None]))


def spherical_basis(d: Array, angle: Array, cfg: DimeNetConfig) -> Array:
    """(T,) in-edge distances + (T,) angles -> (T, n_sph * n_rad)."""
    ds = d / cfg.cutoff
    env = envelope(ds, cfg.envelope_exponent)
    n = jnp.arange(1, cfg.n_radial + 1, dtype=jnp.float32)
    rad = env[:, None] * jnp.sin(n[None, :] * jnp.pi * ds[:, None])
    l = jnp.arange(cfg.n_spherical, dtype=jnp.float32)
    ang = jnp.cos(l[None, :] * angle[:, None])
    return (rad[:, None, :] * ang[:, :, None]).reshape(
        d.shape[0], cfg.n_spherical * cfg.n_radial)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _dense(key, din, dout, dtype):
    return {
        "w": jax.random.normal(key, (din, dout), dtype) * din ** -0.5,
        "b": jnp.zeros((dout,), dtype),
    }


def _apply(layer, x):
    return x @ layer["w"] + layer["b"]


def init_params(key: jax.Array, cfg: DimeNetConfig) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    d = cfg.d_hidden
    n_sbf = cfg.n_spherical * cfg.n_radial
    ks = iter(jax.random.split(key, 12 + 10 * cfg.n_blocks))

    params: Params = {
        "embed_nodes": (
            jax.random.normal(next(ks), (cfg.n_atom_types, d), dtype) * 0.1
            if cfg.d_feat == 0 else _dense(next(ks), cfg.d_feat, d, dtype)
        ),
        "embed_rbf": _dense(next(ks), cfg.n_radial, d, dtype),
        "embed_msg": _dense(next(ks), 3 * d, d, dtype),
        "blocks": [],
        "out_final": _dense(next(ks), d, cfg.n_targets, dtype),
    }
    for _ in range(cfg.n_blocks):
        blk = {
            "rbf_gate": _dense(next(ks), cfg.n_radial, d, dtype),
            "sbf_proj": _dense(next(ks), n_sbf, cfg.n_bilinear, dtype),
            "w_bilinear": jax.random.normal(
                next(ks), (cfg.n_bilinear, d, d), dtype) * d ** -0.5,
            "msg_in": _dense(next(ks), d, d, dtype),
            "msg_out": _dense(next(ks), 2 * d, d, dtype),
            "out_rbf": _dense(next(ks), cfg.n_radial, d, dtype),
            "out_node": _dense(next(ks), d, d, dtype),
        }
        params["blocks"].append(blk)
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _geometry(batch: Dict[str, Array]) -> Tuple[Array, Array, Array]:
    """Edge distances + triplet (in-edge distance, angle)."""
    pos = batch["positions"]
    src, dst = batch["edge_src"], batch["edge_dst"]
    vec = jnp.take(pos, src, axis=0) - jnp.take(pos, dst, axis=0)
    dist = jnp.sqrt(jnp.sum(vec * vec, axis=-1) + 1e-12)

    t_in, t_out = batch["t_in"], batch["t_out"]
    v_in = jnp.take(vec, t_in, axis=0)       # k - j (in-edge k->j)
    v_out = -jnp.take(vec, t_out, axis=0)    # i - j (out-edge j->i)
    d_in = jnp.take(dist, t_in)
    cosang = jnp.sum(v_in * v_out, axis=-1) / jnp.maximum(
        d_in * jnp.sqrt(jnp.sum(v_out * v_out, axis=-1) + 1e-12), 1e-9)
    angle = jnp.arccos(jnp.clip(cosang, -1.0 + 1e-7, 1.0 - 1e-7))
    return dist, d_in, angle


def forward_dense_triplets(
    params: Params, cfg: DimeNetConfig, batch: Dict[str, Array],
    shard_axes: Optional[Tuple[str, ...]] = None,
) -> Array:
    """Dense-(E, K) triplet layout + distributed gather/scatter —
    the §Perf-optimized path for capped-triplet graphs.

    With ``max_triplets_per_edge = K``, triplets are laid out as a
    dense ``t_in_dense (E, K)`` index matrix (mask for short rows).
    The per-triplet aggregation to edges becomes a LOCAL sum over K
    (no segment scatter), and all cross-shard row accesses (edge
    messages by ``t_in_dense``, node features by ``src``/``dst``,
    edge-to-node aggregation) go through the all_to_all-based
    ``repro.sparse.distributed`` ops instead of partitioner-inserted
    all-gathers. Measured on ogb_products: 439 GB -> see §Perf.
    """
    from repro.sparse.distributed import (distributed_segment_sum_local,
                                          distributed_take_local)

    src, dst = batch["edge_src"], batch["edge_dst"]
    e_mask = batch["edge_mask"].astype(jnp.float32)
    tk_mask = batch["t_mask_dense"].astype(jnp.float32)  # (E, K)
    t_in = batch["t_in_dense"]                           # (E, K)
    n_nodes = batch["node_mask"].shape[0]
    E, K = t_in.shape

    if shard_axes:
        from jax.sharding import PartitionSpec as P

        def row_sharded(x):
            return jax.lax.with_sharding_constraint(
                x, P(shard_axes, *([None] * (x.ndim - 1))))

        def take_rows(table, idx, wire_dtype=None):
            # wire_dtype=bf16 halves a2a wire+buffers on TPU, but the
            # CPU backend legalizes bf16 back to f32 (measured: no
            # delta, +converts) -> off by default in the dry-run
            flat = idx.reshape(-1)
            fn = jax.shard_map(
                lambda t, i: distributed_take_local(
                    t, i, axis_names=shard_axes)[0],
                mesh=None,
                in_specs=(P(shard_axes, None), P(shard_axes)),
                out_specs=P(shard_axes, None), check_vma=False)
            src = table if wire_dtype is None else \
                table.astype(wire_dtype)
            out = fn(src, flat).astype(table.dtype)
            return out.reshape(idx.shape + (table.shape[-1],))

        def scatter_rows(vals, idx, n_rows, wire_dtype=None):
            # rows per shard must divide; specs pad to 512
            fn = jax.shard_map(
                lambda v, i: distributed_segment_sum_local(
                    v, i, n_rows // _n_shards(shard_axes),
                    axis_names=shard_axes)[0],
                mesh=None,
                in_specs=(P(shard_axes, None), P(shard_axes)),
                out_specs=P(shard_axes, None), check_vma=False)
            v = vals if wire_dtype is None else vals.astype(wire_dtype)
            return fn(v, idx).astype(vals.dtype)
    else:
        def row_sharded(x):
            return x

        def take_rows(table, idx):
            return jnp.take(table, idx, axis=0)

        def scatter_rows(vals, idx, n_rows):
            return segment_sum(vals, idx, n_rows)

    # geometry: per-edge local; per-triplet via gather of edge rows
    pos_src = take_rows(batch["positions"], src)
    pos_dst = take_rows(batch["positions"], dst)
    vec = pos_src - pos_dst                                   # (E, 3)
    dist = jnp.sqrt(jnp.sum(vec * vec, axis=-1) + 1e-12)
    vec_in = take_rows(vec, t_in)                             # (E, K, 3)
    v_out = -vec[:, None, :]                                  # (E, 1, 3)
    d_in = jnp.sqrt(jnp.sum(vec_in * vec_in, axis=-1) + 1e-12)
    cosang = jnp.sum(vec_in * v_out, axis=-1) / jnp.maximum(
        d_in * dist[:, None], 1e-9)
    angle = jnp.arccos(jnp.clip(cosang, -1.0 + 1e-7, 1.0 - 1e-7))

    rbf = row_sharded(radial_basis(dist, cfg) * e_mask[:, None])
    sbf = spherical_basis(d_in.reshape(-1), angle.reshape(-1), cfg)
    sbf = row_sharded(
        sbf.reshape(E, K, -1) * tk_mask[..., None])           # (E,K,nsbf)

    if cfg.d_feat == 0:
        h = jnp.take(params["embed_nodes"], batch["node_feat"], axis=0)
    else:
        h = jax.nn.silu(_apply(params["embed_nodes"], batch["node_feat"]))
    h = row_sharded(h)

    rbf_e = jax.nn.silu(_apply(params["embed_rbf"], rbf))
    h_src = take_rows(h, src)
    h_dst = take_rows(h, dst)
    m = jax.nn.silu(_apply(params["embed_msg"], jnp.concatenate(
        [h_src, h_dst, rbf_e], axis=-1)))                     # (E, d)
    m = row_sharded(m)

    node_out = row_sharded(jnp.zeros((n_nodes, cfg.d_hidden), m.dtype))

    def block_fn(blk, m, node_out):
        x_kj = jax.nn.silu(_apply(blk["msg_in"], m))          # (E, d)
        x_t = take_rows(x_kj, t_in)                           # (E, K, d)
        s = _apply(blk["sbf_proj"], sbf)                      # (E, K, nb)
        # bilinear + K-sum in one local einsum — no triplet scatter
        xt2 = jnp.einsum("ekb,ekd,bdf->ef",
                         s * tk_mask[..., None], x_t, blk["w_bilinear"])
        agg = row_sharded(xt2)                                # (E, d)
        gate = jax.nn.silu(_apply(blk["rbf_gate"], rbf))
        upd = jax.nn.silu(_apply(
            blk["msg_out"], jnp.concatenate([m * gate, agg], axis=-1)))
        m = row_sharded(m + upd)
        contrib = m * jax.nn.silu(_apply(blk["out_rbf"], rbf))
        node_agg = scatter_rows(contrib * e_mask[:, None], dst, n_nodes)
        node_out = node_out + jax.nn.silu(_apply(blk["out_node"],
                                                 node_agg))
        return m, node_out

    # NOTE (§Perf, hypothesis refuted): jax.checkpoint per block made
    # the peak WORSE here (36.9 -> 47.2 GB on ogb_products): the block
    # closure (sbf, rbf, masks) is saved per block anyway and the
    # backward re-runs the distributed gathers, doubling the live
    # all_to_all buffers. Blocks therefore run un-remat'ed.
    for blk in params["blocks"]:
        m, node_out = block_fn(blk, m, node_out)

    return _apply(params["out_final"], node_out)


def _n_shards(axes: Tuple[str, ...]) -> int:
    mesh = jax.sharding.get_abstract_mesh()
    n = 1
    for ax in axes:
        n *= mesh.shape[ax]
    return n


def forward(
    params: Params, cfg: DimeNetConfig, batch: Dict[str, Array],
    shard_axes: Optional[Tuple[str, ...]] = None,
) -> Array:
    """Returns node-level outputs (N, n_targets).

    ``shard_axes``: when running under a mesh with edge/triplet/node
    counts divisible by the device count, per-edge and per-triplet
    intermediates (and the segment-sum outputs) are constrained to be
    row-sharded over these axes. Without the constraints the SPMD
    partitioner replicates every segment_sum output — at ogb-products
    scale that is a 31 GB/device tensor per block (measured ~430 GB
    peak on the baseline dry-run).

    Batches carrying the dense ``t_in_dense (E, K)`` triplet layout
    dispatch to ``forward_dense_triplets`` (the §Perf-optimized path).
    """
    if "t_in_dense" in batch:
        return forward_dense_triplets(params, cfg, batch,
                                      shard_axes=shard_axes)
    if shard_axes:
        from jax.sharding import PartitionSpec as P

        def row_sharded(x):
            spec = P(shard_axes, *([None] * (x.ndim - 1)))
            return jax.lax.with_sharding_constraint(x, spec)
    else:
        def row_sharded(x):
            return x

    src, dst = batch["edge_src"], batch["edge_dst"]
    e_mask = batch["edge_mask"].astype(jnp.float32)
    t_mask = batch["t_mask"].astype(jnp.float32)
    n_nodes = batch["node_mask"].shape[0]
    n_edges = src.shape[0]

    dist, d_in, angle = _geometry(batch)
    rbf = row_sharded(radial_basis(dist, cfg) * e_mask[:, None])
    sbf = row_sharded(spherical_basis(d_in, angle, cfg) * t_mask[:, None])

    if cfg.d_feat == 0:
        h = jnp.take(params["embed_nodes"], batch["node_feat"], axis=0)
    else:
        h = jax.nn.silu(_apply(params["embed_nodes"], batch["node_feat"]))
    h = row_sharded(h)

    rbf_e = jax.nn.silu(_apply(params["embed_rbf"], rbf))
    m = jax.nn.silu(_apply(params["embed_msg"], jnp.concatenate(
        [jnp.take(h, src, axis=0), jnp.take(h, dst, axis=0), rbf_e],
        axis=-1)))                                          # (E, d)
    m = row_sharded(m)

    node_out = jnp.zeros((n_nodes, cfg.d_hidden), m.dtype)
    t_in, t_out = batch["t_in"], batch["t_out"]
    for blk in params["blocks"]:
        # directional aggregation over triplets
        x_kj = jax.nn.silu(_apply(blk["msg_in"], m))        # (E, d)
        x_t = row_sharded(jnp.take(x_kj, t_in, axis=0))     # (T, d)
        s = _apply(blk["sbf_proj"], sbf)                    # (T, nb)
        # bilinear: (T, nb) x (T, d) x (nb, d, d) -> (T, d)
        xt2 = jnp.einsum("tb,td,bde->te", s, x_t, blk["w_bilinear"])
        agg = row_sharded(
            segment_sum(xt2 * t_mask[:, None], t_out, n_edges))
        gate = jax.nn.silu(_apply(blk["rbf_gate"], rbf))
        upd = jax.nn.silu(_apply(
            blk["msg_out"], jnp.concatenate([m * gate, agg], axis=-1)))
        m = row_sharded(m + upd)
        # per-block output: edges -> nodes
        contrib = m * jax.nn.silu(_apply(blk["out_rbf"], rbf))
        node_agg = row_sharded(
            segment_sum(contrib * e_mask[:, None], dst, n_nodes))
        node_out = node_out + jax.nn.silu(_apply(blk["out_node"], node_agg))

    return _apply(params["out_final"], node_out)            # (N, n_targets)


def forward_graph(
    params: Params, cfg: DimeNetConfig, batch: Dict[str, Array],
    n_graphs: int,
    shard_axes: Optional[Tuple[str, ...]] = None,
) -> Array:
    """Graph-level readout: sum node outputs per graph id."""
    node_out = forward(params, cfg, batch, shard_axes=shard_axes)
    node_out = node_out * batch["node_mask"].astype(node_out.dtype)[:, None]
    return segment_sum(node_out, batch["node_graph_id"], n_graphs)
