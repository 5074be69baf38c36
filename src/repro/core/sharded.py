"""Vocabulary-sharded Sparton head — the technique at pod scale.

The paper is single-GPU. At |V| = 256k (gemma-2) even the *reduced*
``(B, V)`` output is large, and the head weights ``E (V, D)`` dominate
HBM on one chip. We shard the vocabulary dimension over the ``model``
mesh axis with ``shard_map`` (DESIGN.md §3):

* ``E``, ``b`` row-sharded on ``model`` — each device holds V/n rows.
* ``H`` replicated over ``model`` (it is batch-sharded over ``data``).
* Each device runs the *local* Sparton head over its vocab shard —
  the streaming max is per-vocab-column independent, so the forward
  needs **zero collectives**, and ``∇E`` is computed shard-locally.
* ``∇H = Σ_v g·E[v]`` sums over the vocab => one ``psum`` over
  ``model`` in the backward. That is the entire communication cost.

The InfoNCE similarity ``q · dᵀ = Σ_v q_v d_v`` is likewise a
vocab-sum: computed shard-locally and ``psum``-reduced, so the full
``(B, V)`` sparse vectors are never gathered on any device
(``sharded_similarity``). Sparsity regularizers (FLOPS, L1) are also
vocab-sums and follow the same pattern.

All functions here are *shard_map bodies* plus factory wrappers binding
a mesh. The train step in ``launch/train.py`` composes them under
``jax.jit`` with explicit in/out shardings.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array


def sharded_sparton_head(
    mesh: Mesh,
    *,
    axis_name: str = "model",
    batch_axes: Tuple[str, ...] = ("pod", "data"),
    vocab_tile: int = 4096,
    logit_softcap: Optional[float] = None,
    unroll: int = 1,
    bwd_batch_chunk: int = 8,
):
    """Returns head(H, E, b, mask) -> Y with E/b/Y vocab-sharded.

    Shardings (global view):
      H    (B, S, D)  — batch over ``batch_axes``, replicated over model
      E    (V, D)     — rows over ``axis_name``
      b    (V,)       — over ``axis_name``
      Y    (B, V)     — batch over ``batch_axes``, vocab over ``axis_name``

    Thin wrapper over the unified factory: equivalent to
    ``make_head(HeadSpec(impl="sparton", ...), mesh=mesh, ...)``. The
    shard_map body construction (and the kernel-capable variant) lives
    in ``core.head_api``; each device differentiates its local head,
    ∇E stays shard-local, and shard_map's transpose inserts the single
    ∇H psum over ``axis_name``.
    """
    from repro.core.head_api import HeadSpec, make_head

    spec = HeadSpec(impl="sparton", vocab_tile=vocab_tile,
                    logit_softcap=logit_softcap, unroll=unroll,
                    bwd_batch_chunk=bwd_batch_chunk)
    return make_head(spec, mesh=mesh, axis_name=axis_name,
                     batch_axes=batch_axes)


def sharded_similarity(
    mesh: Mesh,
    *,
    axis_name: str = "model",
    batch_axes: Tuple[str, ...] = ("pod", "data"),
):
    """(Bq, V)·(Bd, V)ᵀ with V sharded: local matmul + psum over model.

    Queries/documents stay batch-sharded; the (Bq, Bd) score matrix is
    small (batch²) and comes out replicated over ``model``. The in-batch
    InfoNCE denominator needs *global* batch scores, so the batch axes
    are all-gathered for the document side only (Bd × V_local slab per
    device — still 1/n of the full sparse matrix).
    """

    def body(q, d):
        # q: (Bq_local, V_local); d: (Bd_local, V_local)
        d_full = d
        if batch_axes:
            d_full = jax.lax.all_gather(d_full, batch_axes, axis=0,
                                        tiled=True)
        scores = jnp.einsum("qv,dv->qd", q, d_full,
                            preferred_element_type=jnp.float32)
        return jax.lax.psum(scores, axis_name)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(batch_axes, axis_name), P(batch_axes, axis_name)),
        out_specs=P(batch_axes, None),
    )


def sharded_infonce(
    mesh: Mesh,
    *,
    axis_name: str = "model",
    batch_axes: Tuple[str, ...] = ("pod", "data"),
    temperature: float = 1.0,
):
    """In-batch InfoNCE over vocab-sharded sparse reps, fully fused.

    Each device scores its local query rows against the *globally
    gathered* documents on its vocab shard, psums the partial scores
    over ``model``, and picks the diagonal label at the query's global
    row offset. Only the (Bd_global, V_local) doc slab and the
    (Bq_local, Bd_global) score block ever exist per device.
    """

    def body(q, d):
        bq_local = q.shape[0]
        d_full = d
        if batch_axes:
            d_full = jax.lax.all_gather(d_full, batch_axes, axis=0,
                                        tiled=True)
        scores = jnp.einsum("qv,dv->qd", q, d_full,
                            preferred_element_type=jnp.float32)
        scores = jax.lax.psum(scores, axis_name) / temperature

        # global row offset of this shard's queries
        offset = jnp.zeros((), jnp.int32)
        for ax in batch_axes:  # row-major over batch_axes (gather order)
            offset = offset * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
        labels = offset * bq_local + jnp.arange(bq_local)

        logp = jax.nn.log_softmax(scores, axis=-1)
        local = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
        if batch_axes:
            local = jax.lax.pmean(local, batch_axes)
        return local

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(batch_axes, axis_name), P(batch_axes, axis_name)),
        out_specs=P(),
    )


def sharded_flops_reg(
    mesh: Mesh,
    *,
    axis_name: str = "model",
    batch_axes: Tuple[str, ...] = ("pod", "data"),
):
    """SPLADE FLOPS regularizer sum_v (mean_b Y[b,v])² over sharded V."""

    def body(y):
        mean_b = jnp.mean(jnp.abs(y), axis=0)     # local batch mean
        if batch_axes:
            mean_b = jax.lax.pmean(mean_b, batch_axes)
        local = jnp.sum(mean_b * mean_b)
        total = jax.lax.psum(local, axis_name)
        return total

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(batch_axes, axis_name),),
        out_specs=P(),
    )


def sharded_l1_reg(
    mesh: Mesh,
    *,
    axis_name: str = "model",
    batch_axes: Tuple[str, ...] = ("pod", "data"),
):
    """L1 regularizer mean_b sum_v |Y[b,v]| over sharded V — the row
    sum psums over ``model``, the batch mean pmeans over the batch
    axes, matching ``losses.l1_regularizer`` on the gathered array."""

    def body(y):
        local = jnp.mean(jnp.sum(jnp.abs(y.astype(jnp.float32)), axis=-1))
        total = jax.lax.psum(local, axis_name)
        if batch_axes:
            total = jax.lax.pmean(total, batch_axes)
        return total

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(batch_axes, axis_name),),
        out_specs=P(),
    )


def sharded_row_dots(
    mesh: Mesh,
    *,
    axis_name: str = "model",
    batch_axes: Tuple[str, ...] = ("pod", "data"),
):
    """Per-row dots ``s[b] = sum_v a[b,v]·c[b,v]`` over sharded V —
    the score primitive MarginMSE distillation needs (aligned q/doc
    pairs, no cross-batch matrix): shard-local einsum + one psum, the
    ``(B, V)`` reps never gather anywhere."""

    def body(a, c):
        local = jnp.einsum("bv,bv->b", a, c,
                           preferred_element_type=jnp.float32)
        return jax.lax.psum(local, axis_name)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(batch_axes, axis_name), P(batch_axes, axis_name)),
        out_specs=P(batch_axes),
    )


def head_shardings(mesh: Mesh, *, axis_name: str = "model",
                   batch_axes: Tuple[str, ...] = ("pod", "data")):
    """NamedShardings for (H, E, b, mask, Y) used by jit'd callers."""
    return {
        "H": NamedSharding(mesh, P(batch_axes, None, None)),
        "E": NamedSharding(mesh, P(axis_name, None)),
        "b": NamedSharding(mesh, P(axis_name)),
        "mask": NamedSharding(mesh, P(batch_axes, None)),
        "Y": NamedSharding(mesh, P(batch_axes, axis_name)),
    }
