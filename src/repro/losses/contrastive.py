"""LSR training objectives — InfoNCE + SPLADE sparsity regularizers.

The paper trains SPLADE with the InfoNCE loss [19] over in-batch
negatives on Mistral-Splade data; SPLADE sparsity is induced by the
FLOPS regularizer (Paria et al. / Formal et al.) and optionally L1.
MarginMSE distillation is included because SPLADE-v3's recipe uses it
(the paper's Table 3 compares against it).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


def infonce_loss(
    q_reps: Array,     # (B, V) query sparse vectors
    d_reps: Array,     # (B*(1+n_neg), V) docs; first B are positives
    *,
    temperature: float = 1.0,
) -> Array:
    """In-batch-negatives InfoNCE: positive of query i is document i."""
    scores = jnp.einsum("qv,dv->qd", q_reps, d_reps,
                        preferred_element_type=jnp.float32) / temperature
    labels = jnp.arange(q_reps.shape[0])
    logp = jax.nn.log_softmax(scores, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def gathered_infonce(
    q_reps: Array,     # (B_local, V) this shard's query rows
    d_reps: Array,     # (B_local, V) this shard's doc rows
    *,
    axis_names: Tuple[str, ...] = (),
    temperature: float = 1.0,
) -> Array:
    """Mesh-aware in-batch InfoNCE: negatives gathered across the data
    axes.

    Inside ``shard_map``/``pmap`` over ``axis_names``, each device
    holds a ``B_local`` slice of the global batch; in-batch negatives
    must still span the *global* batch or the effective negative pool
    shrinks by the data-parallel degree. Documents are all_gather'd
    over ``axis_names`` (row-major gather order), the diagonal label
    is offset by this shard's global row position, and the per-shard
    mean is pmean'd so the result equals single-device
    :func:`infonce_loss` on the concatenated batch. With no axes it
    *is* ``infonce_loss``. (The vocab-sharded head path instead uses
    ``core.sharded.sharded_infonce``, which fuses the same gather with
    the partial-score psum.)
    """
    if not axis_names:
        return infonce_loss(q_reps, d_reps, temperature=temperature)

    d_full = jax.lax.all_gather(d_reps, axis_names, axis=0, tiled=True)
    scores = jnp.einsum("qv,dv->qd", q_reps, d_full,
                        preferred_element_type=jnp.float32) / temperature
    offset = jnp.zeros((), jnp.int32)
    for ax in axis_names:
        offset = offset * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    labels = offset * q_reps.shape[0] + jnp.arange(q_reps.shape[0])
    logp = jax.nn.log_softmax(scores, axis=-1)
    local = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
    return jax.lax.pmean(local, axis_names)


def infonce_from_scores(scores: Array, *, temperature: float = 1.0) -> Array:
    """InfoNCE when the (Bq, Bd) score matrix is precomputed (the
    vocab-sharded path computes scores without gathering reps)."""
    labels = jnp.arange(scores.shape[0])
    logp = jax.nn.log_softmax(scores / temperature, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def flops_regularizer(reps: Array) -> Array:
    """SPLADE FLOPS: sum_v (mean_b |Y[b, v]|)^2 — pushes mean activation
    per vocab dim to zero => sparsity aligned with inverted-index cost."""
    mean_act = jnp.mean(jnp.abs(reps.astype(jnp.float32)), axis=0)
    return jnp.sum(mean_act * mean_act)


def l1_regularizer(reps: Array) -> Array:
    return jnp.mean(jnp.sum(jnp.abs(reps.astype(jnp.float32)), axis=-1))


def margin_mse_loss(
    q_reps: Array, d_pos: Array, d_neg: Array, teacher_margin: Array,
) -> Array:
    """MarginMSE distillation: match teacher score margins."""
    s_pos = jnp.einsum("bv,bv->b", q_reps, d_pos)
    s_neg = jnp.einsum("bv,bv->b", q_reps, d_neg)
    return jnp.mean((s_pos - s_neg - teacher_margin) ** 2)


def splade_loss(
    q_reps: Array,
    d_reps: Array,
    *,
    temperature: float = 1.0,
    lambda_q: float = 5e-4,
    lambda_d: float = 3e-4,
    l1_weight: float = 0.0,
    aux_loss: Optional[Array] = None,
    aux_weight: float = 1e-2,
) -> Array:
    """Full SPLADE objective = InfoNCE + FLOPS(q) + FLOPS(d) (+ MoE aux)."""
    loss = infonce_loss(q_reps, d_reps, temperature=temperature)
    loss = loss + lambda_q * flops_regularizer(q_reps)
    loss = loss + lambda_d * flops_regularizer(d_reps)
    if l1_weight:
        loss = loss + l1_weight * (
            l1_regularizer(q_reps) + l1_regularizer(d_reps))
    if aux_loss is not None:
        loss = loss + aux_weight * aux_loss
    return loss
