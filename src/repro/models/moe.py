"""Mixture-of-Experts FFN with sort-based capacity dispatch.

Token->expert dispatch on TPU cannot use the (T, E, C) one-hot tensor
of the original GShard formulation at 1M-token batches (it is
astronomically large). We use the sort-based dropping dispatch that
production JAX MoE stacks (MaxText, MegaBlocks-style) use:

1. route: top-k softmax gating over expert logits,
2. sort the T*k (token, expert) assignments by expert id,
3. compute each assignment's rank within its expert (cumulative
   position), drop ranks >= capacity C,
4. scatter surviving tokens into an (E, C, D) buffer,
5. batched expert FFN via one einsum over the stacked expert weights,
6. gather back and combine with gate weights.

All steps are dense, static-shape ops (argsort / cumsum / scatter),
which XLA SPMD can partition: the expert dimension shards over the
``model`` mesh axis (expert parallelism), tokens over ``data``.

Load-balancing auxiliary loss follows Switch Transformer (eq. 4-6).

``sigmoid_moe_ffn`` is DeepSeek-V3's layer (arXiv:2412.19437, section
2.1.2): sigmoid scores, top-k selection on score plus a per-expert bias
that steers selection only, gates normalised over the selected scores,
shared experts on every token, and a sequence-wise balance loss. Its
dispatch is dropless: every assignment to a held expert is computed,
by a grouped matmul (megablox) whose grid covers only the held
experts' rows.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


def moe_ffn(
    x: Array,                 # (T, D) flattened tokens
    router_w: Array,          # (D, E)
    w_gate: Array,            # (E, D, F)
    w_up: Array,              # (E, D, F)
    w_down: Array,            # (E, F, D)
    *,
    top_k: int,
    capacity_factor: float = 1.25,
) -> Tuple[Array, Array]:
    """Returns (output (T, D), aux_loss scalar)."""
    T, D = x.shape
    E = router_w.shape[1]
    C = max(1, int(capacity_factor * top_k * T / E))

    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                    # (T, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)        # (T, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # Switch-style load-balance loss: E * sum_e f_e * p_e.
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(expert_idx, E, dtype=jnp.float32), axis=1),
        axis=0,
    ) / top_k
    aux_loss = E * jnp.sum(me * ce)

    # --- sort-based dispatch -------------------------------------------
    flat_expert = expert_idx.reshape(-1)                       # (T*k,)
    flat_gate = gate_vals.reshape(-1)
    flat_token = jnp.repeat(jnp.arange(T, dtype=jnp.int32), top_k)

    order = jnp.argsort(flat_expert)                           # stable
    s_expert = flat_expert[order]
    s_token = flat_token[order]
    s_gate = flat_gate[order]

    # rank of each assignment within its expert
    counts = jnp.bincount(flat_expert, length=E)               # (E,)
    starts = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(T * top_k, dtype=jnp.int32) - starts[s_expert]
    keep = rank < C

    # scatter into the (E, C, D) expert buffer; dropped tokens go to a
    # sacrificial slot (row C) that is sliced off.
    slot = jnp.where(keep, rank, C)
    buf = jnp.zeros((E, C + 1, D), x.dtype)
    buf = buf.at[s_expert, slot].set(jnp.take(x, s_token, axis=0))
    buf = buf[:, :C]                                           # (E, C, D)

    # --- expert FFN (SwiGLU), one batched einsum over experts ----------
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(buf.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(buf.dtype))
    h = jax.nn.silu(g) * u
    y = jnp.einsum("ecf,efd->ecd", h, w_down.astype(buf.dtype))

    # --- gather back + combine -----------------------------------------
    y_pad = jnp.concatenate([y, jnp.zeros((E, 1, D), y.dtype)], axis=1)
    per_assign = y_pad[s_expert, slot]                         # (T*k, D)
    per_assign = per_assign * s_gate[:, None].astype(y.dtype)
    out = jax.ops.segment_sum(per_assign, s_token, num_segments=T)
    return out.astype(x.dtype), aux_loss


def moe_ffn_local_experts(
    x: Array,                 # (T_local, D) this token shard
    router_w: Array,          # (D, E_global) replicated
    w_gate: Array,            # (E_local, D, F) this expert shard
    w_up: Array,
    w_down: Array,
    *,
    top_k: int,
    capacity_factor: float,
    expert_axis: str,
    token_axes: Tuple[str, ...],
) -> Tuple[Array, Array]:
    """Expert-parallel MoE body (inside shard_map, DESIGN.md §5).

    Tokens are sharded over ``token_axes`` (data parallel), experts
    over ``expert_axis`` (the model axis). Routing is computed against
    the *global* expert set (router replicated); each device dispatches
    its local tokens to its local experts only (assignments to remote
    experts contribute zero locally) and the partial outputs are
    psum-combined over the expert axis — the EP collective. Capacity is
    per-(token-shard, expert), the standard per-device-capacity
    semantics of production MoE systems.
    """
    T, D = x.shape
    E_local = w_gate.shape[0]
    E = router_w.shape[1]
    shard = jax.lax.axis_index(expert_axis)
    lo = shard * E_local

    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)       # (T, k) global
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(expert_idx, E, dtype=jnp.float32), axis=1),
        axis=0) / top_k
    aux_loss = E * jnp.sum(me * ce)
    if token_axes:
        aux_loss = jax.lax.pmean(aux_loss, token_axes)
    aux_loss = aux_loss / jax.lax.psum(
        jnp.ones((), jnp.float32), expert_axis)  # replicated psum later

    C = max(1, int(capacity_factor * top_k * T / E))

    flat_expert = expert_idx.reshape(-1)
    flat_gate = gate_vals.reshape(-1)
    flat_token = jnp.repeat(jnp.arange(T, dtype=jnp.int32), top_k)

    local_e = flat_expert - lo
    is_local = (local_e >= 0) & (local_e < E_local)
    local_e = jnp.where(is_local, local_e, E_local)  # sink row

    order = jnp.argsort(jnp.where(is_local, flat_expert, E))  # locals first
    s_e = local_e[order]
    s_token = flat_token[order]
    s_gate = jnp.where(is_local, flat_gate, 0.0)[order]

    counts = jnp.bincount(s_e, length=E_local + 1)
    starts = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(T * top_k, dtype=jnp.int32) - starts[s_e]
    keep = (rank < C) & (s_e < E_local)
    slot = jnp.where(keep, rank, C)
    e_safe = jnp.minimum(s_e, E_local - 1)
    e_scatter = jnp.where(keep, e_safe, 0)
    slot = jnp.where(keep, slot, C)

    buf = jnp.zeros((E_local, C + 1, D), x.dtype)
    buf = buf.at[e_scatter, slot].set(jnp.take(x, s_token, axis=0))
    buf = buf[:, :C]

    g = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(buf.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(buf.dtype))
    h = jax.nn.silu(g) * u
    y = jnp.einsum("ecf,efd->ecd", h, w_down.astype(buf.dtype))

    y_pad = jnp.concatenate([y, jnp.zeros((E_local, 1, D), y.dtype)], axis=1)
    per_assign = y_pad[e_scatter, slot] * s_gate[:, None].astype(y.dtype)
    per_assign = jnp.where(keep[:, None], per_assign, 0.0)
    out = jax.ops.segment_sum(per_assign, s_token, num_segments=T)
    # combine partial expert outputs across the expert shards
    out = jax.lax.psum(out, expert_axis)
    aux_loss = jax.lax.psum(aux_loss, expert_axis)
    return out.astype(x.dtype), aux_loss


def init_moe_params(
    key: jax.Array, n_layers: int, d_model: int, d_ff: int, n_experts: int,
    dtype=jnp.float32,
) -> Dict[str, Array]:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    sc_in = d_model ** -0.5
    sc_ff = d_ff ** -0.5
    return {
        "router": (jax.random.normal(k1, (n_layers, d_model, n_experts),
                                     dtype) * sc_in),
        "w_gate": (jax.random.normal(
            k2, (n_layers, n_experts, d_model, d_ff), dtype) * sc_in),
        "w_up": (jax.random.normal(
            k3, (n_layers, n_experts, d_model, d_ff), dtype) * sc_in),
        "w_down": (jax.random.normal(
            k4, (n_layers, n_experts, d_ff, d_model), dtype) * sc_ff),
    }


# ---------------------------------------------------------------------------
# sigmoid router, dropless held-expert dispatch (DeepSeek-V3)
# ---------------------------------------------------------------------------

def sigmoid_route(x: Array, router_w: Array, bias: Array, *, top_k: int,
                  routed_scale: float) -> Tuple[Array, Array, Array]:
    """(scores (T, E), expert ids (T, k), gates (T, k)) in float32.

    The bias enters the selection only: a gate is the selected expert's
    sigmoid score over the sum of the k selected scores, times
    ``routed_scale``."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    picked = jnp.take_along_axis(scores, idx, axis=1)
    gates = picked / jnp.sum(picked, axis=1, keepdims=True) * routed_scale
    return scores, idx, gates


def sequence_balance_loss(scores: Array, idx: Array, mask: Array) -> Array:
    """DeepSeek-V3's sequence-wise balance loss (eq. 17-20) without its
    weight: per sequence, sum_i f_i P_i over the real tokens, with f_i
    = E / (k n) times the tokens that selected expert i and P_i the mean
    of expert i's score normalised over all experts; the mean over
    sequences. ``scores`` (B, S, E), ``idx`` (B, S, k), ``mask`` (B, S)."""
    E, k = scores.shape[-1], idx.shape[-1]
    real = (mask > 0).astype(jnp.float32)
    n = jnp.maximum(jnp.sum(real, axis=1), 1.0)                  # (B,)
    picks = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=2)
    f = jnp.einsum("bse,bs->be", picks, real) * (E / k) / n[:, None]
    norm = scores / jnp.sum(scores, axis=-1, keepdims=True)
    P = jnp.einsum("bse,bs->be", norm, real) / n[:, None]
    return jnp.mean(jnp.sum(jax.lax.stop_gradient(f) * P, axis=1))


@jax.custom_vjp
def _permute_rows(x: Array, order: Array, inverse: Array) -> Array:
    """``x[order]`` for a permutation, whose gradient gathers by the
    inverse permutation (no scatter)."""
    return jnp.take(x, order, axis=0)


def _permute_fwd(x, order, inverse):
    return jnp.take(x, order, axis=0), (order, inverse)


def _permute_bwd(res, g):
    order, inverse = res
    return jnp.take(g, inverse, axis=0), None, None


_permute_rows.defvjp(_permute_fwd, _permute_bwd)

# rows of the grouped matmul's tiles; a group's rows round up to whole
# tiles, so a smaller tile wastes less on the held experts' boundaries
ROW_TILE = 256


def _gmm_tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """Megablox tiles: ``ROW_TILE`` rows; the contraction and output
    widths in 512s where they divide, else whole (an expert width of
    1408 = 11 x 128). About 6-10 MiB of VMEM at d_model 2048."""
    return (ROW_TILE, 512 if k % 512 == 0 else k, 512 if n % 512 == 0 else n)


def _grouped(x: Array, w: Array, sizes: Array, offset: Array,
             interpret: bool) -> Array:
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    return gmm(x, w.astype(x.dtype), sizes, x.dtype, _gmm_tiling, offset,
               None, False, interpret)


def held_experts(x: Array, idx: Array, gates: Array, real: Array,
                 w_gate: Array, w_up: Array, w_down: Array, *,
                 n_experts: int, offset, interpret: bool) -> Array:
    """The held experts' part of the routed output, dropless.

    ``x`` (T, D); ``idx``/``gates`` (T, k) over all ``n_experts``;
    ``real`` (T,) bool: pad tokens are not routed. ``w_*`` hold the
    experts ``[offset, offset + w_gate.shape[0])``. Every (token, slot)
    assignment is sorted by expert (pads after every expert), and the
    grouped matmul computes only the held experts' rows and zeroes the
    rest, so no assignment is dropped whatever the imbalance."""
    T, D = x.shape
    k = idx.shape[1]
    key = jnp.where(real[:, None], idx, n_experts).reshape(-1)    # (T*k,)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(T * k, dtype=jnp.int32))
    sizes = jnp.bincount(key, length=n_experts + 1).astype(jnp.int32)
    M = T * k + (-T * k) % ROW_TILE
    rows = _permute_rows(jnp.repeat(x, k, axis=0), order, inverse)
    rows = jnp.pad(rows, ((0, M - T * k), (0, 0)))
    sizes = sizes.at[n_experts].add(M - T * k)
    offset = jnp.asarray(offset, jnp.int32)
    g = _grouped(rows, w_gate, sizes, offset, interpret)
    u = _grouped(rows, w_up, sizes, offset, interpret)
    y = _grouped(jax.nn.silu(g) * u, w_down, sizes, offset, interpret)
    y = _permute_rows(y[:T * k], inverse, order).reshape(T, k, D)
    w = jnp.where(real[:, None], gates, 0.0).astype(y.dtype)
    return jnp.einsum("tkd,tk->td", y, w)


def sigmoid_moe_ffn(x: Array, mask: Array, p: Dict[str, Array], *,
                    top_k: int, routed_scale: float, n_experts: int,
                    offset, interpret: bool) -> Tuple[Array, Array]:
    """DeepSeek-V3's routed experts on ``x`` (B, S, D) with ``mask``
    (B, S): routed over all ``n_experts``, the held experts' part of
    their output. Returns (out (B, S, D), the sequence-wise balance
    loss). The shared experts are ``shared_experts``."""
    B, S, D = x.shape
    flat = x.reshape(B * S, D)
    scores, idx, gates = sigmoid_route(flat, p["router"], p["router_bias"],
                                       top_k=top_k,
                                       routed_scale=routed_scale)
    out = held_experts(flat, idx, gates, mask.reshape(-1) > 0,
                       p["w_gate"], p["w_up"], p["w_down"],
                       n_experts=n_experts, offset=offset,
                       interpret=interpret)
    aux = sequence_balance_loss(scores.reshape(B, S, -1),
                                idx.reshape(B, S, -1), mask)
    return out.reshape(B, S, D), aux


def shared_experts(x: Array, p: Dict[str, Array]) -> Array:
    """The shared experts, one SwiGLU of their summed width, on every
    token."""
    c = x.dtype
    return (jax.nn.silu(x @ p["w_gate"].astype(c))
            * (x @ p["w_up"].astype(c))) @ p["w_down"].astype(c)


def init_sigmoid_moe_params(key: jax.Array, n_layers: int, d_model: int,
                            d_expert: int, n_experts: int, n_held: int,
                            n_shared: int, dtype=jnp.float32
                            ) -> Dict[str, Array]:
    """Router over all experts, a selection bias drawn from the key
    (held fixed: no gradient reaches it, and the train step leaves it
    out of AdamW), the held experts and the shared expert."""
    ks = jax.random.split(key, 8)
    L, D, F, Fs = n_layers, d_model, d_expert, n_shared * d_expert
    n = lambda k, shape, fan_in: (jax.random.normal(k, shape, dtype)
                                  * fan_in ** -0.5)
    return {
        "router": n(ks[0], (L, D, n_experts), D),
        "router_bias": jax.random.normal(ks[1], (L, n_experts), dtype)
        * 1e-2,
        "w_gate": n(ks[2], (L, n_held, D, F), D),
        "w_up": n(ks[3], (L, n_held, D, F), D),
        "w_down": n(ks[4], (L, n_held, F, D), F),
        "shared": {"w_gate": n(ks[5], (L, D, Fs), D),
                   "w_up": n(ks[6], (L, D, Fs), D),
                   "w_down": n(ks[7], (L, Fs, D), Fs)},
    }
