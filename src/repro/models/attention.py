"""Attention primitives: RoPE, chunked (online-softmax) attention, GQA,
multi-head latent attention (MLA).

``attention`` is what the layers call. On a TPU, for attention with
neither a sliding window nor a logit softcap and outside the cost
probes, it runs the fused Pallas kernel (``kernels/attention.py``),
which keeps the scores on chip and visits only the blocks inside each
row's extent. Everywhere else (the CPU backend, where the tests and the
multi-pod dry-run compile and Pallas TPU kernels cannot lower; softcaps;
sliding windows; the cost probes) it runs ``chunked_attention``: a
lax.scan over KV chunks with a running max/sum, the flash-attention
recurrence expressed in XLA, which holds one ``(B, Sq, H, chunk)`` f32
score block at a time.

Masks are never materialized as ``(S, S)`` tensors: causal and
sliding-window constraints are evaluated per KV chunk from iota
comparisons, which XLA fuses into the score computation.

MLA (DeepSeek-V2/V3, arXiv:2412.19437 section 2.1.1) projects each
position to a small latent and one RoPE key that every head shares, and
up-projects the latent to the heads' keys and values; queries and keys
are ``qk_nope + qk_rope`` wide, values ``v_head_dim`` wide.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels._common import interpret_mode
from repro.kernels.attention import fused_attention

Array = jax.Array
NEG_INF = -1e30


def rms_norm(x: Array, scale: Array, eps: float) -> Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(d_head: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32)
                            / d_head))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (B, S, N, d_head); positions: (B, S) int32."""
    d_head = x.shape[-1]
    freqs = rope_frequencies(d_head, theta)                 # (d/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, d/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Chunked multi-head attention with GQA
# ---------------------------------------------------------------------------

def _chunk_mask(
    q_pos: Array,       # (Sq,) absolute query positions
    k_pos: Array,       # (Ck,) absolute key positions of this chunk
    kv_valid: Array,    # (B, Ck) bool — padding mask of this chunk
    causal: bool,
    window: Optional[int],
) -> Array:
    """(B, Sq, Ck) bool keep-mask, built from iota comparisons."""
    m = kv_valid[:, None, :]
    rel = q_pos[None, :, None] - k_pos[None, None, :]  # (1, Sq, Ck)
    if causal:
        m = m & (rel >= 0)
    if window is not None:
        m = m & (rel < window)
    return m


def chunked_attention(
    q: Array,            # (B, Sq, H, dh)
    k: Array,            # (B, Sk, KV, dh)
    v: Array,            # (B, Sk, KV, dv)
    *,
    q_positions: Array,  # (Sq,)
    k_positions: Array,  # (Sk,)
    kv_mask: Array,      # (B, Sk) 1 = valid
    causal: bool,
    window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    chunk_size: int = 512,
    unroll: int = 1,
) -> Array:
    """Online-softmax attention over KV chunks; returns (B, Sq, H, dv).

    ``unroll`` is for cost-probe lowering only (roofline.py): the KV
    chunk scan body must be replicated so cost_analysis counts it."""
    B, Sq, H, dh = q.shape
    Sk, KV, dv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // KV  # query groups per kv head
    scale = dh ** -0.5

    chunk_size = min(chunk_size, max(Sk, 1))  # no padding blow-up at small S
    pad = (-Sk) % chunk_size
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_mask = jnp.pad(kv_mask, ((0, 0), (0, pad)))
        k_positions = jnp.pad(k_positions, (0, pad), constant_values=-1)
    n_chunks = k.shape[1] // chunk_size

    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, KV, G, dh)
    k_c = k.reshape(B, n_chunks, chunk_size, KV, dh)
    v_c = v.reshape(B, n_chunks, chunk_size, KV, dv)
    m_c = kv_mask.reshape(B, n_chunks, chunk_size)
    p_c = k_positions.reshape(n_chunks, chunk_size)

    def body(carry, xs):
        acc, row_max, row_sum = carry
        kc, vc, mc, pc = xs  # (B,C,KV,dh), (B,C,KV,dh), (B,C), (C,)
        s = jnp.einsum("bqkgd,bckd->bqkgc", qf.astype(kc.dtype), kc,
                       preferred_element_type=jnp.float32)
        if logit_softcap is not None:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        keep = _chunk_mask(q_positions, pc, mc > 0, causal, window)
        s = jnp.where(keep[:, :, None, None, :], s, NEG_INF)
        new_max = jnp.maximum(row_max, jnp.max(s, axis=-1))
        alpha = jnp.exp(row_max - new_max)
        p = jnp.exp(s - new_max[..., None])
        acc = acc * alpha[..., None] + jnp.einsum(
            "bqkgc,bckd->bqkgd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        row_sum = row_sum * alpha + jnp.sum(p, axis=-1)
        return (acc, new_max, row_sum), None

    init = (
        jnp.zeros((B, Sq, KV, G, dv), jnp.float32),
        jnp.full((B, Sq, KV, G), NEG_INF, jnp.float32),
        jnp.zeros((B, Sq, KV, G), jnp.float32),
    )
    (acc, _, row_sum), _ = jax.lax.scan(
        body, init,
        (jnp.moveaxis(k_c, 1, 0), jnp.moveaxis(v_c, 1, 0),
         jnp.moveaxis(m_c, 1, 0), p_c),
        unroll=unroll,
    )
    out = acc / jnp.maximum(row_sum[..., None], 1e-30)
    return out.reshape(B, Sq, H, dv).astype(q.dtype)


def _fused_applies(window: Optional[int], logit_softcap: Optional[float],
                   unroll: int) -> bool:
    """Whether ``attention`` runs the fused kernel: on a TPU, with no
    sliding window, no softcap and no cost-probe unroll."""
    return (window is None and logit_softcap is None and unroll == 1
            and not interpret_mode())


def attention(
    q: Array,            # (B, S, H, dqk)
    k: Array,            # (B, S, KV, dqk)
    v: Array,            # (B, S, KV, dv)
    *,
    positions: Array,    # (S,) 0 .. S-1
    kv_mask: Array,      # (B, S) 1 = valid
    causal: bool,
    window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    chunk_size: int = 512,
    unroll: int = 1,
    mesh: Optional[Mesh] = None,
) -> Array:
    """Self-attention over one sequence of positions; returns
    (B, S, H, dv), under the named scope ``attention``. The fused kernel
    leaves q blocks past a row's extent at 0; every real position reads
    the same in both paths. ``mesh``: the mesh of a data-parallel step,
    whose batch axes split the rows."""
    with jax.named_scope("attention"):
        if _fused_applies(window, logit_softcap, unroll):
            return _fused(q, k, v, kv_mask, causal, mesh)
        return chunked_attention(
            q, k, v, q_positions=positions, k_positions=positions,
            kv_mask=kv_mask, causal=causal, window=window,
            logit_softcap=logit_softcap, chunk_size=chunk_size,
            unroll=unroll)


def _fused(q, k, v, kv_mask, causal, mesh):
    """The kernel; on a mesh, inside a shard_map over the batch axes that
    split the rows, since the partitioner cannot split a Mosaic kernel."""
    def run(q, k, v, kv_mask):
        return fused_attention(q, k, v, kv_mask, causal, interpret_mode())

    if mesh is None:
        return run(q, k, v, kv_mask)
    from repro.launch.sharding import batch_axes_for

    rows = P(batch_axes_for(mesh, q.shape[0]) or None)
    # the kernel's call declares no varying axes
    return jax.shard_map(run, mesh=mesh, in_specs=(rows,) * 4,
                         out_specs=rows, check_vma=False)(q, k, v, kv_mask)


def mla_attention(
    h: Array,            # (B, S, D) normed layer input, compute dtype
    p,                   # wq, wkv_a, kv_norm, wkv_b, wo (one layer)
    *,
    n_heads: int,
    kv_lora_rank: int,
    qk_nope_head_dim: int,
    qk_rope_head_dim: int,
    v_head_dim: int,
    positions: Array,    # (S,)
    mask: Array,         # (B, S)
    causal: bool,
    rope_theta: float,
    norm_eps: float,
    chunk_size: int = 512,
    unroll: int = 1,
    mesh: Optional[Mesh] = None,
) -> Array:
    """MLA with a full query projection (``q_lora_rank`` null); returns
    the output projection (B, S, D). Each head's score is over
    ``qk_nope + qk_rope`` dims, scaled by their count ** -0.5; RoPE
    rotates the rope part of the queries and the shared rope key."""
    B, S, _ = h.shape
    H, r = n_heads, kv_lora_rank
    dn, dr, dv = qk_nope_head_dim, qk_rope_head_dim, v_head_dim
    c = h.dtype
    pos2d = jnp.broadcast_to(positions[None], (B, S))
    q = (h @ p["wq"].astype(c)).reshape(B, S, H, dn + dr)
    q = jnp.concatenate(
        [q[..., :dn], apply_rope(q[..., dn:], pos2d, rope_theta)], -1)
    kv_a = h @ p["wkv_a"].astype(c)                          # (B, S, r+dr)
    latent = rms_norm(kv_a[..., :r], p["kv_norm"], norm_eps)
    k_rope = apply_rope(kv_a[..., None, r:], pos2d, rope_theta)
    kv = (latent @ p["wkv_b"].astype(c)).reshape(B, S, H, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (B, S, H, dr))], -1)
    out = attention(q, k, kv[..., dn:], positions=positions, kv_mask=mask,
                    causal=causal, chunk_size=chunk_size, unroll=unroll,
                    mesh=mesh)
    return out.reshape(B, S, H * dv) @ p["wo"].astype(c)


def decode_attention(
    q: Array,            # (B, 1, H, dh)
    k_cache: Array,      # (B, S_max, KV, dh)
    v_cache: Array,      # (B, S_max, KV, dh)
    *,
    positions: Array,    # (B,) current write position (# valid tokens - 1)
    window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
) -> Array:
    """Single-token decode attention against a (possibly huge) cache.

    Scores are (B, H, S_max) — linear in cache length, never quadratic.
    """
    B, _, H, dh = q.shape
    S_max, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = dh ** -0.5

    # keep the (huge) cache in its storage dtype: upcasting would
    # materialize an fp32 copy of the full cache (2x HBM). The einsum
    # accumulates in fp32 via preferred_element_type (MXU-native).
    qf = (q.astype(jnp.float32) * scale).astype(k_cache.dtype)
    qf = qf.reshape(B, KV, G, dh)
    s = jnp.einsum("bkgd,bskd->bkgs", qf, k_cache,
                   preferred_element_type=jnp.float32)
    if logit_softcap is not None:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    k_pos = jnp.arange(S_max, dtype=jnp.int32)
    keep = k_pos[None, :] <= positions[:, None]           # causal / validity
    if window is not None:
        keep = keep & (positions[:, None] - k_pos[None, :] < window)
    s = jnp.where(keep[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, dh).astype(q.dtype)
