"""Int8 gradient compression with error feedback for DP all-reduce.

At 1000+ nodes the DP gradient all-reduce is the dominant collective;
int8 quantization cuts its payload 4× vs fp32 (2× vs bf16). Per-leaf
symmetric scaling (max-abs / 127) keeps the quantizer cheap; the
*error-feedback residual* (Seide et al. / EF-SGD) accumulates the
quantization error into the next step's gradient so convergence is
provably unaffected for smooth objectives.

``compressed_allreduce`` is written as a shard_map-compatible function:
quantize -> psum the int8 payload widened to int32 (exact — sums of
≤2^15 int8 values fit int32) -> dequantize with psum'd scales.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

PyTree = Any


def compress_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x (f32/bf16) -> (int8 payload, f32 scale)."""
    x32 = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x32)) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def decompress_int8(q: jax.Array, scale: jax.Array,
                    dtype=jnp.float32) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def compress_tree(grads: PyTree, residual: Optional[PyTree]
                  ) -> Tuple[PyTree, PyTree, PyTree]:
    """Error-feedback compression of a gradient pytree.

    Returns (quantized payloads, scales, new residuals).
    """
    if residual is None:
        residual = jax.tree.map(
            lambda g: jnp.zeros(g.shape, jnp.float32), grads)

    def comp(g, r):
        corrected = g.astype(jnp.float32) + r
        q, s = compress_int8(corrected)
        new_r = corrected - decompress_int8(q, s)
        return q, s, new_r

    out = jax.tree.map(comp, grads, residual)
    is_triple = lambda x: isinstance(x, tuple) and len(x) == 3
    qs = jax.tree.map(lambda o: o[0], out, is_leaf=is_triple)
    ss = jax.tree.map(lambda o: o[1], out, is_leaf=is_triple)
    rs = jax.tree.map(lambda o: o[2], out, is_leaf=is_triple)
    return qs, ss, rs


def _flatten(grads: PyTree) -> Tuple[jax.Array, Any]:
    leaves, treedef = jax.tree.flatten(grads)
    shapes = [l.shape for l in leaves]
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32)
                            for l in leaves])
    return flat, (treedef, shapes)


def _unflatten(flat: jax.Array, spec) -> PyTree:
    treedef, shapes = spec
    leaves, off = [], 0
    for shp in shapes:
        n = 1
        for s in shp:
            n *= s
        leaves.append(flat[off:off + n].reshape(shp))
        off += n
    return jax.tree.unflatten(treedef, leaves)


def compressed_allreduce(
    grads: PyTree,
    residual: Optional[jax.Array],
    axis_name: str,
) -> Tuple[PyTree, jax.Array]:
    """Inside-shard_map DP all-reduce with a true int8 wire format.

    Ring-psum of fp32 moves ~2x payload_fp32 bytes per device; this
    scheme moves ~2x payload_int8 — a 4x wire saving:

      1. error-feedback int8-quantize the flattened gradient,
      2. reduce-scatter: ``all_to_all`` the int8 payload (each device
         receives shard i of every peer), sum dequantized shards,
      3. requantize the reduced shard to int8,
      4. ``all_gather`` the int8 result + fp32 scales; dequantize.

    ``residual`` is the flat fp32 error-feedback buffer (None at step
    0). Returns (mean grads pytree, new residual).
    """
    n = jax.lax.axis_size(axis_name)
    flat, spec = _flatten(grads)
    size = flat.shape[0]
    pad = (-size) % n
    flat_p = jnp.pad(flat, (0, pad))
    if residual is None:
        residual = jnp.zeros_like(flat_p)

    corrected = flat_p + residual
    q, s = compress_int8(corrected)                    # int8 payload
    new_residual = corrected - decompress_int8(q, s)

    # 2. reduce-scatter via all_to_all on the int8 wire
    chunk = flat_p.shape[0] // n
    q_chunks = q.reshape(n, chunk)
    recv = jax.lax.all_to_all(q_chunks, axis_name, split_axis=0,
                              concat_axis=0, tiled=False)  # (n, chunk) int8
    s_all = jax.lax.all_gather(s, axis_name)               # (n,) f32
    part = jnp.sum(recv.astype(jnp.float32) * s_all[:, None], axis=0) / n

    # 3-4. requantize the reduced shard; all_gather int8 + scales
    q2, s2 = compress_int8(part)
    q2_all = jax.lax.all_gather(q2, axis_name)             # (n, chunk) int8
    s2_all = jax.lax.all_gather(s2, axis_name)             # (n,) f32
    mean_flat = (q2_all.astype(jnp.float32)
                 * s2_all[:, None]).reshape(-1)[:size]
    return _unflatten(mean_flat, spec), new_residual
