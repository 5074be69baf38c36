"""Fused impact scoring + streaming top-k — Pallas TPU kernel.

The Sparton fusion applied to the *query* side of LSR retrieval
(DESIGN.md §12). The plain-JAX impact scorer
(``retrieval/score.py:impact_scores``) gathers the query terms' posting
windows, segment-sums them into a dense ``(B, N)`` score matrix, and
runs a *separate* ``lax.top_k`` — at serving batch sizes and
million-doc corpora that matrix is the retrieval analogue of the
``(B, S, V)`` logit tensor Sparton refuses to materialize on the encode
side. This kernel streams it away: per query, the gathered posting
window stays resident in VMEM while doc-range tiles of width
``block_n`` are scored via a scatter-free one-hot contraction and
folded into a running ``(1, k)`` top-k that returns what
``topk_score.merge_topk`` returns for the same union. Peak scoring
memory is the window plus one ``(block_n,)`` tile — independent of N.

Grid: ``(B, N_pad / block_n)``, doc tiles innermost and visited in
ascending-id order so ties break to the lowest doc id exactly like the
reference ``lax.top_k`` path (the id-parity contract).

Scoring one tile: the flattened posting axis (``W = Q * L_max`` lanes
of ``(weight, doc_id)``) is walked in ``block_w`` chunks; each chunk
builds the ``(block_n, block_w)`` membership one-hot ``1[doc_c ==
d0 + n]`` and multiply-accumulates ``w_chunk @ onehot^T`` on the MXU —
the same irregular-scatter-to-dense-contraction trade as the backward
kernels' ``onehot_weights`` (Mosaic has no scatter). Mosaic has no
sort either, so the running top-k takes the maximum k times per tile.

Two entry points share that machinery:

* ``fused_impact_topk`` — raw f32 windows (from an ``InvertedIndex``).
* ``fused_quantized_topk`` — u4+delta windows (from a
  ``QuantizedIndex``): the *packed* byte and gap windows are shipped to
  the kernel, which unpacks nibbles, affine-decodes against the
  per-term bounds, and cumsums gaps to absolute doc ids once per query
  into VMEM scratch — the standalone dequant materialization
  ``quantized_scores`` pays is gone.

Both compile for the TPU and run under the Pallas interpreter on the
CPU (``_common.interpret_mode``).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._common import NEG_INF, compiler_params, pad_to

# Below NEG_INF: marks entries the top-k selection has already taken,
# so padded (NEG_INF) entries can still fill columns past n_docs.
_TAKEN = 2 * NEG_INF
_NO_ID = jnp.iinfo(jnp.int32).max


def _score_tile(w_ref, d_ref, d0, *, block_n: int, block_w: int):
    """Score one ``(1, block_n)`` doc tile from posting lanes in VMEM.

    ``w_ref``/``d_ref`` are ``(R, C)`` with C a multiple of
    ``block_w``; invalid lanes carry weight 0 (their doc id then
    contributes nothing). Each ``(1, block_w)`` chunk builds the
    transposed membership one-hot ``1[doc_c == d0 + n]`` as a
    ``(block_n, block_w)`` tile — docs broadcast over sublanes, so no
    lane-to-sublane relayout — and contracts it on the MXU at full f32
    precision, so scores match the unfused sum.
    """
    n_rows, n_cols = w_ref.shape
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (block_n, block_w), 0)

    def body(r, acc):
        # whole rows: Mosaic loads a dynamic row only from lane 0
        w_row = w_ref[pl.ds(r, 1), :]
        d_row = d_ref[pl.ds(r, 1), :]
        for c in range(0, n_cols, block_w):
            wc = w_row[:, c:c + block_w]
            dc = d_row[:, c:c + block_w]
            onehot_t = (dc - d0 == row_ids).astype(jnp.float32)
            acc = acc + jax.lax.dot_general(
                wc, onehot_t, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        return acc

    return jax.lax.fori_loop(
        0, n_rows, body, jnp.zeros((1, block_n), jnp.float32))


def _select_topk(run_vals, run_idx, new_vals, new_idx, k: int):
    """Top-k of the union of the running ``(1, k)`` set and one tile.

    Mosaic has no sort, so this takes the maximum k times. Ties go to
    the lowest doc id, which is what ``topk_score.merge_topk`` (a
    stable ``lax.top_k`` over ids visited in ascending order) returns.
    """
    col = jax.lax.broadcasted_iota(jnp.int32, run_vals.shape, 1)

    def body(t, carry):
        rv, nv, out_v, out_i = carry
        best = jnp.maximum(jnp.max(rv, axis=1, keepdims=True),
                           jnp.max(nv, axis=1, keepdims=True))
        pick = jnp.minimum(
            jnp.min(jnp.where(rv == best, run_idx, _NO_ID), axis=1,
                    keepdims=True),
            jnp.min(jnp.where(nv == best, new_idx, _NO_ID), axis=1,
                    keepdims=True))
        out_v = jnp.where(col == t, jnp.maximum(best, NEG_INF), out_v)
        out_i = jnp.where(col == t, pick, out_i)
        rv = jnp.where((run_idx == pick) & (rv == best), _TAKEN, rv)
        nv = jnp.where((new_idx == pick) & (nv == best), _TAKEN, nv)
        return rv, nv, out_v, out_i

    _, _, out_v, out_i = jax.lax.fori_loop(
        0, k, body, (run_vals, new_vals, run_vals, run_idx))
    return out_v, out_i


def _merge_tile(scores, val_ref, idx_ref, j, *, k: int, block_n: int,
                n_real: int):
    """Mask padded docs and fold one scored tile into the running top-k."""
    cand = j * block_n + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_n), 1)
    scores = jnp.where(cand < n_real, scores, NEG_INF)
    top_vals, top_idx = _select_topk(val_ref[...], idx_ref[...], scores,
                                     cand, k)
    val_ref[...] = top_vals
    idx_ref[...] = top_idx


def _init_topk(val_ref, idx_ref):
    val_ref[...] = jnp.full(val_ref.shape, NEG_INF, jnp.float32)
    idx_ref[...] = jnp.zeros(idx_ref.shape, jnp.int32)


def _impact_kernel(
    w_ref,      # (R, block_w) f32 — q[t] * impact, invalid lanes 0
    d_ref,      # (R, block_w) i32 — absolute doc ids
    val_ref,    # (1, k) out — running top-k values
    idx_ref,    # (1, k) out — running top-k doc ids
    *,
    k: int,
    block_n: int,
    block_w: int,
    n_real: int,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_topk(val_ref, idx_ref)

    scores = _score_tile(w_ref, d_ref, j * block_n, block_n=block_n,
                         block_w=block_w)
    _merge_tile(scores, val_ref, idx_ref, j, k=k, block_n=block_n,
                n_real=n_real)


def _lane_cumsum(x):
    """Inclusive prefix sum along lanes (Hillis-Steele: log2(n) rolls),
    exact in int32; Mosaic has no cumsum."""
    n = x.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    shift = 1
    while shift < n:
        x = x + jnp.where(lane >= shift, pltpu.roll(x, shift, 1), 0)
        shift *= 2
    return x


def _impact_q_kernel(
    byte_ref,    # (Q, L) i32 — gathered *packed* bytes per lane
    gap_ref,     # (Q, L) i32 — gathered doc-id gaps per lane
    starts_ref,  # (Q, 1) i32 — posting offsets (nibble parity)
    lens_ref,    # (Q, 1) i32 — expanded list lengths
    qv_ref,      # (Q, 1) f32 — query term weights
    lo_ref,      # (Q, 1) f32 — per-term affine low
    step_ref,    # (Q, 1) f32 — per-term affine step
    val_ref,     # (1, k) out
    idx_ref,     # (1, k) out
    w_dec,       # (Q, L) f32 VMEM scratch — decoded weights
    d_dec,       # (Q, L) i32 VMEM scratch — decoded doc ids
    *,
    k: int,
    block_n: int,
    block_w: int,
    n_real: int,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _decode():
        # in-kernel u4+delta decode, once per query — bit-identical to
        # quantized_scores: nibble parity from the absolute posting
        # position, code 0 = phantom (weight exactly 0, cumsum still
        # advances)
        _init_topk(val_ref, idx_ref)
        lane = jax.lax.broadcasted_iota(jnp.int32, byte_ref.shape, 1)
        qv = qv_ref[...]
        valid = (lane < lens_ref[...]) & (qv > 0)
        byte = byte_ref[...]
        code = jnp.where((starts_ref[...] + lane) & 1 == 1,
                         byte >> 4, byte & 0xF)
        code = jnp.where(valid, code, 0)
        d_dec[...] = _lane_cumsum(jnp.where(valid, gap_ref[...], 0))
        w_dec[...] = jnp.where(
            code > 0,
            lo_ref[...] + (code - 1).astype(jnp.float32) * step_ref[...],
            0.0) * qv

    scores = _score_tile(w_dec, d_dec, j * block_n, block_n=block_n,
                         block_w=block_w)
    _merge_tile(scores, val_ref, idx_ref, j, k=k, block_n=block_n,
                n_real=n_real)


def _topk_call(kernel, args, in_specs, scratch, *, B, k, n_tiles,
               interpret):
    """One grid step per (query, doc tile); the (1, k) running top-k of
    each query sits in a (B, 1, k) output so its block is whole in its
    last two dims, as Mosaic's tiling rule requires."""
    row = pl.BlockSpec((None, 1, k), lambda i, j: (i, 0, 0))
    vals, idx = pl.pallas_call(
        kernel,
        grid=(B, n_tiles),
        in_specs=in_specs,
        out_specs=[row, row],
        out_shape=[jax.ShapeDtypeStruct((B, 1, k), jnp.float32),
                   jax.ShapeDtypeStruct((B, 1, k), jnp.int32)],
        scratch_shapes=scratch,
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(*args)
    return vals[:, 0], idx[:, 0]


@functools.partial(
    jax.jit,
    static_argnames=("n_docs", "k", "block_n", "block_w", "interpret"))
def fused_impact_topk(
    w: jax.Array,       # (B, W) f32 — per-lane q[t]*impact, invalid 0
    docs: jax.Array,    # (B, W) i32 — per-lane absolute doc ids
    *,
    n_docs: int,
    k: int,
    block_n: int = 1024,
    block_w: int = 256,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Fused scoring + top-k over flat posting windows.

    Returns ``(vals (B, k), idx (B, k))`` with the ``topk_score``
    contract: ties to the lowest doc id, ``k > n_docs`` columns carry
    NEG_INF. Callers clamp k; the posting axis is zero-padded here so
    the chunk walk divides evenly (weight-0 lanes score nothing) and
    folded into ``(W / block_w, block_w)`` rows per query.
    """
    B, W = w.shape
    if W == 0:      # no active terms anywhere — keep the grid non-empty
        w = jnp.zeros((B, block_w), jnp.float32)
        docs = jnp.zeros((B, block_w), jnp.int32)
    wp = pad_to(w.astype(jnp.float32), 1, block_w)
    dp = pad_to(docs.astype(jnp.int32), 1, block_w)
    rows = wp.shape[1] // block_w
    win = pl.BlockSpec((None, rows, block_w), lambda i, j: (i, 0, 0))
    return _topk_call(
        functools.partial(_impact_kernel, k=k, block_n=block_n,
                          block_w=block_w, n_real=n_docs),
        (wp.reshape(B, rows, block_w), dp.reshape(B, rows, block_w)),
        [win, win], [], B=B, k=k, n_tiles=-(-n_docs // block_n),
        interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("n_docs", "k", "block_n", "block_w", "interpret"))
def fused_quantized_topk(
    byte_win: jax.Array,   # (B, Q, L) i32 — packed bytes per lane
    gap_win: jax.Array,    # (B, Q, L) i32 — doc-id gaps per lane
    starts: jax.Array,     # (B, Q) i32 — posting offsets per term
    lens: jax.Array,       # (B, Q) i32 — expanded lengths per term
    qv: jax.Array,         # (B, Q) f32 — query term weights
    lo: jax.Array,         # (B, Q) f32 — per-term affine low
    step: jax.Array,       # (B, Q) f32 — per-term affine step
    *,
    n_docs: int,
    k: int,
    block_n: int = 1024,
    block_w: int = 256,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Fused u4+delta dequant + scoring + top-k.

    The packed windows are decoded *inside* the kernel (nibble unpack,
    affine decode, gap cumsum), once per query into VMEM scratch — no
    dequantized ``(B, Q, L)`` weight or doc-id array is ever
    materialized in HBM. Lane padding added here lands outside every
    term's length, so the in-kernel valid mask zeroes it.
    """
    B, Q, L = byte_win.shape
    bw = pad_to(byte_win.astype(jnp.int32), 2, block_w)
    gw = pad_to(gap_win.astype(jnp.int32), 2, block_w)
    l_pad = bw.shape[2]
    meta = [a.reshape(B, Q, 1) for a in (
        starts.astype(jnp.int32), lens.astype(jnp.int32),
        qv.astype(jnp.float32), lo.astype(jnp.float32),
        step.astype(jnp.float32))]
    win = pl.BlockSpec((None, Q, l_pad), lambda i, j: (i, 0, 0))
    col = pl.BlockSpec((None, Q, 1), lambda i, j: (i, 0, 0))
    return _topk_call(
        functools.partial(_impact_q_kernel, k=k, block_n=block_n,
                          block_w=block_w, n_real=n_docs),
        (bw, gw, *meta), [win, win] + [col] * 5,
        [pltpu.VMEM((Q, l_pad), jnp.float32),
         pltpu.VMEM((Q, l_pad), jnp.int32)],
        B=B, k=k, n_tiles=-(-n_docs // block_n), interpret=interpret)


def fused_window_bytes(B: int, Q: int, L: int,
                       variant: str = "f32") -> int:
    """HBM bytes of the gathered posting windows one fused call ships.

    The analytic peak-scoring-memory model benches gate on: the fused
    path's scoring footprint is these windows plus the ``(B, k)``
    outputs — the ``(B, N)`` score matrix of the unfused paths never
    exists. ``variant`` "f32" = raw windows (f32 weights + i32 docs),
    "u4" = quantized windows (i32 packed bytes + i32 gaps + 5 small
    per-term columns).
    """
    if variant == "f32":
        return B * Q * L * (4 + 4)
    if variant == "u4":
        return B * Q * L * (4 + 4) + B * Q * 5 * 4
    raise ValueError(f"unknown fused variant {variant!r}")
