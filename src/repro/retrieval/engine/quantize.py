"""Posting-list compression: quantized impacts + delta-encoded doc ids
(DESIGN.md §8.2).

A raw posting costs 8 bytes (i32 doc id + f32 impact). The quantized
layout stores the same posting in 1.5 bytes:

* **Impacts — nibble-packed u4, per-term affine.** LSR impacts within
  one posting list cluster tightly (a term's weight is IDF-like across
  documents), so a per-term affine code ``val ~= lo[t] + (q-1) *
  (hi[t]-lo[t])/14`` with q in 1..15 keeps the dequantization error
  <= spread/28. Code 0 is reserved for phantom postings (see below),
  two codes pack per byte. ``lo``/``hi`` are stored f16 per term; the
  build quantizes against the f16-rounded bounds so build and scorer
  agree bit-exactly.

* **Doc ids — delta encoding with escape phantoms.** Posting lists
  are doc-id ascending, so ids are stored as gaps. A gap g > the
  delta dtype's escape value E is encoded as ``g // E`` phantom
  postings (delta=E, code=0) before the real posting's ``g % E``: the
  scorer's running cumsum passes through phantoms, whose code-0
  impact contributes exactly 0. The first posting's "gap" is its
  absolute doc id. The build picks u8 or u16 deltas by total bytes:
  dense posting lists (small gaps) take u8 (1.5 B/posting); sparse
  lists whose gaps would drown u8 in phantoms take u16 (2.5
  B/posting) instead of silently exploding the index and the
  per-query gather window.

The scorer (``quantized_scores``) walks the same padded per-term
windows as the exact impact scorer and dequantizes on the fly inside
the jitted gather — unpack nibble, affine-decode, cumsum the deltas to
absolute doc ids, segment-sum. No dequantized copy of the index ever
exists in memory.

On LSR-shaped corpora this is a >= 4x index-size reduction at
unchanged top-k ids (pinned by tests and ``benchmarks/bench_engine.py``
— the asymptote is 8 B / 1.5 B ~= 5.3x, minus O(V) metadata).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels._common import interpret_mode
from repro.retrieval.index import InvertedIndex
from repro.retrieval.sparse_rep import SparseRep
from repro.sparse.segment import segment_sum

Array = jax.Array

_LEVELS = 14          # q in 1..15 -> 14 steps between lo and hi
_DELTA_DTYPES = ((np.uint8, 255), (np.uint16, 65535))  # (dtype, escape)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class QuantizedIndex:
    term_starts: Array      # (V,) i32 — offsets in *postings* units
    term_lens: Array        # (V,) u16/i32 — expanded list lengths
    packed_vals: Array      # (ceil(P/2),) u8 — two u4 codes per byte
    deltas: Array           # (P,) u8/u16 — doc-id gaps (max = escape)
    term_lo: Array          # (V,) f16 — affine low per term
    term_hi: Array          # (V,) f16 — affine high per term
    n_docs: int             # static
    vocab_size: int         # static
    max_postings: int       # static — longest *expanded* list (>= 1)
    n_source_postings: int  # static — postings before phantom expansion

    def tree_flatten(self):
        children = (self.term_starts, self.term_lens, self.packed_vals,
                    self.deltas, self.term_lo, self.term_hi)
        aux = (self.n_docs, self.vocab_size, self.max_postings,
               self.n_source_postings)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def n_postings(self) -> int:
        return self.deltas.shape[0]

    def memory_bytes(self) -> int:
        return int(sum(np.asarray(a).nbytes for a in (
            self.term_starts, self.term_lens, self.packed_vals,
            self.deltas, self.term_lo, self.term_hi)))

    def stats(self) -> Dict[str, float]:
        return {
            "n_docs": self.n_docs,
            "vocab_size": self.vocab_size,
            "n_postings": self.n_postings,
            "n_source_postings": self.n_source_postings,
            "phantom_frac": 1.0 - self.n_source_postings
            / max(self.n_postings, 1),
            "max_postings": self.max_postings,
            "memory_bytes": self.memory_bytes(),
        }


def quantize_index(index: InvertedIndex) -> QuantizedIndex:
    """Compress an ``InvertedIndex`` (host-side numpy build)."""
    V = index.vocab_size
    starts = np.asarray(index.term_starts, np.int64)
    lens = np.asarray(index.term_lens, np.int64)
    docs = np.asarray(index.postings_doc, np.int64)
    vals = np.asarray(index.postings_val, np.float32)
    P = docs.shape[0]
    has_real = lens.sum() > 0

    # per-term affine bounds over the *source* impacts, f16-rounded so
    # the scorer's decode matches the build's encode exactly
    term_of = np.repeat(np.arange(V), lens)          # (P_real,)
    real = slice(0, term_of.shape[0])
    lo = np.full(V, np.inf, np.float32)
    hi = np.zeros(V, np.float32)
    if has_real:
        np.minimum.at(lo, term_of, vals[real])
        np.maximum.at(hi, term_of, vals[real])
    lo[~np.isfinite(lo)] = 0.0
    lo16 = lo.astype(np.float16)
    hi16 = hi.astype(np.float16)
    lo_r = lo16.astype(np.float32)
    step = (hi16.astype(np.float32) - lo_r) / _LEVELS

    # u4 codes (1..15) for real postings
    if has_real:
        s = step[term_of]
        q = np.where(s > 0,
                     np.rint((vals[real] - lo_r[term_of])
                             / np.where(s > 0, s, 1.0)),
                     0.0)
        codes = (1 + np.clip(q, 0, _LEVELS)).astype(np.uint8)
    else:
        codes = np.ones(0, np.uint8)

    # doc-id gaps (reset at term boundaries; first gap = absolute id)
    gaps = np.empty(term_of.shape[0], np.int64)
    if has_real:
        d = docs[real]
        gaps[:] = d
        gaps[1:] -= d[:-1]
        first = starts[lens > 0]
        gaps[first] = d[first]

    # escape expansion: gap = escape*m + r -> m phantoms + the real
    # entry. Pick the delta width minimizing total posting bytes —
    # u8 for dense lists, u16 when large gaps would drown u8 in
    # phantoms (and blow up max_postings, the per-query gather width).
    def posting_bytes(dtype, escape):
        n = int((1 + gaps // escape).sum()) if has_real else 1
        return n * (np.dtype(dtype).itemsize + 0.5)

    dtype, escape = min(_DELTA_DTYPES,
                        key=lambda de: posting_bytes(*de))
    m = gaps // escape
    counts = (1 + m).astype(np.int64)
    Pq = int(counts.sum()) if has_real else 1
    out_deltas = np.full(Pq, escape, dtype)
    out_codes = np.zeros(Pq, np.uint8)
    if has_real:
        real_pos = np.cumsum(counts) - 1
        out_deltas[real_pos] = (gaps % escape).astype(dtype)
        out_codes[real_pos] = codes
        new_lens = np.zeros(V, np.int64)
        np.add.at(new_lens, term_of, counts)
    else:
        out_deltas[0] = 0
        new_lens = np.zeros(V, np.int64)
    new_starts = np.zeros(V, np.int64)
    np.cumsum(new_lens[:-1], out=new_starts[1:])

    # nibble-pack: even posting -> low nibble, odd -> high
    padded = np.zeros(Pq + (Pq & 1), np.uint8)
    padded[:Pq] = out_codes
    packed = (padded[0::2] | (padded[1::2] << 4)).astype(np.uint8)

    lens_dtype = np.uint16 if new_lens.max(initial=0) < 2**16 else np.int32
    return QuantizedIndex(
        term_starts=jnp.asarray(new_starts.astype(np.int32)),
        term_lens=jnp.asarray(new_lens.astype(lens_dtype)),
        packed_vals=jnp.asarray(packed),
        deltas=jnp.asarray(out_deltas),
        term_lo=jnp.asarray(lo16),
        term_hi=jnp.asarray(hi16),
        n_docs=index.n_docs,
        vocab_size=index.vocab_size,
        max_postings=max(int(new_lens.max(initial=0)), 1),
        n_source_postings=int(lens.sum()),
    )


def quantized_scores(queries: SparseRep, index: QuantizedIndex) -> Array:
    """Dense ``(B, n_docs)`` scores, dequantizing on the fly.

    Identical window walk to ``score.impact_scores``; per lane the
    u4 code is unpacked from its byte, affine-decoded against the
    term's f16 bounds, and the u8 gaps are cumsum-ed into absolute doc
    ids. Phantom lanes (code 0) decode to weight 0 and only advance
    the cumsum.
    """
    l_max = index.max_postings
    p_total = index.deltas.shape[0]
    lane = jnp.arange(l_max, dtype=jnp.int32)
    step = (index.term_hi.astype(jnp.float32)
            - index.term_lo.astype(jnp.float32)) / _LEVELS

    def one(qv: Array, qi: Array) -> Array:
        starts = index.term_starts[qi]                     # (Q,)
        lens = index.term_lens[qi].astype(jnp.int32)       # (Q,)
        pos = starts[:, None] + lane[None, :]              # (Q, Lmax)
        valid = (lane[None, :] < lens[:, None]) & (qv > 0)[:, None]
        pos = jnp.clip(pos, 0, p_total - 1)

        byte = index.packed_vals[pos >> 1].astype(jnp.int32)
        code = jnp.where((pos & 1) == 1, byte >> 4, byte & 0xF)
        code = jnp.where(valid, code, 0)

        gaps = jnp.where(valid, index.deltas[pos].astype(jnp.int32), 0)
        docs = jnp.cumsum(gaps, axis=1)                    # absolute ids

        val = (index.term_lo[qi].astype(jnp.float32)[:, None]
               + (code - 1) * step[qi][:, None])
        w = jnp.where(code > 0, val, 0.0) * qv[:, None]
        return segment_sum(w.ravel(), docs.ravel(), index.n_docs)

    qv = queries.values.reshape(-1, queries.width).astype(jnp.float32)
    qi = queries.indices.reshape(-1, queries.width)
    return jax.vmap(one)(qv, qi)


@functools.partial(jax.jit, static_argnames=("k",))
def _quantized_retrieve(queries: SparseRep, index: QuantizedIndex,
                        k: int) -> Tuple[Array, Array]:
    scores = quantized_scores(queries, index)
    vals, idx = jax.lax.top_k(scores, k)
    return vals, idx.astype(jnp.int32)


def quantized_retrieve(queries: SparseRep, index: QuantizedIndex,
                       k: int = 10) -> Tuple[Array, Array]:
    """Top-k over the compressed index — same contract as ``retrieve``."""
    return _quantized_retrieve(queries, index, min(k, index.n_docs))


@jax.jit
def _fused_q_windows(queries: SparseRep, index: QuantizedIndex
                     ) -> Tuple[Array, ...]:
    """Gather the *packed* per-query windows for the fused kernel.

    Unlike ``quantized_scores``, nothing is decoded here: the kernel
    receives the raw packed bytes and gaps plus the per-term affine
    metadata, and the nibble unpack / affine decode / gap cumsum all
    happen inside the Pallas grid (kernels/impact_score.py) — the
    standalone dequant materialization is gone.
    """
    l_max = index.max_postings
    p_total = index.deltas.shape[0]
    lane = jnp.arange(l_max, dtype=jnp.int32)
    qv = queries.values.reshape(-1, queries.width).astype(jnp.float32)
    qi = queries.indices.reshape(-1, queries.width)
    starts = index.term_starts[qi]                         # (B, Q)
    lens = index.term_lens[qi].astype(jnp.int32)           # (B, Q)
    pos = starts[:, :, None] + lane[None, None, :]         # (B, Q, L)
    pos = jnp.clip(pos, 0, p_total - 1)
    byte_win = index.packed_vals[pos >> 1].astype(jnp.int32)
    gap_win = index.deltas[pos].astype(jnp.int32)
    lo = index.term_lo[qi].astype(jnp.float32)
    step = (index.term_hi[qi].astype(jnp.float32) - lo) / _LEVELS
    return byte_win, gap_win, starts, lens, qv, lo, step


def fused_quantized_retrieve(
    queries: SparseRep,
    index: QuantizedIndex,
    k: int = 10,
    *,
    block_n: Optional[int] = None,
    block_w: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[Array, Array]:
    """Fused-kernel top-k over the compressed index — id-identical to
    ``quantized_retrieve`` (the in-kernel decode is bit-exact against
    the same f16-rounded bounds).

    None blocks resolve through the autotune cache/heuristic under the
    ``u4`` ``_impact`` keys; ``interpret`` defaults to the Pallas
    interpreter on the CPU (``kernels._common.interpret_mode``).
    """
    from repro.kernels.autotune import resolve_impact_blocks
    from repro.kernels.impact_score import fused_quantized_topk

    if interpret is None:
        interpret = interpret_mode()
    b = queries.values.reshape(-1, queries.width).shape[0]
    block_n, block_w = resolve_impact_blocks(
        b, queries.width, index.max_postings, index.n_docs,
        block_n, block_w, variant="u4")
    byte_win, gap_win, starts, lens, qv, lo, step = _fused_q_windows(
        queries, index)
    return fused_quantized_topk(
        byte_win, gap_win, starts, lens, qv, lo, step,
        n_docs=index.n_docs, k=min(k, index.n_docs),
        block_n=block_n, block_w=block_w, interpret=interpret)
