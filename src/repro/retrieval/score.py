"""Retrieval scoring — impact, pruned, quantized, fused, sharded,
streaming-kernel, and dense paths behind one ``retrieve()``
dispatcher.

Dispatch table (``method=``):

    method       queries            corpus             scoring
    ---------    ---------------    ---------------    -------------
    "impact"     SparseRep          InvertedIndex      exact segment-
                                                       sums into (B, N)
    "fused"      SparseRep          InvertedIndex      fused Pallas
                                    or QuantizedIndex  kernel: posting
                                                       window resident
                                                       in VMEM, per-
                                                       tile one-hot
                                                       MAC + running
                                                       top-k merge — no
                                                       (B, N) matrix;
                                                       u4 windows are
                                                       dequantized
                                                       inside the
                                                       kernel (kernels/
                                                       impact_score)
    "pruned"     SparseRep          InvertedIndex      two-tier MaxScore:
                                    (+ term_ubs and    upper-bound pass
                                    forward rows)      -> exact rescore
                                                       of candidates
                                                       (engine/pruning)
    "quantized"  SparseRep          QuantizedIndex     on-the-fly
                                                       dequantized
                                                       segment-sums
                                                       (engine/quantize)
    "sharded"    SparseRep          ShardedIndex       per-shard impact
                                                       + cross-shard
                                                       top-k merge
                                                       (engine/
                                                       sharded_index)
    "term_        SparseRep         TermShardedIndex   per-shard PARTIAL
     sharded"                                          sums over vocab
                                                       ranges, psum/
                                                       all-reduce, one
                                                       global top-k
                                                       (engine/
                                                       term_sharded)
    "shard2d"    SparseRep          Shard2DIndex       (doc x term) grid:
                                                       per-cell partials
                                                       psum'd over the
                                                       term axis into
                                                       exact chunk
                                                       scores, then the
                                                       doc axis merges
                                                       per-chunk top-k
                                                       via all_gather +
                                                       re-top-k (engine/
                                                       shard2d)
    "streaming"  dense or rep       dense (N, V)       fused Pallas
                                                       running top-k
    "dense"      dense or rep       dense (N, V)       (B, N) einsum
                                                       + lax.top_k
    "auto"       resolved from the corpus type:
                 * QuantizedIndex: "fused" for corpora >= AUTO_FUSED_N
                   docs (the (B, N) matrix stops being a rounding
                   error), "quantized" below that
                 * ShardedIndex                -> "sharded"
                 * TermShardedIndex            -> "term_sharded"
                 * Shard2DIndex                -> "shard2d"
                 * InvertedIndex with upper bounds AND forward rows
                   (an engine build)           -> "pruned"
                 * any other InvertedIndex: "fused" at >= AUTO_FUSED_N
                   docs, "impact" below
                 * dense matrix: "streaming" for corpora >=
                   AUTO_STREAMING_N rows, "dense" below that

Keyword arguments are validated against the *resolved* method: passing
a kwarg the method cannot honor (``mesh`` with ``"impact"``,
``prune_margin`` with ``"streaming"``) raises instead of being
silently ignored — a typo'd or misrouted tuning knob must not
masquerade as a no-op. The per-method table is ``_METHOD_KWARGS``.

Which *placement* to build in the first place is the upstream choice:
``engine.shard2d.plan_placement(stats, n_devices, per_device_hbm)``
returns a frozen ``ShardPlan`` ``(doc_shards, term_shards, replicas,
axis_order, reason)`` accounting posting bytes, the O(V) term
directory (replicated by doc sharding, divided by term sharding) and
forward-row storage — doc sharding merges cheap (all_gather of k
winners), term sharding splits the posting arrays exactly (the
|V|~250k multilingual regime) at the cost of an all-reduce over
partials, and the 2D grid composes both when neither axis alone fits.
Shard topology rides into ``retrieve`` through the one ``plan=``
kwarg (validated against the built index); the old string-returning
``choose_shard_axis`` survives as a deprecated shim.

All paths return ``(vals (B, k) f32, idx (B, k) i32)`` with identical
ids (scores within fp/quantization tolerance) for equivalent inputs —
the parity tests in ``tests/test_retrieval.py``,
``tests/test_kernels_impact.py`` and ``tests/test_engine.py`` pin that
down. ``pruned`` is id-identical to ``impact`` at the default safe
margin (0.0) with a sufficient candidate budget; ``prune_margin`` > 0
trades recall for speed.

The impact path is the sparse-native one: per query row it gathers the
posting lists of the query's active terms (padded to the index's
``max_postings`` static width) and reduces them with
``sparse/segment.py`` segment-sums — ``scores[d] = sum_t q[t] *
impact[t, d]`` — exactly the inverted-index formulation GPUSparse
serves LSR with. Work per query is ``O(Q * max_postings)``; the
padding cost is the usual TPU trade of ragged gathers for one static
dense gather + masked reduce. The fused path walks the *same* gathered
windows but scores and merges tile-by-tile inside one Pallas kernel
(DESIGN.md §12), so its peak scoring memory is the window plus the
(B, k) winners — independent of the corpus size.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.kernels._common import interpret_mode
from repro.kernels.topk_score import topk_score
from repro.retrieval.index import InvertedIndex
from repro.retrieval.sparse_rep import SparseRep
from repro.sparse.segment import segment_sum

Array = jax.Array
Queries = Union[Array, SparseRep]
Corpus = Union[Array, InvertedIndex]

METHODS = ("auto", "impact", "fused", "pruned", "quantized", "sharded",
           "term_sharded", "shard2d", "streaming", "dense")
# methods that need an index-shaped corpus (not a dense matrix)
_INDEX_METHODS = ("impact", "fused", "pruned", "quantized", "sharded",
                  "term_sharded", "shard2d")
# corpora at or above this many rows route "auto" to the streaming
# kernel (the (B, N) score matrix stops being a rounding error)
AUTO_STREAMING_N = 16384
# indexed corpora at or above this many docs route "auto" to the fused
# impact kernel for the same reason: below it the dense (B, N) matrix
# is small enough that the plain segment-sum path's simplicity wins
AUTO_FUSED_N = 16384

# kwargs each resolved method can honor; everything else raises.
# ``interpret`` spans the Pallas-backed paths, block sizes go to the
# kernel they tune, pruning knobs to the two-tier paths. Shard
# topology rides in the one ``plan=`` kwarg (a ShardPlan, validated
# against the built index) + ``mesh=`` for the shard_map paths.
# impact/dense/quantized take no tuning kwargs.
_METHOD_KWARGS = {
    "impact": frozenset(),
    "dense": frozenset(),
    "quantized": frozenset(),
    "fused": frozenset({"interpret", "block_n", "block_w"}),
    "streaming": frozenset({"interpret", "block_b", "block_n"}),
    "pruned": frozenset({"prune_margin", "candidates"}),
    "sharded": frozenset({"mesh", "plan"}),
    "term_sharded": frozenset({"mesh", "plan", "prune_margin",
                               "candidates"}),
    "shard2d": frozenset({"mesh", "plan", "prune_margin",
                          "candidates"}),
}


@functools.lru_cache(maxsize=1)
def _engine():
    """Engine-type lookup, imported once per process.

    The engine package imports the index/rep modules this module also
    feeds, so the imports stay function-local to keep the import graph
    acyclic — but cached, not re-executed per ``retrieve()`` call like
    the old per-call ``from ... import`` blocks.
    """
    from repro.retrieval.engine import (pruning, quantize, shard2d,
                                        sharded_index, term_sharded)

    return {
        "QuantizedIndex": quantize.QuantizedIndex,
        "quantized_retrieve": quantize.quantized_retrieve,
        "fused_quantized_retrieve": quantize.fused_quantized_retrieve,
        "ShardedIndex": sharded_index.ShardedIndex,
        "sharded_retrieve": sharded_index.sharded_retrieve,
        "TermShardedIndex": term_sharded.TermShardedIndex,
        "term_sharded_retrieve": term_sharded.term_sharded_retrieve,
        "Shard2DIndex": shard2d.Shard2DIndex,
        "shard2d_retrieve": shard2d.shard2d_retrieve,
        "pruned_retrieve": pruning.pruned_retrieve,
    }


# ---------------------------------------------------------------------------
# impact scoring (inverted index)
# ---------------------------------------------------------------------------

def impact_scores(queries: SparseRep, index: InvertedIndex) -> Array:
    """Dense ``(B, n_docs)`` impact scores — no (N, V) matrix anywhere.

    Padded query slots (value 0) and posting-list padding both
    contribute exactly 0 to the segment-sums, so no masking state
    leaks into the scores.
    """
    l_max = index.max_postings
    p_total = index.postings_doc.shape[0]
    lane = jnp.arange(l_max, dtype=jnp.int32)

    def one(qv: Array, qi: Array) -> Array:
        starts = index.term_starts[qi]                     # (Q,)
        lens = index.term_lens[qi]                         # (Q,)
        pos = starts[:, None] + lane[None, :]              # (Q, Lmax)
        valid = (lane[None, :] < lens[:, None]) & (qv > 0)[:, None]
        pos = jnp.clip(pos, 0, p_total - 1)
        docs = jnp.where(valid, index.postings_doc[pos], 0)
        w = jnp.where(valid, index.postings_val[pos], 0.0) * qv[:, None]
        return segment_sum(w.ravel(), docs.ravel(), index.n_docs)

    qv = queries.values.reshape(-1, queries.width).astype(jnp.float32)
    qi = queries.indices.reshape(-1, queries.width)
    return jax.vmap(one)(qv, qi)


# ---------------------------------------------------------------------------
# fused impact retrieval (Pallas kernel over gathered windows)
# ---------------------------------------------------------------------------

@jax.jit
def _fused_windows(queries: SparseRep, index: InvertedIndex
                   ) -> Tuple[Array, Array]:
    """Flat ``(B, Q * L_max)`` weight/doc windows for the fused kernel.

    The same padded gather as ``impact_scores`` — invalid lanes carry
    weight exactly 0 — but flattened to the kernel's posting axis
    instead of being segment-summed into (B, N).
    """
    l_max = index.max_postings
    p_total = index.postings_doc.shape[0]
    lane = jnp.arange(l_max, dtype=jnp.int32)
    qv = queries.values.reshape(-1, queries.width).astype(jnp.float32)
    qi = queries.indices.reshape(-1, queries.width)
    starts = index.term_starts[qi]                         # (B, Q)
    lens = index.term_lens[qi]                             # (B, Q)
    pos = starts[:, :, None] + lane[None, None, :]         # (B, Q, L)
    valid = ((lane[None, None, :] < lens[:, :, None])
             & (qv > 0)[:, :, None])
    pos = jnp.clip(pos, 0, p_total - 1)
    docs = jnp.where(valid, index.postings_doc[pos], 0)
    w = jnp.where(valid, index.postings_val[pos], 0.0) * qv[:, :, None]
    b = w.shape[0]
    return w.reshape(b, -1), docs.reshape(b, -1)


def fused_retrieve(
    queries: SparseRep,
    index: InvertedIndex,
    k: int = 10,
    *,
    block_n: Optional[int] = None,
    block_w: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[Array, Array]:
    """Fused-kernel top-k over an ``InvertedIndex`` — id-identical to
    the ``impact`` path (pinned by tests/test_kernels_impact.py).

    None blocks resolve through the autotune cache/heuristic
    (``_impact`` keys); ``interpret`` defaults to the Pallas
    interpreter on the CPU (``kernels._common.interpret_mode``).
    """
    from repro.kernels.autotune import resolve_impact_blocks
    from repro.kernels.impact_score import fused_impact_topk

    if interpret is None:
        interpret = interpret_mode()
    b = queries.values.reshape(-1, queries.width).shape[0]
    block_n, block_w = resolve_impact_blocks(
        b, queries.width, index.max_postings, index.n_docs,
        block_n, block_w, variant="f32")
    w, docs = _fused_windows(queries, index)
    return fused_impact_topk(
        w, docs, n_docs=index.n_docs, k=min(k, index.n_docs),
        block_n=block_n, block_w=block_w, interpret=interpret)


# ---------------------------------------------------------------------------
# dispatcher
# ---------------------------------------------------------------------------

def _dense_queries(queries: Queries, vocab_size: int) -> Array:
    if isinstance(queries, SparseRep):
        return queries.to_dense(vocab_size)
    return queries


def _resolve_method(method: str, corpus: Corpus) -> str:
    if method not in METHODS:
        raise ValueError(f"unknown retrieval method {method!r}; "
                         f"one of {list(METHODS)}")
    if method != "auto":
        return method
    eng = _engine()
    if isinstance(corpus, eng["QuantizedIndex"]):
        return ("fused" if corpus.n_docs >= AUTO_FUSED_N
                else "quantized")
    if isinstance(corpus, eng["ShardedIndex"]):
        return "sharded"
    if isinstance(corpus, eng["TermShardedIndex"]):
        return "term_sharded"
    if isinstance(corpus, eng["Shard2DIndex"]):
        return "shard2d"
    if isinstance(corpus, InvertedIndex):
        # an engine build (upper bounds + forward rows) can serve the
        # two-tier pruned path; a bare PR-3 index only the exact ones
        if corpus.has_upper_bounds and corpus.has_forward:
            return "pruned"
        return "fused" if corpus.n_docs >= AUTO_FUSED_N else "impact"
    return "streaming" if corpus.shape[0] >= AUTO_STREAMING_N else "dense"


def _check_kwargs(method: str, passed: dict) -> None:
    """Raise on kwargs the resolved method cannot honor."""
    allowed = _METHOD_KWARGS[method]
    stray = [name for name, value in passed.items()
             if value is not None and name not in allowed]
    if stray:
        raise ValueError(
            f"method={method!r} does not accept "
            f"{', '.join(sorted(stray))} (accepted: "
            f"{sorted(allowed) if allowed else 'no tuning kwargs'}); "
            "refusing to silently ignore a tuning knob")


@functools.partial(jax.jit, static_argnames=("k",))
def _dense_retrieve(q: Array, C: Array, k: int) -> Tuple[Array, Array]:
    scores = jnp.einsum("bv,nv->bn", q.astype(jnp.float32),
                        C.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
    vals, idx = jax.lax.top_k(scores, k)
    return vals, idx.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k",))
def _impact_retrieve(queries: SparseRep, index: InvertedIndex, k: int
                     ) -> Tuple[Array, Array]:
    scores = impact_scores(queries, index)
    vals, idx = jax.lax.top_k(scores, k)
    return vals, idx.astype(jnp.int32)


def _check_plan(plan, method: str, doc_shards: int, term_shards: int
                ) -> None:
    """A ``plan=`` must describe the index it rides with: the grid the
    planner chose has to match the grid that was actually built."""
    if (plan.doc_shards, plan.term_shards) != (doc_shards, term_shards):
        raise ValueError(
            f"method={method!r}: plan grid "
            f"{plan.doc_shards}x{plan.term_shards} (doc x term) does "
            f"not match the built index "
            f"{doc_shards}x{term_shards} — rebuild from the plan or "
            f"re-plan from the corpus stats")


def retrieve(
    queries: Queries,           # (B, V) dense or SparseRep
    corpus: Corpus,             # (N, V) dense matrix or an index
    k: int = 10,
    *,
    method: str = "auto",
    interpret: Optional[bool] = None,
    block_b: Optional[int] = None,
    block_n: Optional[int] = None,
    block_w: Optional[int] = None,
    prune_margin: Optional[float] = None,
    candidates: Optional[int] = None,
    mesh=None,
    plan=None,
) -> Tuple[Array, Array]:
    """Top-k retrieval via the method table in the module docstring.

    ``k`` is clamped to the corpus size so every path returns the same
    ``(B, min(k, N))`` shape. Tuning kwargs are validated against the
    *resolved* method (``_METHOD_KWARGS``) — a kwarg the method cannot
    honor raises instead of being ignored. ``interpret`` affects the
    Pallas-backed paths (None = auto: interpreter on the CPU only);
    ``block_b``/``block_n`` tune the streaming kernel and
    ``block_n``/``block_w`` the fused one (None = autotune cache /
    heuristic); ``prune_margin``/``candidates`` drive the pruned path
    (``engine.pruning``) and, for margins > 0, the sharded two-tier
    compositions; ``mesh`` runs the sharded paths under shard_map
    (None = single-device vmap over shards) and ``plan`` — a
    ``ShardPlan`` from ``engine.shard2d.plan_placement`` — carries the
    shard topology: it is validated against the built index, and for
    ``shard2d`` its ``axis_order`` maps the (doc, term) grid onto the
    mesh axes.
    """
    method = _resolve_method(method, corpus)
    _check_kwargs(method, {
        "interpret": interpret, "block_b": block_b, "block_n": block_n,
        "block_w": block_w, "prune_margin": prune_margin,
        "candidates": candidates, "mesh": mesh, "plan": plan,
    })

    if method in _INDEX_METHODS:
        eng = _engine()
        if not isinstance(queries, SparseRep):
            raise ValueError(
                f"method={method!r} needs SparseRep queries — sparsify "
                "with retrieval.sparse_rep.sparsify_topk/threshold "
                "(an explicit budget, not a silent one)")
        if method == "fused":
            if isinstance(corpus, eng["QuantizedIndex"]):
                return eng["fused_quantized_retrieve"](
                    queries, corpus, k, block_n=block_n,
                    block_w=block_w, interpret=interpret)
            if not isinstance(corpus, InvertedIndex):
                raise ValueError(
                    "method='fused' needs an InvertedIndex or "
                    "QuantizedIndex corpus — build one with "
                    "retrieval.index.build_inverted_index or "
                    "engine.quantize.quantize_index")
            return fused_retrieve(queries, corpus, k, block_n=block_n,
                                  block_w=block_w, interpret=interpret)
        if method == "quantized":
            if not isinstance(corpus, eng["QuantizedIndex"]):
                raise ValueError(
                    "method='quantized' needs a QuantizedIndex corpus "
                    "— compress one with engine.quantize.quantize_index")
            return eng["quantized_retrieve"](queries, corpus, k)
        if method == "sharded":
            if not isinstance(corpus, eng["ShardedIndex"]):
                raise ValueError(
                    "method='sharded' needs a ShardedIndex corpus — "
                    "build one with engine.sharded_index.shard_index")
            if plan is not None:
                _check_plan(plan, method, corpus.n_shards, 1)
            return eng["sharded_retrieve"](queries, corpus, k,
                                           mesh=mesh)
        if method == "term_sharded":
            if not isinstance(corpus, eng["TermShardedIndex"]):
                raise ValueError(
                    "method='term_sharded' needs a TermShardedIndex "
                    "corpus — build one with "
                    "engine.term_sharded.term_shard_index")
            if plan is not None:
                _check_plan(plan, method, 1, corpus.n_shards)
            # margin 0 routes to the exact psum path (identical ids,
            # no candidate budget to size); > 0 opts into the
            # two-tier composition and requires forward rows
            margin = prune_margin if prune_margin is not None else 0.0
            return eng["term_sharded_retrieve"](
                queries, corpus, k, mesh=mesh,
                prune_margin=margin if margin > 0 else None,
                candidates=candidates)
        if method == "shard2d":
            if not isinstance(corpus, eng["Shard2DIndex"]):
                raise ValueError(
                    "method='shard2d' needs a Shard2DIndex corpus — "
                    "build one with engine.shard2d.shard2d_index")
            if plan is not None:
                _check_plan(plan, method, corpus.doc_shards,
                            corpus.term_shards)
            margin = prune_margin if prune_margin is not None else 0.0
            return eng["shard2d_retrieve"](
                queries, corpus, k, mesh=mesh, plan=plan,
                prune_margin=margin if margin > 0 else None,
                candidates=candidates)
        if not isinstance(corpus, InvertedIndex):
            raise ValueError(
                f"method={method!r} needs an InvertedIndex corpus — "
                "build one with retrieval.index.build_inverted_index")
        if method == "pruned":
            return eng["pruned_retrieve"](
                queries, corpus, k,
                prune_margin=(prune_margin if prune_margin is not None
                              else 0.0),
                candidates=candidates)
        return _impact_retrieve(queries, corpus, min(k, corpus.n_docs))

    if isinstance(corpus, InvertedIndex) or not hasattr(corpus, "shape"):
        raise ValueError(
            f"method={method!r} needs a dense (N, V) corpus matrix; "
            f"got {type(corpus).__name__} (use an index method or "
            "'auto')")
    n_docs, vocab = corpus.shape
    q = _dense_queries(queries, vocab)
    k = min(k, n_docs)

    if method == "dense":
        return _dense_retrieve(q, corpus, k)

    if interpret is None:
        interpret = interpret_mode()
    return topk_score(q, corpus, k=k,
                      block_b=block_b if block_b is not None else 8,
                      block_n=block_n if block_n is not None else 1024,
                      interpret=interpret)
