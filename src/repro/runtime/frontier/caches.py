"""Serving-frontier caches: query results + hot posting windows.

Skewed query popularity dominates real LSR serving traffic (GPUSparse
organizes its GPU inverted indexes around exactly this access
pattern), so the highest-leverage throughput win in front of
``CorpusEngine`` is remembering work: a repeated query should cost a
hash lookup, and the heaviest terms' gather windows should be resident
instead of re-gathered per query. Two caches, one hard invariant:

**Cache-on must be bit-identical to cache-off.** Not "close", not
"same ids" — identical arrays. Both caches get there structurally
rather than by tolerance:

* ``QueryResultCache`` — bounded, byte-accounted LRU over *final*
  search results ``(vals (k,), ext_ids (k,))``. The key is derived
  from the normalized query rep (the exact f32/i32 bytes of its
  active-prefix slots — f32 **is** the wire quantization; an optional
  ``decimals`` knob coarsens it, off by default because rounding two
  near-equal queries onto one entry would serve one query the other's
  results), the search kwargs, the corpus tag, and the index
  **generation**. ``IndexBuilder`` bumps its generation on every
  visible mutation (add/remove/dirty-flush/compact — compact too,
  because re-packing reorders fp summation), so a stale entry's key
  simply never matches again; ``invalidate()`` reclaims the dead
  entries' bytes eagerly.
* ``HotPostingCache`` — pins the top-m heaviest terms' gather windows
  (their posting lists padded to the index's static ``max_postings``
  width) so the fused scorer's window assembly skips the gather for
  exactly the terms that dominate it. Byte accounting charges the
  *padded* window (the memory the cache actually spends); the host
  mirror of the posting arrays stands in for the backing store the
  windows are pinned out of. ``ensure()`` rebuilds on generation
  change — a stale window is never served.

``hot_fused_retrieve`` reproduces ``score._fused_windows`` exactly
(same valid-lane masking, same f32 multiply, same resolved kernel
blocks) and feeds the same ``fused_impact_topk`` — so with the hot
cache on, off, or partially warm, the kernel sees bit-identical
inputs. ``CachedEngine`` wires both caches over a ``CorpusEngine``:
row-level result lookups (a batch with 3 hits only re-scores the 2
misses — rows are scored independently by every retrieval path),
generation-driven invalidation, and the ``base_scorer`` seam into
``IndexBuilder.search`` for hot-window scoring. DESIGN.md §13.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
from typing import Any, Dict, List, Mapping, Optional, Tuple

import numpy as np

from repro.kernels._common import interpret_mode
from repro.retrieval.index import InvertedIndex
from repro.retrieval.sparse_rep import SparseRep, split_rows, stack_rows

__all__ = [
    "QueryResultCache",
    "HotPostingCache",
    "CachedEngine",
    "query_cache_key",
    "hot_fused_retrieve",
]

# fixed per-entry overhead charged on top of the payload arrays (key
# digest + OrderedDict node + entry record, order-of-magnitude)
ENTRY_OVERHEAD_BYTES = 128


def query_cache_key(row: SparseRep, k: int, kwargs: Mapping[str, Any],
                    tag: str, generation: int,
                    decimals: Optional[int] = None) -> bytes:
    """Digest of one normalized query row + everything else that can
    change its result.

    The rep is normalized to its active prefix (``nnz`` leading slots
    — the sparsifiers keep actives as a value-descending prefix), so
    two reps differing only in padding width hash the same. Values
    enter as exact f32 bytes by default; ``decimals`` rounds first —
    a recall-over-parity knob that is deliberately **not** used by the
    serving stack (see module docstring).
    """
    v = np.asarray(row.values, np.float32).reshape(-1)
    i = np.asarray(row.indices, np.int32).reshape(-1)
    n = int(np.asarray(row.nnz).reshape(-1)[0])
    v, i = v[:n], i[:n]
    if decimals is not None:
        v = np.round(v, decimals).astype(np.float32)
    h = hashlib.blake2b(digest_size=16)
    h.update(v.tobytes())
    h.update(i.tobytes())
    meta = (int(k), str(tag), int(generation),
            tuple(sorted((name, repr(val)) for name, val
                         in kwargs.items() if val is not None)))
    h.update(repr(meta).encode())
    return h.digest()


@dataclasses.dataclass
class _Entry:
    tag: str
    generation: int
    vals: np.ndarray
    ids: np.ndarray
    nbytes: int


class QueryResultCache:
    """Bounded byte-accounted LRU over per-row search results.

    ``get``/``put`` move entries to the MRU end; inserts evict from
    the LRU end until the payload fits ``capacity_bytes``. Entries are
    tagged with a corpus name + generation so one tenant's mutation
    invalidates only its own entries (``invalidate(tag, live_gen)``)
    — keys embed the generation, so stale entries can never *hit*;
    invalidation just reclaims their bytes eagerly.
    """

    def __init__(self, capacity_bytes: int):
        if capacity_bytes <= 0:
            raise ValueError(
                f"capacity_bytes must be positive, got {capacity_bytes}")
        self.capacity_bytes = int(capacity_bytes)
        self._entries: "collections.OrderedDict[bytes, _Entry]" = \
            collections.OrderedDict()
        self.bytes_used = 0
        self.counters: collections.Counter = collections.Counter()

    def __len__(self) -> int:
        return len(self._entries)

    def get(self, key: bytes) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        e = self._entries.get(key)
        if e is None:
            self.counters["misses"] += 1
            return None
        self._entries.move_to_end(key)
        self.counters["hits"] += 1
        # copies: a caller mutating its result must not poison the
        # cache (parity with cache-off is a hard invariant)
        return e.vals.copy(), e.ids.copy()

    def put(self, key: bytes, tag: str, generation: int,
            vals: np.ndarray, ids: np.ndarray) -> None:
        vals = np.asarray(vals)
        ids = np.asarray(ids)
        nbytes = int(vals.nbytes + ids.nbytes) + ENTRY_OVERHEAD_BYTES
        if nbytes > self.capacity_bytes:
            self.counters["oversize_skipped"] += 1
            return
        old = self._entries.pop(key, None)
        if old is not None:
            self.bytes_used -= old.nbytes
        self._entries[key] = _Entry(str(tag), int(generation),
                                    vals.copy(), ids.copy(), nbytes)
        self.bytes_used += nbytes
        while self.bytes_used > self.capacity_bytes and self._entries:
            _, victim = self._entries.popitem(last=False)
            self.bytes_used -= victim.nbytes
            self.counters["evictions"] += 1

    def invalidate(self, tag: str, live_generation: int) -> int:
        """Reclaim every entry of ``tag`` whose generation is not the
        live one. Returns the number invalidated."""
        dead = [k for k, e in self._entries.items()
                if e.tag == tag and e.generation != live_generation]
        for k in dead:
            e = self._entries.pop(k)
            self.bytes_used -= e.nbytes
        self.counters["invalidations"] += len(dead)
        return len(dead)

    def stats(self) -> Dict[str, Any]:
        c = self.counters
        looked = c["hits"] + c["misses"]
        return {
            "entries": len(self._entries),
            "bytes_used": self.bytes_used,
            "capacity_bytes": self.capacity_bytes,
            "hits": c["hits"],
            "misses": c["misses"],
            "hit_rate": round(c["hits"] / looked, 4) if looked else 0.0,
            "evictions": c["evictions"],
            "invalidations": c["invalidations"],
        }


class HotPostingCache:
    """Pinned gather windows for the heaviest terms of one index.

    ``ensure(index, generation)`` (re)builds against the given index
    snapshot: posting arrays are mirrored to host once, terms are
    ranked by posting-list length, and the top terms' windows — docs
    and raw (un-multiplied) impact values padded to ``max_postings`` —
    are pinned until ``capacity_bytes`` is spent. ``window(term)``
    serves a pinned window or ``None`` (counted as hit/miss).

    Byte accounting covers the pinned padded windows — that padding is
    the memory the cache trades for gather-free scoring. A generation
    change drops everything (``invalidations`` counts rebuilds that
    discarded pins); a stale window is never served.
    """

    def __init__(self, capacity_bytes: int, *, top_m: int = 1 << 30):
        if capacity_bytes <= 0:
            raise ValueError(
                f"capacity_bytes must be positive, got {capacity_bytes}")
        self.capacity_bytes = int(capacity_bytes)
        self.top_m = int(top_m)
        self.counters: collections.Counter = collections.Counter()
        self.bytes_pinned = 0
        self.generation: Optional[int] = None
        self._index_ref: Optional[int] = None
        self._windows: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        self._pdoc: Optional[np.ndarray] = None
        self._pval: Optional[np.ndarray] = None
        self._starts: Optional[np.ndarray] = None
        self._lens: Optional[np.ndarray] = None
        self._l_max = 1

    @property
    def pinned_terms(self) -> int:
        return len(self._windows)

    def ensure(self, index: InvertedIndex, generation: int) -> None:
        """Make the cache current for ``(index, generation)``; no-op
        when it already is."""
        if (self.generation == generation
                and self._index_ref == id(index)):
            return
        if self._windows:
            self.counters["invalidations"] += 1
        self.counters["rebuilds"] += 1
        self.generation = generation
        self._index_ref = id(index)
        # host mirror of the backing store the windows are pinned from
        self._pdoc = np.asarray(index.postings_doc, np.int32)
        self._pval = np.asarray(index.postings_val, np.float32)
        self._starts = np.asarray(index.term_starts, np.int32)
        self._lens = np.asarray(index.term_lens, np.int32)
        self._l_max = int(index.max_postings)
        self._windows = {}
        self.bytes_pinned = 0
        per_window = self._l_max * (4 + 4) + ENTRY_OVERHEAD_BYTES
        # heaviest terms first — the gathers worth skipping; stable
        # sort keeps the pin set deterministic across rebuilds
        order = np.argsort(-self._lens, kind="stable")
        for t in order[:self.top_m]:
            n = int(self._lens[t])
            if n == 0 or self.bytes_pinned + per_window > \
                    self.capacity_bytes:
                break
            s = int(self._starts[t])
            docs = np.zeros(self._l_max, np.int32)
            vals = np.zeros(self._l_max, np.float32)
            docs[:n] = self._pdoc[s:s + n]
            vals[:n] = self._pval[s:s + n]
            self._windows[int(t)] = (docs, vals)
            self.bytes_pinned += per_window

    def window(self, term: int) -> Optional[Tuple[np.ndarray,
                                                  np.ndarray]]:
        win = self._windows.get(int(term))
        if win is None:
            self.counters["misses"] += 1
        else:
            self.counters["hits"] += 1
        return win

    def stats(self) -> Dict[str, Any]:
        c = self.counters
        looked = c["hits"] + c["misses"]
        return {
            "pinned_terms": self.pinned_terms,
            "bytes_pinned": self.bytes_pinned,
            "capacity_bytes": self.capacity_bytes,
            "hits": c["hits"],
            "misses": c["misses"],
            "hit_rate": round(c["hits"] / looked, 4) if looked else 0.0,
            "rebuilds": c["rebuilds"],
            "invalidations": c["invalidations"],
        }


def hot_fused_retrieve(
    queries: SparseRep,
    index: InvertedIndex,
    k: int,
    *,
    hot: HotPostingCache,
    block_n: Optional[int] = None,
    block_w: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """``score.fused_retrieve`` with hot-window reuse — bit-identical
    outputs for the same call, cache warm or cold.

    The window assembly mirrors ``score._fused_windows`` lane for
    lane: a (query-slot, lane) position is valid iff the lane is
    inside the term's posting list AND the slot's value is positive;
    valid lanes carry ``postings_val * qv`` (one f32 multiply — same
    op, same order as the jit path) and gathered doc ids, everything
    else exact zeros. Hot terms copy their pinned window instead of
    gathering; kernel blocks resolve through the same
    ``resolve_impact_blocks`` call as ``fused_retrieve``.
    """
    from repro.kernels.autotune import resolve_impact_blocks
    from repro.kernels.impact_score import fused_impact_topk

    if interpret is None:
        interpret = interpret_mode()
    qv = np.asarray(queries.values, np.float32).reshape(
        -1, queries.width)
    qi = np.asarray(queries.indices, np.int32).reshape(
        -1, queries.width)
    b, q_width = qv.shape
    block_n, block_w = resolve_impact_blocks(
        b, q_width, index.max_postings, index.n_docs,
        block_n, block_w, variant="f32")

    l_max = hot._l_max
    w = np.zeros((b, q_width, l_max), np.float32)
    docs = np.zeros((b, q_width, l_max), np.int32)
    for r in range(b):
        for s in range(q_width):
            v = qv[r, s]
            if not v > 0:
                continue
            t = int(qi[r, s])
            win = hot.window(t)
            if win is not None:
                docs[r, s] = win[0]
                w[r, s] = win[1] * v
            else:
                n = int(hot._lens[t])
                if n:
                    p0 = int(hot._starts[t])
                    docs[r, s, :n] = hot._pdoc[p0:p0 + n]
                    w[r, s, :n] = hot._pval[p0:p0 + n] * v
    return fused_impact_topk(
        w.reshape(b, -1), docs.reshape(b, -1),
        n_docs=index.n_docs, k=min(k, index.n_docs),
        block_n=block_n, block_w=block_w, interpret=interpret)


class CachedEngine:
    """The caching frontier over one ``CorpusEngine``.

    Mutations delegate straight through (the builder's generation bump
    is the invalidation signal); ``search`` goes row-level through the
    shared ``QueryResultCache`` — hits are served from cache, misses
    are re-batched into **one** underlying search (rows are scored
    independently by every retrieval path, so re-batching cannot
    change a row's result) and stored. When a ``HotPostingCache`` is
    attached, miss searches thread a hot-window ``base_scorer`` into
    ``IndexBuilder.search``; the scorer engages only when the resolved
    method is ``fused`` over a raw ``InvertedIndex`` base and declines
    (returns None → normal dispatch) otherwise.

    ``tag`` namespaces this corpus's entries inside a cache shared
    across tenants — invalidation is per-tag, so one tenant's churn
    never cold-starts another's entries.
    """

    def __init__(self, engine, *, result_cache: QueryResultCache,
                 hot_cache: Optional[HotPostingCache] = None,
                 tag: str = "corpus"):
        self.engine = engine
        self.results = result_cache
        self.hot = hot_cache
        self.tag = str(tag)
        self._seen_generation: Optional[int] = None

    # -- delegated mutations --------------------------------------------

    @property
    def builder(self):
        return self.engine.builder

    def add_docs(self, docs, ids=None):
        return self.engine.add_docs(docs, ids=ids)

    def remove_docs(self, ids):
        return self.engine.remove_docs(ids)

    def flush(self, **kw):
        return self.engine.flush(**kw)

    # -- search ----------------------------------------------------------

    def _hot_scorer(self):
        hot = self.hot

        def scorer(queries, base, k, resolved, kw):
            if resolved != "fused" or type(base) is not InvertedIndex:
                return None
            hot.ensure(base, self.builder.generation)
            return hot_fused_retrieve(queries, base, k, hot=hot, **kw)

        return scorer if hot is not None else None

    def search(self, queries: SparseRep, k: int = 10,
               **kw) -> Tuple[np.ndarray, np.ndarray]:
        """Row-cached top-k — same signature, same results as
        ``CorpusEngine.search`` (the hard parity invariant)."""
        b = self.builder
        if b.dirty:
            b.flush()
        gen = b.generation
        if gen != self._seen_generation:
            self.results.invalidate(self.tag, gen)
            self._seen_generation = gen

        rows = split_rows(queries)
        keys = [query_cache_key(r, k, kw, self.tag, gen) for r in rows]
        out_v: List[Optional[np.ndarray]] = [None] * len(rows)
        out_i: List[Optional[np.ndarray]] = [None] * len(rows)
        miss_rows, miss_pos = [], []
        for j, key in enumerate(keys):
            hit = self.results.get(key)
            if hit is not None:
                out_v[j], out_i[j] = hit
            else:
                miss_rows.append(rows[j])
                miss_pos.append(j)
        if miss_rows:
            mv, mi = b.search(stack_rows(miss_rows), k,
                              base_scorer=self._hot_scorer(), **kw)
            mv = np.asarray(mv)
            mi = np.asarray(mi)
            for r, j in enumerate(miss_pos):
                self.results.put(keys[j], self.tag, gen, mv[r], mi[r])
                out_v[j], out_i[j] = mv[r], mi[r]
        return np.stack(out_v), np.stack(out_i)

    # -- observability ---------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        d = {"tag": self.tag, "results": self.results.stats()}
        if self.hot is not None:
            d["hot"] = self.hot.stats()
        d["engine"] = self.engine.stats()
        return d
