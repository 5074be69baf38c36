"""Doc-sharded inverted index over a device mesh (DESIGN.md §8.3).

The single-device index caps the corpus at one HBM's worth of
postings. Sharding the *documents* (not the vocabulary) keeps every
shard a self-contained inverted index over a contiguous doc range —
each device scores its local range with the unchanged impact scorer,
then the per-shard winners are merged with the same all_gather +
re-top-k reduction ``launch/steps.build_retrieval_step`` already uses
for dense candidate sharding. Corpus size scales with device count;
the (B, N) score matrix never exists anywhere.

Layout: the per-shard CSC arrays are stacked on a leading shard axis
(padded to the widest shard) —

    term_starts  (S, V) i32      postings_doc (S, Pmax) i32
    term_lens    (S, V) i32      postings_val (S, Pmax) f32
    shard_counts (S,)   i32      — real docs per shard

Shard ``s`` holds docs ``[s*docs_per_shard, ...)`` in original order,
so ``global id = s * docs_per_shard + local id`` and tie-breaks match
the unsharded scorer exactly (per-shard top-k is stable, shards are
gathered in ascending order).

Two execution paths with identical semantics:

* ``mesh`` given — ``shard_map`` over the shard axis: one shard per
  device, cross-shard merge via ``all_gather``. ``n_shards`` must
  equal the mesh axis size.
* ``mesh=None`` — a ``vmap`` over the shard axis on one device: the
  functional fallback used by tests, CPU benches, and single-device
  serving (sharding is then a partition of work, not of memory).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.retrieval.index import InvertedIndex, build_inverted_index
from repro.retrieval.sparse_rep import SparseRep

Array = jax.Array
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# shared mesh plumbing (doc- and term-sharded paths)
# ---------------------------------------------------------------------------

def resolve_mesh_axes(mesh, axis_names, sizes: Tuple[int, ...],
                      what: str = "sharded_retrieve"
                      ) -> Tuple[str, ...]:
    """Default + validate the mesh axes the logical shard dims map
    onto: one shard per device along each axis, so each axis size must
    equal the corresponding shard count. ``axis_names=None`` takes the
    mesh's leading axes in order (the 1D indexes use its first axis,
    the 2D grid its first two)."""
    if axis_names is None:
        if len(mesh.axis_names) < len(sizes):
            raise ValueError(
                f"{what}: mesh has {len(mesh.axis_names)} axes "
                f"{tuple(mesh.axis_names)}, needs {len(sizes)}")
        axis_names = tuple(mesh.axis_names[:len(sizes)])
    else:
        axis_names = tuple(axis_names)
        if len(axis_names) != len(sizes):
            raise ValueError(
                f"{what}: {len(axis_names)} axis names for "
                f"{len(sizes)} shard dims")
    for name, n_shards in zip(axis_names, sizes):
        n_dev = mesh.shape[name]
        if n_dev != n_shards:
            raise ValueError(
                f"{what}: n_shards={n_shards} must equal "
                f"mesh axis {name!r} size {n_dev}")
    return axis_names


def resolve_shard_axis(mesh, axis_name: Optional[str], n_shards: int,
                       what: str = "sharded_retrieve") -> str:
    """1D special case of ``resolve_mesh_axes``: the single mesh axis
    the shard dimension maps onto."""
    names = None if axis_name is None else (axis_name,)
    return resolve_mesh_axes(mesh, names, (n_shards,), what)[0]


def shard_mapped(body, mesh, axis_name: Optional[str], n_in: int,
                 n_out: int = 2, in_specs=None):
    """``jax.shard_map`` wrapper shared by the sharded indexes:
    the first ``n_in`` args are split on ``axis_name`` (one shard per
    device), outputs are replicated. The 2D grid passes explicit
    ``in_specs`` instead (its stacked arrays split on two mesh axes at
    once, its range/chunk arrays on one each). ``check_vma`` is off —
    the post-merge results (all_gather+top_k or psum) ARE replicated
    but the vma/rep tracer cannot prove it, same situation as
    ``build_retrieval_step``."""
    from jax.sharding import PartitionSpec as P

    if in_specs is None:
        in_specs = tuple(P(axis_name) for _ in range(n_in))
    else:
        in_specs = tuple(in_specs)
        if len(in_specs) != n_in:
            raise ValueError(
                f"shard_mapped: {len(in_specs)} in_specs for "
                f"{n_in} inputs")

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=in_specs,
        out_specs=tuple(P() for _ in range(n_out)),
        check_vma=False,
    )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ShardedIndex:
    term_starts: Array      # (S, V) i32
    term_lens: Array        # (S, V) i32
    postings_doc: Array     # (S, Pmax) i32 — local doc ids
    postings_val: Array     # (S, Pmax) f32
    shard_counts: Array     # (S,) i32 — real docs per shard
    n_shards: int           # static
    docs_per_shard: int     # static — uniform shard stride
    n_docs: int             # static — total real docs
    vocab_size: int         # static
    max_postings: int       # static — longest list over all shards

    def tree_flatten(self):
        children = (self.term_starts, self.term_lens,
                    self.postings_doc, self.postings_val,
                    self.shard_counts)
        aux = (self.n_shards, self.docs_per_shard, self.n_docs,
               self.vocab_size, self.max_postings)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    def memory_bytes(self) -> int:
        return int(sum(np.asarray(a).nbytes for a in (
            self.term_starts, self.term_lens,
            self.postings_doc, self.postings_val, self.shard_counts)))

    def stats(self) -> Dict[str, float]:
        return {
            "n_shards": self.n_shards,
            "docs_per_shard": self.docs_per_shard,
            "n_docs": self.n_docs,
            "vocab_size": self.vocab_size,
            "max_postings": self.max_postings,
            "memory_bytes": self.memory_bytes(),
        }


def shard_index(reps: SparseRep, vocab_size: int, n_shards: int
                ) -> ShardedIndex:
    """Build per-shard indexes over contiguous doc chunks (host-side).

    Docs are split into ``n_shards`` contiguous ranges of
    ``ceil(N / n_shards)``; each range is indexed independently with
    local doc ids and the CSC arrays are padded to the widest shard so
    the stacked layout is rectangular.
    """
    from repro.retrieval.sparse_rep import device_get

    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    host = device_get(reps) if isinstance(reps.values, jax.Array) else reps
    k = host.width
    v = np.asarray(host.values, np.float32).reshape(-1, k)
    i = np.asarray(host.indices, np.int32).reshape(-1, k)
    n = np.asarray(host.nnz, np.int32).reshape(-1)
    n_docs = v.shape[0]
    if n_shards > n_docs:
        raise ValueError(
            f"n_shards={n_shards} exceeds corpus size {n_docs}")
    dps = -(-n_docs // n_shards)

    parts = []
    for s in range(n_shards):
        lo, hi = s * dps, min((s + 1) * dps, n_docs)
        parts.append(build_inverted_index(
            SparseRep(v[lo:hi], i[lo:hi], n[lo:hi]), vocab_size,
            with_upper_bounds=False, stopword_warn_frac=1.1))

    p_max = max(p.n_postings for p in parts)
    starts = np.stack([np.asarray(p.term_starts) for p in parts])
    lens = np.stack([np.asarray(p.term_lens) for p in parts])
    pdoc = np.zeros((n_shards, p_max), np.int32)
    pval = np.zeros((n_shards, p_max), np.float32)
    for s, p in enumerate(parts):
        pdoc[s, :p.n_postings] = np.asarray(p.postings_doc)
        pval[s, :p.n_postings] = np.asarray(p.postings_val)
    counts = np.asarray(
        [min((s + 1) * dps, n_docs) - s * dps for s in range(n_shards)],
        np.int32)

    return ShardedIndex(
        term_starts=jnp.asarray(starts),
        term_lens=jnp.asarray(lens),
        postings_doc=jnp.asarray(pdoc),
        postings_val=jnp.asarray(pval),
        shard_counts=jnp.asarray(counts),
        n_shards=n_shards,
        docs_per_shard=dps,
        n_docs=n_docs,
        vocab_size=vocab_size,
        max_postings=max(p.max_postings for p in parts),
    )


def _local_scores(qv: Array, qi: Array, starts: Array, lens: Array,
                  pdoc: Array, pval: Array, count: Array,
                  index: ShardedIndex) -> Array:
    """(B, docs_per_shard) exact scores of one shard; padded docs
    (local id >= count) are masked to -inf."""
    from repro.retrieval.score import impact_scores

    local = InvertedIndex(
        term_starts=starts, term_lens=lens,
        postings_doc=pdoc, postings_val=pval,
        n_docs=index.docs_per_shard, vocab_size=index.vocab_size,
        max_postings=index.max_postings)
    scores = impact_scores(SparseRep(qv, qi, jnp.sum(
        (qv > 0).astype(jnp.int32), axis=-1)), local)
    doc_ids = jnp.arange(index.docs_per_shard, dtype=jnp.int32)
    return jnp.where(doc_ids[None, :] < count, scores, NEG_INF)


@functools.partial(jax.jit, static_argnames=("k",))
def _vmap_retrieve(qv: Array, qi: Array, index: ShardedIndex, k: int
                   ) -> Tuple[Array, Array]:
    """Single-device path: all shards scored under one jitted vmap.

    Shard chunks are contiguous, so the flattened (S * dps) position
    of a doc IS its original id — no offset bookkeeping needed."""
    scores = jax.vmap(
        lambda st, ln, pd, pv, ct: _local_scores(
            qv, qi, st, ln, pd, pv, ct, index)
    )(index.term_starts, index.term_lens, index.postings_doc,
      index.postings_val, index.shard_counts)          # (S, B, dps)
    flat = jnp.moveaxis(scores, 0, 1).reshape(qv.shape[0], -1)
    vals, idx = jax.lax.top_k(flat, k)
    return vals, idx.astype(jnp.int32)


def sharded_retrieve(
    queries: SparseRep,
    index: ShardedIndex,
    k: int = 10,
    *,
    mesh=None,
    axis_name: Optional[str] = None,
) -> Tuple[Array, Array]:
    """Top-k over the sharded index; ids are global (original) doc ids.

    With ``mesh`` the shard axis runs under ``shard_map`` (one shard
    per device along ``axis_name``, default: the mesh's first axis);
    without, a single-device ``vmap`` computes the same thing.
    """
    k = min(k, index.n_docs)
    dps = index.docs_per_shard
    qv = queries.values.reshape(-1, queries.width).astype(jnp.float32)
    qi = queries.indices.reshape(-1, queries.width)

    if mesh is None:
        return _vmap_retrieve(qv, qi, index, k)

    axis_name = resolve_shard_axis(mesh, axis_name, index.n_shards)
    kk = min(k, dps)

    def body(st, ln, pd, pv, ct):
        scores = _local_scores(qv, qi, st[0], ln[0], pd[0], pv[0],
                               ct[0], index)           # (B, dps)
        lv, li = jax.lax.top_k(scores, kk)
        li = li + jax.lax.axis_index(axis_name) * dps  # -> global ids
        all_v = jax.lax.all_gather(lv, axis_name, axis=1, tiled=True)
        all_i = jax.lax.all_gather(li, axis_name, axis=1, tiled=True)
        mv, pos = jax.lax.top_k(all_v, k)
        return mv, jnp.take_along_axis(all_i, pos, axis=1)

    merged = shard_mapped(body, mesh, axis_name, n_in=5)
    vals, idx = merged(index.term_starts, index.term_lens,
                       index.postings_doc, index.postings_val,
                       index.shard_counts)
    return vals, idx.astype(jnp.int32)
