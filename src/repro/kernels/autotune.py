"""Block-size autotuner for the Sparton Pallas kernels.

The v1 kernels hard-coded ``(8, 128, 128)`` blocks for every shape from
Splade-BERT (V≈30k) to XLM-R (V≈250k). Block choice governs both HBM
traffic and VMEM residency, and the best point moves with the shape:

* total HBM reads of the forward are
  ``|H| * V/block_v  +  |E| * B/block_b``
  (each H tile is re-fetched per vocab block; each E tile per batch
  block), so large-V shapes want the largest ``block_v`` that fits;
* VMEM must hold the double-buffered input tiles, the logit tile, the
  scratch accumulators — and, because the same blocks drive the
  backward, the ``(block_b, block_s, D)`` / ``(block_v, D)`` backward
  scratch accumulators too.

The kernels take rows in length order and skip the sequence tiles past
each row block's extent (``kernels/sparton.live_tiles``), which changes
the best point again: short sequence tiles let the skip bite, at the
cost of more grid steps. This module enumerates candidates under a VMEM
budget, ranks them by their distance from a rule timed on the chip
(``_preferred_blocks``; the analytic traffic model breaks ties), takes
the first (``heuristic_blocks``) or *times* the first few on the
traffic's own mask (``autotune_blocks`` — on a TPU the real kernel,
elsewhere the Pallas interpreter on a capped proxy shape), and persists
measured winners in a JSON cache keyed by ``(B, S, D, V, dtype,
backend)``.

``get_blocks`` is the cheap entry point used by the kernel wrappers
when no explicit blocks are passed: cache hit, else heuristic — never
a measurement (safe to call under ``jax.jit`` tracing).
"""

from __future__ import annotations

import json
import math
import os
import time
import warnings
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels._common import VMEM_LIMIT_BYTES, interpret_mode

Blocks = Tuple[int, int, int]  # (block_b, block_s, block_v)
ImpactBlocks = Tuple[int, int]  # (block_n, block_w)

# The three Pallas kernels with independently tunable blocks. One joint
# triple (the legacy scheme) leaves measurable wins on the table at
# large D: the dH kernel's VMEM is dominated by its (bb, bs, D) scratch
# while dE's is (bv, D), so their feasible/optimal regions differ.
KERNELS = ("fwd", "dh", "de")
# Fused impact-scoring kernel variants (kernels/impact_score.py): raw
# f32 windows vs in-kernel u4+delta dequant. Tuned separately from the
# head kernels — different block axes ((block_n, block_w), not a
# (bb, bs, bv) triple) and a different shape key ("_impact" suffix).
IMPACT_VARIANTS = ("f32", "u4")

CACHE_ENV = "SPARTON_AUTOTUNE_CACHE"
# Inside the checkout, never under $HOME: the blocks a run uses must not
# depend on state left outside the repository.
DEFAULT_CACHE = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".autotune", "autotune.json"))
# The model below over-counts what Mosaic allocates, so budgeting it
# against the limit the kernels are compiled under keeps every admitted
# candidate compilable.
VMEM_BUDGET_BYTES = VMEM_LIMIT_BYTES

# Mosaic tiling: a block's last two dims are multiples of (8, 128) or
# the whole array dim. block_b sits on the sublanes of the (B, V)
# tiles, block_s on the sublanes of the H and dH tiles (16 holds a
# packed bf16 tile), block_v on the lanes of the (B, V) tiles.
_BB_CHOICES = (8, 16, 32, 64)
_BS_CHOICES = (16, 32, 64, 128, 256, 512)
_BV_CHOICES = (128, 256, 512, 1024, 2048)

# The kernels take rows in length order and skip the sequence tiles
# past each row block's extent (kernels/sparton.live_tiles), so short
# sequence tiles pay for their extra grid steps. Timed on a TPU v5e at
# the benchmark cells' shapes with their length-ordered masks (PERF.md
# section 6): 32-position tiles (16 where the sequence is at most 32
# long) with these rows (block_b * block_s) and vocab tiles a step ran
# each kernel within 6% of the fastest block tried, in 0.42-0.50 of the
# time of the dense blocks chosen before at 256 positions, 0.67-0.83 at
# 32.
_SKIP_ROWS = {"fwd": 1024, "dh": 1024, "de": 512}
_SKIP_BV = {"fwd": 2048, "dh": 512, "de": 2048}
# block_s may instead be the whole sequence, padded to the bf16
# sublane tile, so short sequences need not pad to 128.
_S_ALIGN = 16
_IMPACT_BN_CHOICES = (128, 256, 512, 1024, 2048, 4096)
_IMPACT_BW_CHOICES = (128, 256, 512)

# Smallest enumerable triple — the overflow-*minimizing* fallback when
# no candidate fits the budget (a huge D can make even this overflow,
# but never by more than any other choice would).
MIN_BLOCKS: Blocks = (min(_BB_CHOICES), min(_BS_CHOICES),
                      min(_BV_CHOICES))
MIN_IMPACT_BLOCKS: ImpactBlocks = (min(_IMPACT_BN_CHOICES),
                                   min(_IMPACT_BW_CHOICES))

# One in-memory cache per JSON file: entries from distinct cache paths
# must never bleed into each other's saves.
_caches: Dict[str, Dict[str, dict]] = {}


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

def cache_path(path: Optional[str] = None) -> str:
    return path or os.environ.get(CACHE_ENV) or DEFAULT_CACHE


def shape_key(B: int, S: int, D: int, V: int, dtype, backend: str,
              kernel: Optional[str] = None) -> str:
    """Cache key for a shape — optionally extended per kernel.

    ``kernel=None`` is the legacy joint key (one triple for all three
    kernels); ``"fwd"``/``"dh"``/``"de"`` suffixes address per-kernel
    winners. Old cache files only hold joint keys and stay readable:
    per-kernel lookups fall back to the joint entry.
    """
    base = f"B{B}_S{S}_D{D}_V{V}_{jnp.dtype(dtype).name}_{backend}"
    return base if kernel is None else f"{base}_{kernel}"


def impact_shape_key(B: int, Q: int, L: int, N: int, variant: str,
                     backend: str) -> str:
    """Cache key for the fused impact-scoring kernel.

    Its shape space is (batch, query width, window length, corpus
    docs) — disjoint from the head kernels' (B, S, D, V) — and the
    ``_impact`` suffix keeps the two families from ever colliding in
    one cache file. ``variant`` is "f32" (raw windows) or "u4"
    (in-kernel dequant).
    """
    if variant not in IMPACT_VARIANTS:
        raise ValueError(f"unknown impact variant {variant!r}; "
                         f"one of {list(IMPACT_VARIANTS)}")
    return f"B{B}_Q{Q}_L{L}_N{N}_{variant}_{backend}_impact"


def _load(path: str) -> Dict[str, dict]:
    if path not in _caches:
        cache: Dict[str, dict] = {}
        try:
            with open(path) as f:
                cache.update(json.load(f))
        except (OSError, ValueError):
            pass
        _caches[path] = cache
    return _caches[path]


def _save(path: str) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    # Read-merge-write: another process (a second tuner on a shared
    # home dir, a parallel CI job) may have persisted winners since our
    # _load — merge them in rather than clobbering the file with our
    # stale view. Our own entries win per-key. Not a lock, but it
    # shrinks the lost-update window to a single key instead of the
    # whole file.
    merged: Dict[str, dict] = {}
    try:
        with open(path) as f:
            merged.update(json.load(f))
    except (OSError, ValueError):
        pass
    merged.update(_caches.get(path, {}))
    _caches[path] = merged
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(merged, f, indent=2, sort_keys=True)
    os.replace(tmp, path)


def clear_cache(path: Optional[str] = None, *, disk: bool = False) -> None:
    """Drop the in-memory caches (and optionally one JSON file)."""
    _caches.clear()
    if disk:
        try:
            os.remove(cache_path(path))
        except OSError:
            pass


# ---------------------------------------------------------------------------
# VMEM model + candidate enumeration
# ---------------------------------------------------------------------------

def _vmem_components(blocks: Blocks, D: int, dtype=jnp.float32
                     ) -> Dict[str, int]:
    """Per-kernel VMEM residency (double-buffered pipelined tiles,
    single-buffered scratch accumulators, in-register logit/one-hot
    tile)."""
    bb, bs, bv = blocks
    in_b = jnp.dtype(dtype).itemsize
    f32 = 4
    tile_bv = bb * bv * f32                      # dy/y/g/out (B, V) tiles
    fwd = (2 * (bb * bs * D * in_b + bv * D * in_b + bv * f32)
           + bb * bs * bv * f32                  # logit tile
           + 2 * 2 * tile_bv                     # y, i outputs
           + 2 * tile_bv)                        # max/argmax scratch
    dh = (2 * (3 * tile_bv + bv * D * in_b)
          + bb * bs * bv * f32                   # one-hot tile
          + bb * bs * D * f32                    # scratch accumulator
          + 2 * bb * bs * D * f32)               # output tile
    de = (2 * (3 * tile_bv + bb * bs * D * in_b)
          + bb * bs * bv * f32
          + bv * D * f32 + bv * f32              # scratch accumulators
          + 2 * (bv * D * f32 + bv * f32))       # output tiles
    return {"fwd": fwd, "dh": dh, "de": de}


def vmem_bytes(blocks: Blocks, D: int, dtype=jnp.float32,
               kernel: Optional[str] = None) -> int:
    """VMEM residency of one kernel, or the worst case over all three
    (``kernel=None`` — the budget a joint triple must satisfy)."""
    comps = _vmem_components(blocks, D, dtype)
    return comps[kernel] if kernel is not None else max(comps.values())


def hbm_traffic_elems(blocks: Blocks, B: int, S: int, D: int,
                      V: int, kernel: Optional[str] = None) -> float:
    """Analytic HBM read volume (elements) of one kernel's grid.

    Uses the *padded* array sizes — the kernels read whole tiles, so a
    block larger than the problem dim pays for the padding. This is
    what makes an oversized block rank strictly worse than a fitting
    one at equal grid counts (instead of winning the size tiebreak).
    Per kernel (from the grid layouts in ``sparton.py``/
    ``sparton_bwd.py``): the forward re-fetches H per vocab block and
    E per batch block; dH re-fetches the three (B, V) operands per
    sequence block and E per (batch, seq) block; dE re-fetches the
    (B, V) operands per sequence block and H per vocab block.
    """
    bb, bs, bv = blocks
    n_b = -(-B // bb)
    n_s = -(-S // bs)
    n_v = -(-V // bv)
    h_padded = float(n_b * bb) * (n_s * bs) * D
    e_padded = float(n_v * bv) * D
    if kernel in (None, "fwd"):
        return h_padded * n_v + e_padded * n_b
    y_padded = float(n_b * bb) * (n_v * bv)      # dy/y/i_max operands
    if kernel == "dh":
        return 3 * y_padded * n_s + e_padded * n_b * n_s
    if kernel == "de":
        return 3 * y_padded * n_s + h_padded * n_v
    raise ValueError(f"unknown kernel {kernel!r}; one of {KERNELS}")


Pinned = Tuple[Optional[int], Optional[int], Optional[int]]


def candidate_blocks(
    B: int, S: int, D: int, V: int,
    *,
    dtype=jnp.float32,
    vmem_budget: int = VMEM_BUDGET_BYTES,
    pinned: Optional[Pinned] = None,
    kernel: Optional[str] = None,
) -> List[Blocks]:
    """All (block_b, block_s, block_v) under the VMEM budget, least
    traffic first.

    Candidates keep Mosaic's tiling rules (block_b a multiple of 8;
    block_s a multiple of 16, the packed bf16 sublane tile, or the whole
    padded sequence; block_v a multiple of 128) and skip blocks grossly
    larger than the padded problem. Sorted by the analytic HBM-traffic
    model of a full mask, least traffic first (``ranked_blocks`` puts
    them in the order the block choice uses). ``pinned``
    components (from a config) are honored exactly — only the free
    components are enumerated, and the VMEM budget is checked on the
    *combined* triple. ``kernel`` scopes both the VMEM residency and
    the traffic model to one kernel (fwd/dh/de); None keeps the legacy
    joint behavior (worst-case VMEM, forward traffic).
    """
    pb, ps, pv = pinned or (None, None, None)
    whole_s = -(-S // _S_ALIGN) * _S_ALIGN
    bbs = (pb,) if pb is not None else _BB_CHOICES
    bss = (ps,) if ps is not None else tuple(sorted(
        {bs for bs in _BS_CHOICES if bs <= max(128, 2 * S)}
        | ({whole_s} if whole_s <= max(_BS_CHOICES) else set())))
    bvs = (pv,) if pv is not None else _BV_CHOICES
    out = []
    for bb in bbs:
        if pb is None and bb > max(8, B):
            continue
        for bs in bss:
            for bv in bvs:
                if pv is None and bv > max(128, 2 * V):
                    continue
                blocks = (bb, bs, bv)
                if vmem_bytes(blocks, D, dtype, kernel) > vmem_budget:
                    continue
                out.append(blocks)
    out.sort(key=lambda blk: (hbm_traffic_elems(blk, B, S, D, V, kernel),
                              -blk[0] * blk[1] * blk[2]))
    return out


def _preferred_blocks(S: int, kernel: Optional[str] = None) -> Blocks:
    """The measured rule on shapes: ``block_s`` 32, or 16 where ``S`` is
    at most 32, and the kernel's rows and vocab tile a step (the joint
    triple takes the forward's)."""
    bs = 16 if S <= 32 else 32
    kn = kernel or "fwd"
    return (_SKIP_ROWS[kn] // bs, bs, _SKIP_BV[kn])


def ranked_blocks(B: int, S: int, D: int, V: int,
                  *, dtype=jnp.float32,
                  vmem_budget: int = VMEM_BUDGET_BYTES,
                  pinned: Optional[Pinned] = None,
                  kernel: Optional[str] = None) -> List[Blocks]:
    """``candidate_blocks`` nearest ``_preferred_blocks`` first: its
    ``block_s`` first, then its rows a step, then its vocab tile, the
    traffic model breaking ties (it picks among blocks the shape or the
    VMEM budget leave when the preferred ones do not fit). The one
    order both ``heuristic_blocks`` and the timed tuners take."""
    cands = candidate_blocks(B, S, D, V, dtype=dtype,
                             vmem_budget=vmem_budget, pinned=pinned,
                             kernel=kernel)
    pb, ps, pv = _preferred_blocks(S, kernel)
    # sorted is stable: equals keep their traffic order
    return sorted(cands, key=lambda c: (
        c[1] != ps,
        abs(math.log2(c[0] * c[1] / (pb * ps))),
        abs(math.log2(c[2] / pv))))


def heuristic_blocks(B: int, S: int, D: int, V: int,
                     *, dtype=jnp.float32,
                     vmem_budget: int = VMEM_BUDGET_BYTES,
                     pinned: Optional[Pinned] = None,
                     kernel: Optional[str] = None) -> Blocks:
    """The first of ``ranked_blocks`` — no measurement.

    With pins, the free components shrink as needed to keep the
    combined triple under the budget; if no free choice fits (the pins
    alone overflow), the smallest free components are used so the
    overflow is at least minimal, not amplified.
    """
    cands = ranked_blocks(B, S, D, V, dtype=dtype,
                          vmem_budget=vmem_budget, pinned=pinned,
                          kernel=kernel)
    if cands:
        return cands[0]
    if pinned and any(p is not None for p in pinned):
        return tuple(p if p is not None else s
                     for p, s in zip(pinned, MIN_BLOCKS))  # type: ignore
    return MIN_BLOCKS


# ---------------------------------------------------------------------------
# lookup + measurement
# ---------------------------------------------------------------------------

def get_blocks(
    B: int, S: int, D: int, V: int,
    *,
    dtype=jnp.float32,
    backend: Optional[str] = None,
    path: Optional[str] = None,
    kernel: Optional[str] = None,
) -> Blocks:
    """Cached winner for the shape, else the analytic heuristic.

    Never measures — cheap enough to call on every kernel invocation
    (including under jit tracing, where it runs once per compilation).
    With ``kernel`` set, the lookup prefers the per-kernel entry and
    falls back to a legacy joint entry (old cache files stay usable),
    then to the kernel-scoped heuristic.
    """
    backend = backend or jax.default_backend()
    cache = _load(cache_path(path))
    hit = cache.get(shape_key(B, S, D, V, dtype, backend, kernel))
    if hit is None and kernel is not None:
        hit = cache.get(shape_key(B, S, D, V, dtype, backend))
    if hit is not None:
        return (hit["block_b"], hit["block_s"], hit["block_v"])
    return heuristic_blocks(B, S, D, V, dtype=dtype, kernel=kernel)


def _measure_shape(B: int, S: int, V: int,
                   interpret: bool) -> Tuple[int, int, int]:
    """Interpret mode executes the grid serially on the host — cap the
    proxy shape so a 250k-vocab tuning run stays seconds, not hours.
    The cache key still records the *real* shape."""
    if not interpret:
        return B, S, V
    return min(B, 8), min(S, 256), min(V, 2048)


def _tuning_mask(mask: Optional[jax.Array], B: int, S: int, mb: int,
                 ms: int) -> jax.Array:
    """The mask the tuners time: the traffic's own ``(B, S)`` mask (a
    full one where none is given) cut to the measured shape, its rows in
    length order as ``kernels/ops.py`` takes them."""
    from repro.kernels.sparton import row_extents

    if mask is None:
        return jnp.ones((mb, ms), jnp.int32)
    if tuple(mask.shape) != (B, S):
        raise ValueError(f"mask shape {tuple(mask.shape)} is not the "
                         f"tuned shape {(B, S)}")
    mask = jnp.asarray(mask, jnp.int32)[:mb, :ms]
    return mask[jnp.argsort(-row_extents(mask), stable=True)]


def _time_ms(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(times))


def autotune_blocks(
    B: int, S: int, D: int, V: int,
    *,
    dtype=jnp.float32,
    backend: Optional[str] = None,
    softcap: Optional[float] = None,
    interpret: Optional[bool] = None,
    max_candidates: int = 8,
    include_backward: bool = True,
    path: Optional[str] = None,
    vmem_budget: int = VMEM_BUDGET_BYTES,
    mask: Optional[jax.Array] = None,
) -> Blocks:
    """Time block candidates for the shape, persist and return the winner.

    On a TPU the real Mosaic kernels are timed at the real shape; on
    CPU/GPU hosts (``interpret`` defaults to True there) the Pallas
    interpreter is timed on a capped proxy shape — a rough but
    deterministic ordering that keeps CI and laptops tune-able. The
    first ``max_candidates`` of ``ranked_blocks`` are timed on ``mask``,
    a ``(B, S)`` keep mask of the traffic to tune for (None: a full
    mask), so the skipped tiles count as they will in the run.
    """
    from repro.kernels.ops import sparton_head
    from repro.kernels.sparton import sparton_forward

    backend = backend or jax.default_backend()
    if interpret is None:
        interpret = interpret_mode()
    p = cache_path(path)
    cache = _load(p)
    key = shape_key(B, S, D, V, dtype, backend)
    hit = cache.get(key)
    if hit is not None and hit.get("source") == "measured":
        return (hit["block_b"], hit["block_s"], hit["block_v"])

    cands = ranked_blocks(B, S, D, V, dtype=dtype,
                          vmem_budget=vmem_budget)[:max_candidates]
    if not cands:
        cands = [MIN_BLOCKS]

    mb, ms, mv = _measure_shape(B, S, V, interpret)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    H = jax.random.normal(ks[0], (mb, ms, D), dtype)
    E = jax.random.normal(ks[1], (mv, D), dtype) * 0.2
    bias = jax.random.normal(ks[2], (mv,), jnp.float32) * 0.2
    mask = _tuning_mask(mask, B, S, mb, ms)

    best: Tuple[float, Blocks] = (float("inf"), cands[0])
    last_error: Optional[Exception] = None
    for blocks in cands:
        bb, bs, bv = blocks

        def fwd(H, E, bias, mask):
            y, _ = sparton_forward(
                H, E, bias, mask, block_b=bb, block_s=bs, block_v=bv,
                softcap=softcap, interpret=interpret)
            return y

        fn = fwd
        if include_backward:
            def fwd_bwd(H, E, bias, mask, _blk=blocks):
                def loss(H, E, bias):
                    y = sparton_head(
                        H, E, bias, mask, block_b=_blk[0],
                        block_s=_blk[1], block_v=_blk[2],
                        logit_softcap=softcap, interpret=interpret)
                    return jnp.sum(y * y)
                return jax.grad(loss, argnums=(0, 1, 2))(H, E, bias)
            fn = fwd_bwd
        try:
            t = _time_ms(fn, H, E, bias, mask)
        except Exception as e:   # candidate not lowerable on this backend
            last_error = e
            continue
        if t < best[0]:
            best = (t, blocks)

    t, blocks = best
    if t == float("inf"):
        # Every candidate failed to time (e.g. none lowered on this
        # backend): fall back to the heuristic and persist NOTHING, so
        # a later call — possibly in a healthier environment — retries
        # instead of serving a never-validated winner forever. Surface
        # the last error — a systematic kernel bug must not degrade
        # silently into "tuned" blocks.
        warnings.warn(
            f"sparton autotune: all {len(cands)} block candidates "
            f"failed to time for {key}; returning untimed heuristic "
            f"blocks. Last error: {last_error!r}")
        return heuristic_blocks(B, S, D, V, dtype=dtype,
                                vmem_budget=vmem_budget)
    cache[key] = {
        "block_b": blocks[0], "block_s": blocks[1], "block_v": blocks[2],
        "ms": round(t, 3),
        "source": "measured",
        "measured_shape": list(_measure_shape(B, S, V, interpret)) + [D],
        "interpret": bool(interpret),
    }
    _save(p)
    return blocks


def autotune_kernel_blocks(
    B: int, S: int, D: int, V: int,
    *,
    dtype=jnp.float32,
    backend: Optional[str] = None,
    softcap: Optional[float] = None,
    interpret: Optional[bool] = None,
    max_candidates: int = 8,
    path: Optional[str] = None,
    vmem_budget: int = VMEM_BUDGET_BYTES,
    mask: Optional[jax.Array] = None,
) -> Dict[str, Blocks]:
    """Time block candidates **per kernel** (fwd, dH, dE), persist and
    return ``{kernel: winner}``.

    The joint tuner (``autotune_blocks``) times fwd+bwd with one triple
    — convenient, but at large D the dH and dE kernels want different
    blocks (their VMEM is dominated by different scratch shapes). This
    tuner times each kernel in isolation on its own candidate set and
    writes one cache entry per kernel (``<shape>_fwd`` etc.); the
    wrappers' per-kernel lookups pick them up, and old joint entries
    remain readable as the fallback. Candidates and ``mask`` as in
    ``autotune_blocks``.
    """
    from repro.kernels.sparton import row_extents, sparton_forward
    from repro.kernels.sparton_bwd import (sparton_backward_de,
                                           sparton_backward_dh)

    backend = backend or jax.default_backend()
    if interpret is None:
        interpret = interpret_mode()
    p = cache_path(path)
    cache = _load(p)
    keys = {kn: shape_key(B, S, D, V, dtype, backend, kn)
            for kn in KERNELS}
    hits = {kn: cache.get(k) for kn, k in keys.items()}
    if all(h is not None and h.get("source") == "measured"
           for h in hits.values()):
        return {kn: (h["block_b"], h["block_s"], h["block_v"])
                for kn, h in hits.items()}

    mb, ms, mv = _measure_shape(B, S, V, interpret)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    H = jax.random.normal(ks[0], (mb, ms, D), dtype)
    E = jax.random.normal(ks[1], (mv, D), dtype) * 0.2
    bias = jax.random.normal(ks[2], (mv,), jnp.float32) * 0.2
    mask = _tuning_mask(mask, B, S, mb, ms)
    extents = row_extents(mask)
    # one forward at heuristic blocks supplies the backward operands
    fwd_heur = heuristic_blocks(mb, ms, D, mv, dtype=dtype,
                                vmem_budget=vmem_budget, kernel="fwd")
    y, i_max = sparton_forward(
        H, E, bias, mask, block_b=fwd_heur[0], block_s=fwd_heur[1],
        block_v=fwd_heur[2], softcap=softcap, interpret=interpret)
    dy = jax.random.normal(ks[3], y.shape, jnp.float32)

    def fwd_fn(blocks):
        bb, bs, bv = blocks
        return lambda: sparton_forward(
            H, E, bias, mask, block_b=bb, block_s=bs, block_v=bv,
            softcap=softcap, interpret=interpret)

    def dh_fn(blocks):
        bb, bs, bv = blocks
        return lambda: sparton_backward_dh(
            dy, y, i_max, E, ms, extents, block_b=bb, block_s=bs,
            block_v=bv, softcap=softcap, interpret=interpret)

    def de_fn(blocks):
        bb, bs, bv = blocks
        return lambda: sparton_backward_de(
            dy, y, i_max, H, extents, block_b=bb, block_s=bs, block_v=bv,
            softcap=softcap, interpret=interpret)

    builders = {"fwd": fwd_fn, "dh": dh_fn, "de": de_fn}
    winners: Dict[str, Blocks] = {}
    measured_any = False
    for kn in KERNELS:
        hit = hits[kn]
        if hit is not None and hit.get("source") == "measured":
            winners[kn] = (hit["block_b"], hit["block_s"],
                           hit["block_v"])
            continue
        cands = ranked_blocks(B, S, D, V, dtype=dtype,
                              vmem_budget=vmem_budget,
                              kernel=kn)[:max_candidates]
        if not cands:
            cands = [MIN_BLOCKS]
        best: Tuple[float, Blocks] = (float("inf"), cands[0])
        last_error: Optional[Exception] = None
        for blocks in cands:
            try:
                t = _time_ms(builders[kn](blocks))
            except Exception as e:  # candidate not lowerable here
                last_error = e
                continue
            if t < best[0]:
                best = (t, blocks)
        t, blocks = best
        if t == float("inf"):
            # same policy as the joint tuner: heuristic, persist
            # nothing, surface the failure
            warnings.warn(
                f"sparton autotune[{kn}]: all {len(cands)} candidates "
                f"failed to time for {keys[kn]}; returning untimed "
                f"heuristic blocks. Last error: {last_error!r}")
            winners[kn] = heuristic_blocks(B, S, D, V, dtype=dtype,
                                           vmem_budget=vmem_budget,
                                           kernel=kn)
            continue
        cache[keys[kn]] = {
            "block_b": blocks[0], "block_s": blocks[1],
            "block_v": blocks[2],
            "ms": round(t, 3),
            "source": "measured",
            "kernel": kn,
            "measured_shape": list(_measure_shape(B, S, V, interpret))
            + [D],
            "interpret": bool(interpret),
        }
        winners[kn] = blocks
        measured_any = True
    if measured_any:
        _save(p)
    return winners


def resolve_blocks(
    B: int, S: int, D: int, V: int, dtype,
    block_b: Optional[int], block_s: Optional[int],
    block_v: Optional[int],
    *,
    kernel: Optional[str] = None,
) -> Blocks:
    """Fill the None components of a user-supplied block triple. Shared
    by every kernel wrapper so forward and backward resolve identically
    for the same inputs.

    Fully unset triples take the cached winner (or heuristic). Partial
    pins are re-enumerated *jointly* with the pins fixed — grafting a
    pin onto a triple tuned without it could blow the VMEM budget —
    which also means they bypass the winner cache on purpose.
    ``kernel`` ("fwd"/"dh"/"de") scopes cache lookup, VMEM model and
    traffic ranking to that kernel; None keeps the joint behavior.
    """
    if block_b is not None and block_s is not None and block_v is not None:
        return (block_b, block_s, block_v)
    if block_b is None and block_s is None and block_v is None:
        return get_blocks(B, S, D, V, dtype=dtype, kernel=kernel)
    return heuristic_blocks(B, S, D, V, dtype=dtype,
                            pinned=(block_b, block_s, block_v),
                            kernel=kernel)


def blocks_for_config(vocab_size: int, d_model: int, batch: int,
                      seq_len: int, dtype: str = "float32",
                      pinned: Optional[Pinned] = None) -> Blocks:
    """Config-level convenience: cached/heuristic blocks for a model
    operating point (used by configs + launch to stop hard-coding).

    Partially pinned configs bypass the winner cache (the cached triple
    was tuned without the pin) and re-enumerate with the pins fixed so
    the combined triple still respects the VMEM budget. No memoization
    beyond the autotune cache itself — a winner persisted later in the
    process must be visible to the next call.
    """
    if pinned is not None and any(p is not None for p in pinned):
        return heuristic_blocks(batch, seq_len, d_model, vocab_size,
                                dtype=jnp.dtype(dtype), pinned=pinned)
    return get_blocks(batch, seq_len, d_model, vocab_size,
                      dtype=jnp.dtype(dtype))


# ---------------------------------------------------------------------------
# fused impact-scoring kernel (kernels/impact_score.py)
# ---------------------------------------------------------------------------

def impact_vmem_bytes(blocks: ImpactBlocks, Q: int, L: int,
                      variant: str = "f32") -> int:
    """VMEM residency of one fused impact grid step.

    The posting window stays resident across every doc tile of a query
    (same block index -> no re-fetch, but Pallas still double-buffers
    it); each chunk builds a (block_n, block_w) one-hot from an iota of
    the same shape and a compare; the running top-k selection carries a
    few (1, block_n) rows. Per variant: "f32" ships two (W/bw, bw)
    windows (f32 weights + i32 docs); "u4" ships two (Q, L) i32
    windows plus five (Q, 1) per-term columns, padded to 128 lanes, and
    decodes into two (Q, L) scratch planes, with decode temporaries.
    """
    bn, bw = blocks
    f32 = 4
    w_lanes = Q * max(L, 1)
    resident = 2 * 2 * w_lanes * f32               # two windows, dbl-buf
    if variant != "f32":
        resident += (2 * 5 * Q * 128 * f32         # per-term columns
                     + 8 * w_lanes * f32)          # decoded planes + temps
    onehot = 3 * bw * bn * f32                     # one-hot, iota, compare
    merge = 6 * bn * f32                           # selection carries
    return resident + onehot + merge


def impact_traffic_proxy(blocks: ImpactBlocks, B: int, Q: int, L: int,
                         N: int) -> float:
    """Analytic cost proxy ranking impact-block candidates.

    HBM traffic is nearly block-independent here (the window loads once
    per query; outputs are (B, k)), so the ranking term is the serial
    merge work: each doc tile pays one union-top-k of ~(k + block_n)
    lanes, and each chunk pays fixed MXU issue overhead — so fewer,
    larger tiles and chunks win until VMEM says stop. The padded tile
    and chunk remainders are charged in full, which is what stops an
    oversized block from winning on tile count alone.
    """
    bn, bw = blocks
    n_tiles = -(-N // bn)
    n_chunks = -(-(Q * max(L, 1)) // bw)
    k_est = 128.0  # merge working set is k+bn lanes; k is unknown here
    merge_cost = n_tiles * (k_est + bn)
    chunk_cost = n_tiles * n_chunks * (64.0 + bw * bn / 8192.0)
    return float(B) * (merge_cost + chunk_cost)


def impact_candidate_blocks(
    B: int, Q: int, L: int, N: int,
    *,
    variant: str = "f32",
    vmem_budget: int = VMEM_BUDGET_BYTES,
) -> List[ImpactBlocks]:
    """All (block_n, block_w) under the VMEM budget, best first."""
    out = []
    w_lanes = Q * max(L, 1)
    for bn in _IMPACT_BN_CHOICES:
        if bn > max(128, 2 * N):
            continue
        for bw in _IMPACT_BW_CHOICES:
            if bw > max(128, 2 * w_lanes):
                continue
            blocks = (bn, bw)
            if impact_vmem_bytes(blocks, Q, L, variant) > vmem_budget:
                continue
            out.append(blocks)
    out.sort(key=lambda blk: (impact_traffic_proxy(blk, B, Q, L, N),
                              -blk[0] * blk[1]))
    return out


def heuristic_impact_blocks(B: int, Q: int, L: int, N: int,
                            *, variant: str = "f32",
                            vmem_budget: int = VMEM_BUDGET_BYTES
                            ) -> ImpactBlocks:
    """Best impact candidate by the analytic model — no measurement."""
    cands = impact_candidate_blocks(B, Q, L, N, variant=variant,
                                    vmem_budget=vmem_budget)
    return cands[0] if cands else MIN_IMPACT_BLOCKS


def get_impact_blocks(
    B: int, Q: int, L: int, N: int,
    *,
    variant: str = "f32",
    backend: Optional[str] = None,
    path: Optional[str] = None,
) -> ImpactBlocks:
    """Cached impact-kernel winner for the shape, else the heuristic.

    Same contract as ``get_blocks``: never measures, safe under jit
    tracing. There is no joint-key fallback — the ``_impact`` family
    is new, so a miss goes straight to the heuristic.
    """
    backend = backend or jax.default_backend()
    cache = _load(cache_path(path))
    hit = cache.get(impact_shape_key(B, Q, L, N, variant, backend))
    if hit is not None:
        return (hit["block_n"], hit["block_w"])
    return heuristic_impact_blocks(B, Q, L, N, variant=variant)


def resolve_impact_blocks(
    B: int, Q: int, L: int, N: int,
    block_n: Optional[int], block_w: Optional[int],
    *,
    variant: str = "f32",
) -> ImpactBlocks:
    """Fill the None components of a (block_n, block_w) pair — the
    impact-kernel analogue of ``resolve_blocks``. Partial pins are
    re-enumerated with the pin fixed (bypassing the winner cache, which
    was tuned without it)."""
    if block_n is not None and block_w is not None:
        return (block_n, block_w)
    if block_n is None and block_w is None:
        return get_impact_blocks(B, Q, L, N, variant=variant)
    cands = [blk for blk in impact_candidate_blocks(B, Q, L, N,
                                                    variant=variant)
             if (block_n is None or blk[0] == block_n)
             and (block_w is None or blk[1] == block_w)]
    if cands:
        return cands[0]
    return (block_n or MIN_IMPACT_BLOCKS[0],
            block_w or MIN_IMPACT_BLOCKS[1])


def autotune_impact_blocks(
    B: int, Q: int, L: int, N: int,
    *,
    variant: str = "f32",
    backend: Optional[str] = None,
    interpret: Optional[bool] = None,
    max_candidates: int = 6,
    k: int = 100,
    path: Optional[str] = None,
    vmem_budget: int = VMEM_BUDGET_BYTES,
) -> ImpactBlocks:
    """Time impact-block candidates, persist and return the winner.

    Mirrors ``autotune_blocks``: real kernel at the real shape on a
    TPU, Pallas interpreter on a capped proxy shape elsewhere (the key
    still records the real shape), and the all-candidates-failed path
    returns the untimed heuristic without persisting anything.
    """
    from repro.kernels.impact_score import (fused_impact_topk,
                                            fused_quantized_topk)

    backend = backend or jax.default_backend()
    if interpret is None:
        interpret = interpret_mode()
    p = cache_path(path)
    cache = _load(p)
    key = impact_shape_key(B, Q, L, N, variant, backend)
    hit = cache.get(key)
    if hit is not None and hit.get("source") == "measured":
        return (hit["block_n"], hit["block_w"])

    cands = impact_candidate_blocks(B, Q, L, N, variant=variant,
                                    vmem_budget=vmem_budget
                                    )[:max_candidates]
    if not cands:
        cands = [MIN_IMPACT_BLOCKS]

    mb, mq, ml, mn = ((min(B, 4), min(Q, 16), min(L, 256),
                       min(N, 4096)) if interpret else (B, Q, L, N))
    rng = np.random.default_rng(0)
    if variant == "f32":
        w = jnp.asarray(rng.uniform(0, 2, (mb, mq * ml)), jnp.float32)
        d = jnp.asarray(rng.integers(0, mn, (mb, mq * ml)), jnp.int32)

        def run(blocks):
            bn, bw = blocks
            return lambda: fused_impact_topk(
                w, d, n_docs=mn, k=min(k, mn), block_n=bn, block_w=bw,
                interpret=interpret)
    else:
        byte = jnp.asarray(rng.integers(0, 256, (mb, mq, ml)), jnp.int32)
        gap = jnp.asarray(rng.integers(0, 3, (mb, mq, ml)), jnp.int32)
        starts = jnp.asarray(rng.integers(0, 2, (mb, mq)), jnp.int32)
        lens = jnp.full((mb, mq), ml, jnp.int32)
        qv = jnp.asarray(rng.uniform(0.1, 2, (mb, mq)), jnp.float32)
        lo = jnp.zeros((mb, mq), jnp.float32)
        step = jnp.full((mb, mq), 0.1, jnp.float32)

        def run(blocks):
            bn, bw = blocks
            return lambda: fused_quantized_topk(
                byte, gap, starts, lens, qv, lo, step, n_docs=mn,
                k=min(k, mn), block_n=bn, block_w=bw,
                interpret=interpret)

    best: Tuple[float, ImpactBlocks] = (float("inf"), cands[0])
    last_error: Optional[Exception] = None
    for blocks in cands:
        try:
            t = _time_ms(run(blocks))
        except Exception as e:   # candidate not lowerable here
            last_error = e
            continue
        if t < best[0]:
            best = (t, blocks)
    t, blocks = best
    if t == float("inf"):
        warnings.warn(
            f"sparton autotune[impact/{variant}]: all {len(cands)} "
            f"candidates failed to time for {key}; returning untimed "
            f"heuristic blocks. Last error: {last_error!r}")
        return heuristic_impact_blocks(B, Q, L, N, variant=variant,
                                       vmem_budget=vmem_budget)
    cache[key] = {
        "block_n": blocks[0], "block_w": blocks[1],
        "ms": round(t, 3),
        "source": "measured",
        "kernel": "impact",
        "variant": variant,
        "measured_shape": [mb, mq, ml, mn],
        "interpret": bool(interpret),
    }
    _save(p)
    return blocks
