"""Sparton fused LM-head forward v2 — Pallas TPU kernel.

One kernel fuses: tiled GEMM (``H @ E^T``), bias add, optional
gemma-2-style logit soft-capping, attention masking, streaming max
reduction over the sequence dimension (with argmax tracking), and the
final ``log1p(relu(.))`` epilogue. The full ``(B, S, V)`` logit tensor
is never materialized — per grid step only a ``(block_b*block_s,
block_v)`` logit tile lives in VMEM.

v2 over v1 (DESIGN.md §"Kernel v2"):

* The running ``(block_b, block_v)`` max/argmax live in **VMEM
  scratch** (``scratch_shapes``) across sequence steps; the ``(B, V)``
  output tiles are written to HBM exactly once, at the finalize step.
  v1 accumulated through the output refs, leaving the write-back/
  re-fetch decision to the pipeline; v2 makes the single-store
  guarantee structural.
* ``dimension_semantics=("parallel", "parallel", "arbitrary")`` tells
  Mosaic the batch/vocab grid dims carry no cross-step state, so they
  can split across the two TensorCores of a megacore chip; only the
  sequence dim is ordered (it owns the scratch accumulator).
* bf16 ``H``/``E`` tiles feed the MXU directly (no upcast in VMEM);
  accumulation is always f32 via ``preferred_element_type``.

Grid layout: ``(B/bb, V/bv, S/bs)`` with the sequence dimension
innermost, so each ``(b, v)`` tile's accumulator is live for exactly
one scratch lifetime (deterministic, no atomics).

Sequence tiles past a row block's extent hold only padding. A table of
live tiles per row block (``live_tiles``), scalar-prefetched, lets the
kernel skip them and clamps their fetches to a block already held
(DESIGN.md §5, "Length-ordered rows and the extent table"); the head
takes its rows in length order (``kernels/ops.py``) so that few rows
share a block with a much longer one.

VMEM working set per step:
    H tile   bb*bs*D        (input dtype)
    E tile   bv*D           (input dtype)
    logits   bb*bs*bv       f32 (register/VMEM temporary)
    scratch  2 * bb*bv      f32/i32 (running max / argmax)
    y, i     2 * bb*bv      f32/i32 (output tiles)
Block selection is shape-dependent — see ``kernels/autotune.py``; the
(8, 16, 128) fallback keeps this under ~1 MB at D=4096.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._common import NEG_INF, compiler_params, pad_to


def row_extents(mask: jax.Array) -> jax.Array:
    """Each row's extent: its last real position + 1, 0 for a row with
    no real position. ``mask`` is ``(B, S)``, nonzero = keep."""
    S = mask.shape[1]
    pos = jnp.arange(1, S + 1, dtype=jnp.int32)
    return jnp.max(jnp.where(mask != 0, pos, 0), axis=1).astype(jnp.int32)


def live_tiles(extents: jax.Array, block_b: int, block_s: int) -> jax.Array:
    """The extent table: for each block of ``block_b`` rows, how many
    sequence tiles of ``block_s`` reach a real position of some row
    (``ceil(max extent / block_s)``). Tiles at or past that count hold
    only padding for every row of the block, so the kernels skip them.
    Rows past ``B`` (the kernels' row padding) have extent 0."""
    ext = pad_to(jnp.asarray(extents, jnp.int32), 0, block_b)
    block_max = jnp.max(ext.reshape(-1, block_b), axis=1)
    return (block_max + block_s - 1) // block_s


def live_tile_share(extents, block_b: int, block_s: int,
                    seq_len: int) -> float:
    """Share of the ``(row block, sequence tile)`` pairs that the
    kernels run for rows of these extents, in this order."""
    live = live_tiles(extents, block_b, block_s)
    return float(jnp.sum(live)) / (live.shape[0] * -(-seq_len // block_s))


def last_live_tile(live_ref, i, k):
    """Sequence-tile index to fetch at step ``k`` of row block ``i``: the
    step's own while the tile is live, else the block's last live tile,
    which the pipeline already holds, so a skipped step issues no DMA."""
    return jnp.minimum(k, jnp.maximum(live_ref[i] - 1, 0))


def _fwd_kernel(
    live_ref,   # (B/bb,) i32 SMEM — live sequence tiles per row block
    h_ref,      # (bb, bs, D)  input dtype (f32 or bf16)
    e_ref,      # (bv, D)      input dtype
    bias_ref,   # (1, bv)  f32
    mask_ref,   # (bs, bb) int32 — the row block's mask, transposed
    y_ref,      # (bb, bv) f32 out — written once, at finalize
    i_ref,      # (bb, bv) i32 out — written once, at finalize
    acc_ref,    # (bb, bv) f32 VMEM scratch — running max
    arg_ref,    # (bb, bv) i32 VMEM scratch — running argmax
    *,
    n_s_blocks: int,
    block_s: int,
    softcap: Optional[float],
):
    i = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.full(acc_ref.shape, NEG_INF, jnp.float32)
        arg_ref[...] = jnp.zeros(arg_ref.shape, jnp.int32)

    # A tile past the row block's extent is all padding: its logits
    # would all be masked, and a masked tile never beats the running max.
    @pl.when(k < live_ref[i])
    def _tile():
        bb, bs, d = h_ref.shape
        bv = e_ref.shape[0]

        h = h_ref[...].reshape(bb * bs, d)
        e = e_ref[...]
        # (bb*bs, bv) logit tile on the MXU; f32 accumulation regardless
        # of the input dtype (bf16 operands feed the MXU natively).
        logits = jax.lax.dot_general(
            h, e, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        logits = logits + bias_ref[...]  # (1, bv) broadcasts over rows
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        # The logit rows carry s on sublanes, row after row; the mask
        # arrives transposed, so its columns stack into that row mask.
        mask_t = mask_ref[...]  # (bs, bb)
        keep = jnp.concatenate([mask_t[:, r:r + 1] for r in range(bb)],
                               axis=0)
        logits = jnp.where(keep > 0, logits, NEG_INF).reshape(bb, bs, bv)

        tile_max = jnp.max(logits, axis=1)  # (bb, bv)
        # First-occurrence argmax without lax.argmax (portable in Pallas):
        s_iota = jax.lax.broadcasted_iota(jnp.int32, (bb, bs, bv), 1)
        hit = logits >= tile_max[:, None, :]
        tile_arg = jnp.min(jnp.where(hit, s_iota, bs), axis=1) + k * block_s

        cur = acc_ref[...]
        better = tile_max > cur  # strict: earlier blocks win ties
        acc_ref[...] = jnp.where(better, tile_max, cur)
        arg_ref[...] = jnp.where(better, tile_arg, arg_ref[...])

    @pl.when(k == n_s_blocks - 1)
    def _finalize():
        # single HBM store per (b, v) output tile
        y_ref[...] = jnp.log1p(jnp.maximum(acc_ref[...], 0.0))
        i_ref[...] = arg_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_b", "block_s", "block_v", "softcap", "interpret"
    ),
)
def _forward_call(
    H, E, b, mask, live, *, block_b, block_s, block_v, softcap, interpret
):
    B, S, D = H.shape
    V = E.shape[0]

    Hp = pad_to(pad_to(H, 0, block_b), 1, block_s)
    maskp = pad_to(pad_to(mask.astype(jnp.int32), 0, block_b), 1, block_s)
    Ep = pad_to(E, 0, block_v)
    bp = pad_to(b.astype(jnp.float32), 0, block_v).reshape(1, -1)

    Bp, Sp, _ = Hp.shape
    Vp = Ep.shape[0]
    grid = (Bp // block_b, Vp // block_v, Sp // block_s)
    # (B/bb, Sp, bb): each row block's mask with s on sublanes, so a
    # (bs, bb) block keeps Mosaic's tiling at any block_s
    mask_t = maskp.reshape(grid[0], block_b, Sp).transpose(0, 2, 1)

    def seq_tile(i, j, k, lv):
        return (i, last_live_tile(lv, i, k), 0)

    kernel = functools.partial(
        _fwd_kernel,
        n_s_blocks=grid[2],
        block_s=block_s,
        softcap=softcap,
    )
    y, i_max = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_b, block_s, D), seq_tile),
                pl.BlockSpec((block_v, D), lambda i, j, k, lv: (j, 0)),
                pl.BlockSpec((1, block_v), lambda i, j, k, lv: (0, j)),
                pl.BlockSpec((None, block_s, block_b), seq_tile),
            ],
            out_specs=[
                pl.BlockSpec((block_b, block_v), lambda i, j, k, lv: (i, j)),
                pl.BlockSpec((block_b, block_v), lambda i, j, k, lv: (i, j)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_b, block_v), jnp.float32),
                pltpu.VMEM((block_b, block_v), jnp.int32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((Bp, Vp), jnp.float32),
            jax.ShapeDtypeStruct((Bp, Vp), jnp.int32),
        ],
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(live, Hp, Ep, bp, mask_t)
    return y[:B, :V], i_max[:B, :V]


def sparton_forward(
    H: jax.Array,        # (B, S, D) f32 or bf16
    E: jax.Array,        # (V, D) f32 or bf16
    b: jax.Array,        # (V,)
    mask: jax.Array,     # (B, S) int32/bool, 1 = keep
    *,
    block_b: Optional[int] = None,
    block_s: Optional[int] = None,
    block_v: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Fused forward. Returns (y (B, V) f32, i_max (B, V) i32).

    Block sizes default to the autotuner's cached/heuristic choice for
    the call shape (``kernels/autotune.py``); pass explicit ints to pin.
    Each row block runs the sequence tiles up to its rows' largest
    ``row_extents``.
    """
    if block_b is None or block_s is None or block_v is None:
        from repro.kernels.autotune import resolve_blocks  # avoids cycle

        B, S, D = H.shape
        block_b, block_s, block_v = resolve_blocks(
            B, S, D, E.shape[0], H.dtype, block_b, block_s, block_v,
            kernel="fwd")
    return _forward_call(
        H, E, b, mask, live_tiles(row_extents(mask), block_b, block_s),
        block_b=block_b, block_s=block_s, block_v=block_v,
        softcap=softcap, interpret=interpret,
    )
