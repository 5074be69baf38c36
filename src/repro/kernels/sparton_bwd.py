"""Sparton fused LM-head backward v2 — Pallas TPU kernels.

The paper's Alg. 3 computes, per (b, v), the activation-derivative
factor ``g`` and scatters ``g*E[v]`` into ``dH[b, i_max]`` / gathers
``H[b, i_max]`` into ``dE[v]`` using *atomic* accumulation across GPU
thread blocks. TPU Pallas has no atomics; instead we exploit the
sequential grid to accumulate deterministically (DESIGN.md §3):

* ``dH`` kernel — grid ``(B/bb, S/bs, V/bv)``, vocab innermost: each
  ``(b, s)`` tile of ``dH`` accumulates
  ``sum_v g[b,v] * onehot(i_max[b,v], s) * E[v]``.
* ``dE`` kernel — grid ``(V/bv, B/bb, S/bs)``, batch/seq innermost:
  each vocab tile of ``dE`` accumulates
  ``sum_b g[b,v] * onehot(i_max[b,v], s) * H[b,s]``.

v2 over v1 (DESIGN.md §"Kernel v2"):

* **Fused epilogue** — the kernels take the raw upstream cotangent
  ``dy`` and the stored post-activation ``y`` and evaluate ``g = dy *
  f'(y)`` per VMEM tile (``_common.bwd_factor``). v1 materialized ``g``
  with a standalone ``(B, V)`` elementwise pass: one full HBM write +
  two reads of a ``(B, V)`` f32 tensor, gone. The factor is recomputed
  by both kernels — a few VPU ops per tile versus a ``(B, V)`` HBM
  round-trip.
* **Fused bias gradient** — ``db = sum_b g`` accumulates in the dE
  kernel's scratch (one extra ``(1, bv)`` vector), so the wrapper's
  separate ``jnp.sum`` over a re-read ``g`` is gone too.
* **VMEM scratch accumulators** — both kernels accumulate into
  ``scratch_shapes`` and store each output tile to HBM exactly once at
  their finalize step, mirroring the forward's single-store guarantee.
* The weighted one-hot tile construction is shared between the two
  contractions via ``_common.onehot_weights``. (The contractions
  themselves must stay in separate kernels: dH tiles are indexed by
  (b, s) and dE tiles by (v), so no single grid order visits both
  accumulators in consecutive steps — the precondition for
  deterministic revisit-accumulation on Mosaic pipelines.)

Gather/scatter by ``i_max`` is re-expressed as a *one-hot contraction*
(``onehot(i_max) @ E`` / ``(onehot*g)^T @ H``) so the irregular memory
access becomes an MXU matmul — the TPU-native replacement for GPU
scattered atomics.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._common import (bwd_factor, compiler_params,
                                   onehot_weights, pad_to)


def _dh_kernel(
    dy_ref,    # (bb, bv) f32 — raw upstream cotangent
    y_ref,     # (bb, bv) f32 — stored post-activation
    i_ref,     # (bb, bv) i32 — argmax sequence index
    e_ref,     # (bv, D)
    dh_ref,    # (bb, bs, D) out — written once, at finalize
    acc_ref,   # (bb, bs, D) f32 VMEM scratch
    *,
    n_v_blocks: int,
    block_s: int,
    softcap: Optional[float],
):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    bb, bs, d = dh_ref.shape
    k = pl.program_id(1)

    g = bwd_factor(y_ref[...], dy_ref[...], softcap)     # fused epilogue
    local_i = i_ref[...] - k * block_s          # (bb, bv); in-range => hit
    w = onehot_weights(g, local_i, bs)          # (bb, bs, bv)
    # dH[b, s, :] += sum_v w[b, s, v] * E[v, :]  — one MXU contraction.
    contrib = jax.lax.dot_general(
        w.reshape(bb * bs, -1), e_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    ).reshape(bb, bs, d)
    acc_ref[...] += contrib

    @pl.when(j == n_v_blocks - 1)
    def _finalize():
        dh_ref[...] = acc_ref[...]


def _de_kernel(
    dy_ref,    # (bb, bv) f32
    y_ref,     # (bb, bv) f32
    i_ref,     # (bb, bv) i32
    h_ref,     # (bb, bs, D)
    de_ref,    # (bv, D) out — written once, at finalize
    db_ref,    # (1, bv) f32 out — fused bias gradient
    de_acc,    # (bv, D) f32 VMEM scratch
    db_acc,    # (1, bv) f32 VMEM scratch
    *,
    n_b_blocks: int,
    n_s_blocks: int,
    block_s: int,
    softcap: Optional[float],
):
    i = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when((i == 0) & (k == 0))
    def _init():
        de_acc[...] = jnp.zeros(de_acc.shape, jnp.float32)
        db_acc[...] = jnp.zeros(db_acc.shape, jnp.float32)

    bb, bs, _ = h_ref.shape

    g = bwd_factor(y_ref[...], dy_ref[...], softcap)     # fused epilogue
    local_i = i_ref[...] - k * block_s
    w = onehot_weights(g, local_i, bs).reshape(bb * bs, -1)
    # dE[v, :] += sum_{b,s} w[bs, v] * H[bs, :]
    contrib = jax.lax.dot_general(
        w, h_ref[...].reshape(bb * bs, -1).astype(jnp.float32),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    de_acc[...] += contrib

    # db[v] = sum_b g[b, v] — independent of s, so add once per b block.
    @pl.when(k == 0)
    def _db():
        db_acc[...] += jnp.sum(g, axis=0, keepdims=True)

    @pl.when((i == n_b_blocks - 1) & (k == n_s_blocks - 1))
    def _finalize():
        de_ref[...] = de_acc[...]
        db_ref[...] = db_acc[...]


# The two backward contractions are separately-jitted calls with their
# OWN block triples: dH tiles are indexed by (b, s) and dE tiles by
# (v), so the best blocks differ (the autotuner times them apart —
# ROADMAP per-kernel item). Padding invariant shared by both: padded
# rows/cols must not route anywhere real — y == 0 there, so bwd_factor
# yields g == 0 and any index is safe.

@functools.partial(
    jax.jit,
    static_argnames=("seq_len", "block_b", "block_s", "block_v",
                     "softcap", "interpret"),
)
def _dh_call(
    dy, y, i_max, E, *, seq_len, block_b, block_s, block_v, softcap,
    interpret
):
    B, V = dy.shape
    D = E.shape[1]

    dyp = pad_to(pad_to(dy.astype(jnp.float32), 0, block_b), 1, block_v)
    yp = pad_to(pad_to(y.astype(jnp.float32), 0, block_b), 1, block_v)
    ip = pad_to(pad_to(i_max, 0, block_b), 1, block_v)
    Ep = pad_to(E, 0, block_v)

    Bp = dyp.shape[0]
    Vp = Ep.shape[0]
    Sp = -(-seq_len // block_s) * block_s
    nb, ns, nv = Bp // block_b, Sp // block_s, Vp // block_v

    bv_spec = pl.BlockSpec((block_b, block_v), lambda i, k, j: (i, j))
    dH = pl.pallas_call(
        functools.partial(_dh_kernel, n_v_blocks=nv, block_s=block_s,
                          softcap=softcap),
        grid=(nb, ns, nv),
        in_specs=[
            bv_spec,
            bv_spec,
            bv_spec,
            pl.BlockSpec((block_v, D), lambda i, k, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec(
            (block_b, block_s, D), lambda i, k, j: (i, k, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((Bp, Sp, D), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_b, block_s, D), jnp.float32),
        ],
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(dyp, yp, ip, Ep)
    return dH[:B, :seq_len]


@functools.partial(
    jax.jit,
    static_argnames=("block_b", "block_s", "block_v", "softcap",
                     "interpret"),
)
def _de_call(
    dy, y, i_max, H, *, block_b, block_s, block_v, softcap, interpret
):
    B, V = dy.shape
    S, D = H.shape[1], H.shape[2]

    dyp = pad_to(pad_to(dy.astype(jnp.float32), 0, block_b), 1, block_v)
    yp = pad_to(pad_to(y.astype(jnp.float32), 0, block_b), 1, block_v)
    ip = pad_to(pad_to(i_max, 0, block_b), 1, block_v)
    Hp = pad_to(pad_to(H, 0, block_b), 1, block_s)

    Bp, Sp, _ = Hp.shape
    Vp = dyp.shape[1]
    nb, ns, nv = Bp // block_b, Sp // block_s, Vp // block_v

    vb_spec = pl.BlockSpec((block_b, block_v), lambda j, i, k: (i, j))
    dE, db = pl.pallas_call(
        functools.partial(
            _de_kernel, n_b_blocks=nb, n_s_blocks=ns, block_s=block_s,
            softcap=softcap,
        ),
        grid=(nv, nb, ns),
        in_specs=[
            vb_spec,
            vb_spec,
            vb_spec,
            pl.BlockSpec((block_b, block_s, D), lambda j, i, k: (i, k, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_v, D), lambda j, i, k: (j, 0)),
            pl.BlockSpec((1, block_v), lambda j, i, k: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Vp, D), jnp.float32),
            jax.ShapeDtypeStruct((1, Vp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_v, D), jnp.float32),
            pltpu.VMEM((1, block_v), jnp.float32),
        ],
        compiler_params=compiler_params("parallel", "arbitrary",
                                        "arbitrary"),
        interpret=interpret,
    )(dyp, yp, ip, Hp)
    return dE[:V], db[0, :V]


Blocks = Tuple[int, int, int]


def _resolve(shape, V, dtype, kernel, block_b, block_s, block_v) -> Blocks:
    """Autotune-cache resolution. The cache's dtype component keys on
    the kernel's own weight/activation operand (dy/y are always f32):
    E for the dH kernel, H for dE — the same rule every entry point
    (ops.sparton_head, the standalone wrappers) applies, so one tuning
    sweep serves them all."""
    if block_b is not None and block_s is not None and block_v is not None:
        return (block_b, block_s, block_v)
    from repro.kernels.autotune import resolve_blocks  # avoids cycle

    B, S, D = shape
    return resolve_blocks(B, S, D, V, dtype, block_b, block_s,
                          block_v, kernel=kernel)


def sparton_backward_dh(
    dy: jax.Array,      # (B, V) — raw upstream cotangent
    y: jax.Array,       # (B, V) f32 — stored post-activation
    i_max: jax.Array,   # (B, V) i32
    E: jax.Array,       # (V, D) f32 or bf16
    seq_len: int,
    *,
    block_b: Optional[int] = None,
    block_s: Optional[int] = None,
    block_v: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """The dH contraction alone — the unit the autotuner times."""
    B, V = dy.shape
    blocks = _resolve((B, seq_len, E.shape[1]), V, E.dtype, "dh",
                      block_b, block_s, block_v)
    return _dh_call(dy, y, i_max, E, seq_len=seq_len, block_b=blocks[0],
                    block_s=blocks[1], block_v=blocks[2],
                    softcap=softcap, interpret=interpret)


def sparton_backward_de(
    dy: jax.Array,      # (B, V)
    y: jax.Array,       # (B, V) f32
    i_max: jax.Array,   # (B, V) i32
    H: jax.Array,       # (B, S, D) f32 or bf16
    *,
    block_b: Optional[int] = None,
    block_s: Optional[int] = None,
    block_v: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """The dE (+ fused db) contraction alone — the autotuner's unit."""
    blocks = _resolve(H.shape, dy.shape[1], H.dtype, "de",
                      block_b, block_s, block_v)
    return _de_call(dy, y, i_max, H, block_b=blocks[0],
                    block_s=blocks[1], block_v=blocks[2],
                    softcap=softcap, interpret=interpret)


def sparton_backward(
    dy: jax.Array,      # (B, V) — raw upstream cotangent (any float dtype)
    y: jax.Array,       # (B, V) f32 — stored post-activation
    i_max: jax.Array,   # (B, V) i32
    H: jax.Array,       # (B, S, D) f32 or bf16
    E: jax.Array,       # (V, D) f32 or bf16
    *,
    block_b: Optional[int] = None,
    block_s: Optional[int] = None,
    block_v: Optional[int] = None,
    dh_blocks: Optional[Blocks] = None,
    de_blocks: Optional[Blocks] = None,
    softcap: Optional[float] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused backward. Returns (dH (B,S,D), dE (V,D), db (V,)) in f32.

    The activation-derivative factor and the bias gradient are fused
    into the kernels — no standalone elementwise pass over ``(B, V)``.

    Block resolution is **per kernel**: explicit ``dh_blocks`` /
    ``de_blocks`` triples win; else ``block_b/s/v`` pins apply to both
    contractions (the legacy joint behavior); unset components come
    from the autotuner's per-kernel cache ("dh" / "de" entries, falling
    back to a legacy joint entry when only that exists).
    """
    V = E.shape[0]
    if dh_blocks is None:
        dh_blocks = _resolve(H.shape, V, E.dtype, "dh",
                             block_b, block_s, block_v)
    if de_blocks is None:
        de_blocks = _resolve(H.shape, V, H.dtype, "de",
                             block_b, block_s, block_v)
    dH = _dh_call(dy, y, i_max, E, seq_len=H.shape[1],
                  block_b=dh_blocks[0], block_s=dh_blocks[1],
                  block_v=dh_blocks[2], softcap=softcap,
                  interpret=interpret)
    dE, db = _de_call(dy, y, i_max, H, block_b=de_blocks[0],
                      block_s=de_blocks[1], block_v=de_blocks[2],
                      softcap=softcap, interpret=interpret)
    return dH, dE, db
