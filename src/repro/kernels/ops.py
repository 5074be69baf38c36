"""Jit'd, differentiable wrappers around the Pallas Sparton kernels.

``sparton_lm_head_kernel`` is the drop-in kernel-backed equivalent of
``repro.core.lm_head.lm_head_sparton``: a ``jax.custom_vjp`` whose
forward runs the fused Pallas forward (saving only ``(y, i_max)``) and
whose backward runs the two fused Pallas accumulation kernels. The v2
backward consumes the raw cotangent directly — the activation-
derivative factor ``g = dy * f'(y)`` and the bias gradient
``db = sum_b g`` are computed inside the kernels, so no standalone
``(B, V)`` elementwise pass (and no HBM round-trip of ``g``) remains.

Block sizes default to ``None`` = auto: the autotuner's cached winner
**per kernel** (fwd vs dH vs dE — each contraction has its own cache
entry and heuristic), else the analytic heuristic
(``repro.kernels.autotune``). Passing ints pins the same triple across
all three kernels (the legacy joint behavior).

On the CPU backend the kernels run with ``interpret=True`` (the kernel
body executed by the Pallas interpreter); on a TPU the same code
compiles to Mosaic. ``interpret`` is threaded through as a static
argument; ``head_api`` resolves its default from the backend
(``_common.interpret_mode``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.sparton import sparton_forward
from repro.kernels.sparton_bwd import sparton_backward

Blocks = Tuple[int, int, int]


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def sparton_lm_head_kernel(
    H: jax.Array,
    E: jax.Array,
    b: jax.Array,
    mask: jax.Array,
    block_b: Optional[int] = None,
    block_s: Optional[int] = None,
    block_v: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: bool = False,
    out_dtype: Optional[jnp.dtype] = None,
    dh_blocks: Optional[Blocks] = None,
    de_blocks: Optional[Blocks] = None,
) -> jax.Array:
    y, _ = sparton_forward(
        H, E, b, mask,
        block_b=block_b, block_s=block_s, block_v=block_v,
        softcap=softcap, interpret=interpret,
    )
    return y.astype(out_dtype or H.dtype)


def _fwd(H, E, b, mask, block_b, block_s, block_v, softcap, interpret,
         out_dtype, dh_blocks, de_blocks):
    y, i_max = sparton_forward(
        H, E, b, mask,
        block_b=block_b, block_s=block_s, block_v=block_v,
        softcap=softcap, interpret=interpret,
    )
    return y.astype(out_dtype or H.dtype), (H, E, y, i_max)


def _bwd(block_b, block_s, block_v, softcap, interpret, out_dtype,
         dh_blocks, de_blocks, res, dy):
    H, E, y, i_max = res
    # v2: dy and y go straight into the kernels; g and db are computed
    # tile-wise in their epilogues. Each backward contraction runs with
    # its own blocks (explicit triples win; else block_* pins apply
    # jointly; else per-kernel autotune cache).
    dH, dE, db = sparton_backward(
        dy, y, i_max, H, E,
        block_b=block_b, block_s=block_s, block_v=block_v,
        dh_blocks=dh_blocks, de_blocks=de_blocks,
        softcap=softcap, interpret=interpret,
    )
    return dH.astype(H.dtype), dE.astype(E.dtype), db, None


sparton_lm_head_kernel.defvjp(_fwd, _bwd)


def sparton_head(
    H: jax.Array,
    E: jax.Array,
    b: Optional[jax.Array] = None,
    mask: Optional[jax.Array] = None,
    *,
    block_b: Optional[int] = None,
    block_s: Optional[int] = None,
    block_v: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    interpret: bool = False,
    out_dtype: Optional[jnp.dtype] = None,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Convenience entry point with optional bias/mask (kernel-backed).

    With the default ``block_* = None`` the block sizes are resolved
    once here **per kernel** — cache hit (``_fwd``/``_dh``/``_de``
    entries, legacy joint entries as fallback) or per-kernel heuristic,
    keyed on the shapes of THIS call (under shard_map: the local vocab
    shard) — so forward and backward are guaranteed to agree even if
    the autotune cache changes mid-step. Explicit ints pin one joint
    triple across all three kernels.

    ``softcap`` is the deprecated spelling of ``logit_softcap`` (kept
    so pre-registry callers don't break). Prefer building heads through
    ``repro.core.head_api.make_head``.
    """
    from repro.core.head_api import normalize_softcap_kwarg

    logit_softcap = normalize_softcap_kwarg(logit_softcap, softcap,
                                            "sparton_head")
    B, S, D = H.shape
    V = E.shape[0]
    dh_blocks = de_blocks = None
    if block_b is None or block_s is None or block_v is None:
        from repro.kernels.autotune import resolve_blocks

        # cache dtype keys on each kernel's own weight/activation
        # operand — the rule sparton_bwd's standalone wrappers share
        pins = (block_b, block_s, block_v)
        block_b, block_s, block_v = resolve_blocks(
            B, S, D, V, H.dtype, *pins, kernel="fwd")
        dh_blocks = resolve_blocks(B, S, D, V, E.dtype, *pins,
                                   kernel="dh")
        de_blocks = resolve_blocks(B, S, D, V, H.dtype, *pins,
                                   kernel="de")
    if b is None:
        b = jnp.zeros((V,), jnp.float32)
    if mask is None:
        mask = jnp.ones((B, S), jnp.int32)
    return sparton_lm_head_kernel(
        H, E, b, mask, block_b, block_s, block_v, logit_softcap,
        interpret, out_dtype, dh_blocks, de_blocks
    )
