"""Shared helpers for the Sparton Pallas kernels and their wrappers.

Everything here is dependency-light (jnp only) so it can be imported by
the kernel modules, the differentiable wrappers in ``ops.py``, and the
pure-JAX reference head in ``core/lm_head.py`` without cycles.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# Finite stand-in for -inf: keeps the streaming max/argmax well-defined
# in bf16 and lets padded/masked lanes lose every comparison.
NEG_INF = -1e30

# Scoped VMEM every kernel here is compiled under. Mosaic's default on
# v5e is 16 MiB; the autotuner's VMEM model budgets against this value
# (kernels/autotune.py), so the blocks it admits are the blocks that
# compile.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter: on the CPU backend
    (tests), never on a TPU. Any other backend is an error rather than a
    silent fall back to the interpreter."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"no Pallas path for backend {backend!r}: kernels compile on a "
        "TPU and are interpreted on the CPU only")


def compiler_params(*dimension_semantics: str):
    """Mosaic compiler parameters shared by every kernel in the repo."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def pad_to(x: jax.Array, axis: int, multiple: int, value=0) -> jax.Array:
    """Zero-pad (or ``value``-pad) ``axis`` of ``x`` up to a multiple."""
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def bwd_factor(y: jax.Array, dy: jax.Array,
               softcap: Optional[float]) -> jax.Array:
    """g = dY/d(raw max logit), from the *stored post-activation* y.

    f(x) = log1p(relu(c(x))),   c = softcap or identity.
    With m = relu-input value at the max: exp(y) = 1 + relu(c(m)), and
    y > 0  <=>  c(m) > 0  <=>  m > 0 (softcap is sign-preserving).
        df/dc = exp(-y)         on c > 0, else 0
        dc/dm = 1 - (c/cap)^2   (tanh derivative), c = expm1(y)

    Elementwise and branch-free, so it fuses into the backward kernels'
    epilogue (computed per VMEM tile, never materialized in HBM).
    """
    g = dy.astype(jnp.float32) * jnp.exp(-y)
    if softcap is not None:
        c = jnp.expm1(y)
        g = g * (1.0 - (c / softcap) ** 2)
    return jnp.where(y > 0, g, 0.0)


def onehot_weights(g: jax.Array, local_i: jax.Array,
                   block_s: int) -> jax.Array:
    """The weighted one-hot tile both backward contractions contract with.

    ``w[b, s, v] = g[b, v] * 1[local_i[b, v] == s]`` for a ``(bb, bv)``
    gradient-factor tile and sequence-local argmax indices. Positions
    whose argmax falls outside the current sequence block produce an
    all-zero row, which is exactly what routes each gradient to one
    sequence block. The irregular gather/scatter of the paper's Alg. 3
    becomes a dense MXU contraction against this tile.
    """
    bb, bv = g.shape
    s_iota = jax.lax.broadcasted_iota(jnp.int32, (bb, block_s, bv), 1)
    onehot = (local_i[:, None, :] == s_iota).astype(jnp.float32)
    return onehot * g[:, None, :]          # (bb, bs, bv)
