"""Unified head API — one spec, one registry, one factory (DESIGN.md §6).

The paper contributes a single operator (Eq. 1), but the repo grew four
divergent surfaces for it: the pure-JAX ladder in ``core.lm_head``, the
Pallas wrapper in ``kernels.ops`` (with its own kwarg spellings), the
shard_map factory in ``core.sharded``, and per-call-site if/else
ladders in ``launch``/``benchmarks``/``examples``. This module is the
single seam where "which impl, which blocks, which mesh" is decided:

* ``HeadSpec``            — frozen, hashable description of a head
  configuration (impl name, Pallas blocks, scan tile, softcap, ...).
* ``register_head_impl``  — registry of backends with ONE normalized
  calling convention ``fn(H, E, b, mask, *, spec) -> Y``. ``naive``,
  ``tiled``, ``sparton`` (pure JAX) and ``kernel`` (Pallas) ship
  registered; new backends (two-pass backward, per-kernel blocks) are
  one ``register_head_impl`` call, not another if/else.
* ``make_head(spec, mesh=...)`` — factory returning one canonical
  callable ``head(H, E, b=None, mask=None) -> Y`` regardless of
  backend or sharding. With a mesh, the *selected impl* runs inside
  the vocab-sharded ``shard_map`` body — including the Pallas kernel,
  whose block sizes resolve against the **local** vocab shard
  ``V // n_model`` (the shapes the kernel actually sees), so the
  autotune cache is keyed per shard, not per global vocab.

Sharding contract (global view), identical to ``core.sharded``:

    H    (B, S, D)  — batch over ``batch_axes``, replicated over model
    E    (V, D)     — rows over ``axis_name``
    b    (V,)       — over ``axis_name``
    Y    (B, V)     — batch over ``batch_axes``, vocab over ``axis_name``

The streaming max is per-vocab-column independent, so the sharded
forward needs zero collectives and ``∇E`` is shard-local; the single
``∇H`` psum over ``axis_name`` is inserted by shard_map's transpose.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import lm_head as _lm
from repro.kernels._common import interpret_mode, pad_to

Array = jax.Array

# Registered backend convention: fn(H, E, b, mask, *, spec) -> (B, V).
# H (B, S, D); E (V, D); b (V,) f32; mask (B, S) int32/bool — all
# concrete (make_head fills the b/mask defaults before dispatch).
HeadFn = Callable[..., Array]


@dataclasses.dataclass(frozen=True)
class HeadSpec:
    """Everything needed to build a Sparton head, in one hashable value.

    ``impl``            registry name: naive | tiled | sparton | kernel
                        (plus anything registered at runtime).
    ``block_b/s/v``     Pallas kernel blocks; None = autotuner cache /
                        heuristic for the call shape (local shard shape
                        under a mesh). Ignored by the pure-JAX impls.
    ``vocab_tile``      streaming-scan tile of the pure-JAX impls.
    ``logit_softcap``   gemma-2 style ``c * tanh(x / c)`` on the raw
                        logits; the ONE canonical spelling (the legacy
                        ``softcap=`` kwarg is deprecated).
    ``out_dtype``       output dtype; None = H.dtype.
    ``interpret``       Pallas interpreter toggle; None = auto
                        (``kernels._common.interpret_mode``: interpreted
                        on the CPU, compiled on a TPU).
    ``bwd_batch_chunk`` batch chunking of the pure-JAX backward scan.
    ``unroll``          scan unroll of the pure-JAX impls (cost probes).
    ``rep_topk``        sparsify the (B, V) head output to its top-k
                        terms per row (Unified-LSR model knob); the
                        reduction runs on-device via the streaming
                        merge, so the dense rep never reaches host.
    ``rep_threshold``   drop rep entries at or below this impact
                        weight. Composes with ``rep_topk``; alone it
                        caps rows at ``rep_max_nnz`` slots (largest
                        entries win).
    ``rep_max_nnz``     static slot budget of threshold-only
                        sparsification. Both rep knobs None = dense
                        (B, V) output, the pre-sparse default.
    """

    impl: str = "sparton"
    block_b: Optional[int] = None
    block_s: Optional[int] = None
    block_v: Optional[int] = None
    vocab_tile: int = 4096
    logit_softcap: Optional[float] = None
    out_dtype: Optional[str] = None
    interpret: Optional[bool] = None
    bwd_batch_chunk: int = 8
    unroll: int = 1
    rep_topk: Optional[int] = None
    rep_threshold: Optional[float] = None
    rep_max_nnz: int = 256

    @property
    def sparse_reps(self) -> bool:
        """Whether encoders built from this spec emit SparseReps."""
        return self.rep_topk is not None or self.rep_threshold is not None

    def replace(self, **kw) -> "HeadSpec":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, HeadFn] = {}


def register_head_impl(name: str, fn: HeadFn) -> None:
    """Register (or override) a head backend under ``name``.

    ``fn(H, E, b, mask, *, spec: HeadSpec) -> (B, V)`` with concrete
    ``b``/``mask`` — the factory normalizes the optional arguments
    before dispatch, so backends never see ``None``.
    """
    _REGISTRY[name] = fn


def available_impls() -> Tuple[str, ...]:
    """Registered backend names (the user-facing impl enumeration)."""
    return tuple(sorted(_REGISTRY))


def get_head_impl(name: str) -> HeadFn:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown head impl {name!r}; one of {list(available_impls())}"
        ) from None


def normalize_softcap_kwarg(
    logit_softcap: Optional[float],
    softcap: Optional[float],
    where: str,
) -> Optional[float]:
    """Fold the deprecated ``softcap=`` spelling into ``logit_softcap``."""
    if softcap is None:
        return logit_softcap
    warnings.warn(
        f"{where}: the 'softcap' kwarg is deprecated; use "
        "'logit_softcap' (one normalized name across every head "
        "surface)", DeprecationWarning, stacklevel=3)
    if logit_softcap is not None and logit_softcap != softcap:
        raise ValueError(
            f"{where}: conflicting logit_softcap={logit_softcap!r} and "
            f"deprecated softcap={softcap!r}")
    return softcap


def _cast_out(y: Array, H: Array, spec: HeadSpec) -> Array:
    return y.astype(jnp.dtype(spec.out_dtype) if spec.out_dtype else H.dtype)


# ---------------------------------------------------------------------------
# built-in backends
# ---------------------------------------------------------------------------

def _naive_impl(H, E, b, mask, *, spec: HeadSpec) -> Array:
    y = _lm.lm_head_naive(H, E, b, mask, logit_softcap=spec.logit_softcap)
    return _cast_out(y, H, spec)


def _tiled_impl(H, E, b, mask, *, spec: HeadSpec) -> Array:
    y = _lm.lm_head_tiled(H, E, b, mask, vocab_tile=spec.vocab_tile,
                          logit_softcap=spec.logit_softcap)
    return _cast_out(y, H, spec)


def _sparton_impl(H, E, b, mask, *, spec: HeadSpec) -> Array:
    y = _lm.lm_head_sparton(
        H, E, b, mask, vocab_tile=spec.vocab_tile,
        logit_softcap=spec.logit_softcap,
        bwd_batch_chunk=spec.bwd_batch_chunk, unroll=spec.unroll)
    return _cast_out(y, H, spec)


def _kernel_impl(H, E, b, mask, *, spec: HeadSpec) -> Array:
    # Lazy import: keep core importable without pulling Pallas until a
    # kernel head is actually built.
    from repro.kernels.ops import sparton_head

    interpret = spec.interpret
    if interpret is None:
        interpret = interpret_mode()
    # Block resolution happens here, against the shapes this call sees:
    # under shard_map that is the LOCAL vocab shard (V // n_model), so
    # the autotune cache key matches the shard the kernel runs on.
    y = sparton_head(
        H, E, b, mask,
        block_b=spec.block_b, block_s=spec.block_s, block_v=spec.block_v,
        logit_softcap=spec.logit_softcap, interpret=interpret,
        out_dtype=jnp.dtype(spec.out_dtype) if spec.out_dtype else None)
    return y


register_head_impl("naive", _naive_impl)
register_head_impl("tiled", _tiled_impl)
register_head_impl("sparton", _sparton_impl)
register_head_impl("kernel", _kernel_impl)


# ---------------------------------------------------------------------------
# factory
# ---------------------------------------------------------------------------

def _with_defaults(H: Array, E: Array, b: Optional[Array],
                   mask: Optional[Array]) -> Tuple[Array, Array]:
    if b is None:
        b = jnp.zeros((E.shape[0],), jnp.float32)
    if mask is None:
        mask = jnp.ones(H.shape[:2], jnp.int32)
    return b, mask


def make_head(
    spec: HeadSpec,
    mesh: Optional[Mesh] = None,
    *,
    axis_name: str = "model",
    batch_axes: Tuple[str, ...] = ("pod", "data"),
) -> Callable[..., Array]:
    """One canonical ``head(H, E, b=None, mask=None) -> Y`` callable.

    Without a mesh: the registered backend, called directly.

    With a mesh: the backend wrapped in the vocab-sharded shard_map
    body (E/b rows over ``axis_name``, H/Y batch over ``batch_axes``).
    A vocab the model axis does not divide (30522 and 250002 on any
    axis of size 4) is padded to whole shards with zero rows, whose
    outputs are 0; that output is gathered over the model axis and the
    pad sliced off. The selected impl — the Pallas kernel included —
    always runs inside the sharded body.
    """
    impl_fn = get_head_impl(spec.impl)

    if mesh is None:
        def head(H, E, b=None, mask=None):
            b, mask = _with_defaults(H, E, b, mask)
            return impl_fn(H, E, b, mask, spec=spec)
        return head

    n_shard = mesh.shape[axis_name]

    def sharded(gather: bool):
        def body(h, e, b_, m_):
            y = impl_fn(h, e, b_, m_, spec=spec)
            if gather:
                y = jax.lax.all_gather(y, axis_name, axis=1, tiled=True)
            return y

        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P(batch_axes, None, None),   # H — replicated over model
                P(axis_name, None),          # E — vocab rows sharded
                P(axis_name),                # b
                P(batch_axes, None),         # mask
            ),
            out_specs=P(batch_axes, None if gather else axis_name),
            check_vma=False,  # custom_vjp inside: skip replication check
        )

    split, gathered = sharded(False), sharded(True)

    def head(H, E, b=None, mask=None):
        b, mask = _with_defaults(H, E, b, mask)
        V = E.shape[0]
        if V % n_shard == 0:
            return split(H, E, b, mask)
        # zero rows give logit 0 -> y = log1p(relu(0)) = 0 and a zero
        # gradient factor; the padded output is gathered over the model
        # axis so the pad columns can be sliced off
        y = gathered(H, pad_to(E, 0, n_shard),
                     pad_to(b.astype(jnp.float32), 0, n_shard), mask)
        return y[:, :V]

    return head


def make_sparsifier(spec: HeadSpec) -> Optional[Callable[[Array], "object"]]:
    """The spec's rep sparsifier ``(B, V) -> SparseRep``, or None when
    both rep knobs are off (dense output)."""
    if not spec.sparse_reps:
        return None
    # lazy: keep core importable without pulling the retrieval package
    from repro.retrieval.sparse_rep import (sparsify_threshold,
                                            sparsify_topk)

    if spec.rep_topk is not None:
        topk, thr = spec.rep_topk, spec.rep_threshold or 0.0
        return lambda y: sparsify_topk(y, topk, threshold=thr)
    threshold, max_nnz = spec.rep_threshold, spec.rep_max_nnz
    return lambda y: sparsify_threshold(y, threshold, max_nnz=max_nnz)


def make_encoder(
    spec: HeadSpec,
    mesh: Optional[Mesh] = None,
    *,
    axis_name: str = "model",
    batch_axes: Tuple[str, ...] = ("pod", "data"),
) -> Callable[..., "object"]:
    """Head + fused rep sparsifier: the post-head currency seam.

    Returns ``encode(H, E, b=None, mask=None)`` producing a
    ``SparseRep`` when the spec's ``rep_topk``/``rep_threshold`` knobs
    are set, else the dense ``(B, V)`` array (identical to
    ``make_head`` — the tested fallback). The sparsifier runs on the
    head output *before* any host transfer, so a sparse encoder never
    ships more than ``(B, K)`` per batch.
    """
    head = make_head(spec, mesh=mesh, axis_name=axis_name,
                     batch_axes=batch_axes)
    sparsify = make_sparsifier(spec)
    if sparsify is None:
        return head

    def encode(H, E, b=None, mask=None):
        y = head(H, E, b, mask)
        with jax.named_scope("sparsify"):
            return sparsify(y)

    return encode
