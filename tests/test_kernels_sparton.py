"""Sparton Pallas kernel vs pure-jnp oracle: shape/dtype sweeps +
hypothesis property tests (interpret mode on CPU).

v2 coverage: scratch-accumulated forward on non-divisible shapes, bf16
inputs against the f32 oracle, the fused backward epilogue (g and db
computed in-kernel) against both the fused oracle and autograd.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import bench.traffic
from bench.traffic import stratified_lengths
from repro.core.head_api import HeadSpec, make_head
from repro.core.lm_head import lm_head_naive
from repro.kernels.ops import sparton_head, sparton_lm_head_kernel
from repro.kernels.ref import (sparton_backward_fused_ref,
                               sparton_backward_ref, sparton_forward_ref)
from repro.kernels.sparton import (_forward_call, live_tile_share,
                                   live_tiles, row_extents, sparton_forward)
from repro.kernels.sparton_bwd import sparton_backward

KEY = jax.random.PRNGKey(0)


def _inputs(B, S, D, V, dtype=jnp.float32, seed=0, mask_p=0.2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    H = jax.random.normal(ks[0], (B, S, D), dtype)
    E = jax.random.normal(ks[1], (V, D), dtype) * 0.2
    b = jax.random.normal(ks[2], (V,), jnp.float32) * 0.2
    mask = (jax.random.uniform(ks[3], (B, S)) > mask_p).astype(jnp.int32)
    # guarantee >= 1 valid position per row
    mask = mask.at[:, 0].set(1)
    return H, E, b, mask


SHAPES = [
    # (B, S, D, V, blocks)
    (1, 16, 8, 16, (1, 8, 8)),
    (4, 96, 64, 200, (2, 32, 64)),
    (3, 33, 24, 100, (2, 32, 64)),     # non-divisible everything
    (8, 128, 128, 256, (8, 128, 128)),  # exact MXU-aligned tiles
    (2, 256, 32, 512, (2, 64, 256)),
]


@pytest.mark.parametrize("B,S,D,V,blocks", SHAPES)
def test_forward_matches_oracle(B, S, D, V, blocks):
    H, E, b, mask = _inputs(B, S, D, V)
    bb, bs, bv = blocks
    y, i_max = sparton_forward(H, E, b, mask, block_b=bb, block_s=bs,
                               block_v=bv, interpret=True)
    y_ref, i_ref = sparton_forward_ref(H, E, b, mask)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(i_max), np.asarray(i_ref))


@pytest.mark.parametrize("B,S,D,V,blocks", SHAPES)
def test_forward_bf16_matches_f32_oracle(B, S, D, V, blocks):
    """bf16 H/E with f32 in-kernel accumulation vs the f32 oracle."""
    H, E, b, mask = _inputs(B, S, D, V, dtype=jnp.bfloat16, seed=1)
    bb, bs, bv = blocks
    y, i_max = sparton_forward(H, E, b, mask, block_b=bb, block_s=bs,
                               block_v=bv, interpret=True)
    assert y.dtype == jnp.float32  # accumulator dtype, not input dtype
    # oracle at f32 on the *same bf16 values* (exact upcast)
    y_ref, i_ref = sparton_forward_ref(
        H.astype(jnp.float32), E.astype(jnp.float32), b, mask)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(i_max), np.asarray(i_ref))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_forward_dtypes(dtype):
    H, E, b, mask = _inputs(2, 64, 32, 128, dtype=dtype)
    y, i_max = sparton_forward(H, E, b, mask, block_b=2, block_s=32,
                               block_v=64, interpret=True)
    y_ref, i_ref = sparton_forward_ref(H, E, b, mask)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=tol, rtol=tol)


def test_forward_softcap():
    H, E, b, mask = _inputs(2, 32, 16, 64)
    y, _ = sparton_forward(H, E, b, mask, block_b=2, block_s=16,
                           block_v=32, softcap=5.0, interpret=True)
    y_ref, _ = sparton_forward_ref(H, E, b, mask, softcap=5.0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-5)
    # capped: f(max) <= log1p(cap)
    assert float(jnp.max(y)) <= np.log1p(5.0) + 1e-6


def test_fully_masked_row_yields_zero():
    H, E, b, _ = _inputs(2, 16, 8, 32)
    mask = jnp.zeros((2, 16), jnp.int32).at[0, :].set(1)
    y, _ = sparton_forward(H, E, b, mask, block_b=2, block_s=16,
                           block_v=32, interpret=True)
    # masked row: max over -inf -> relu clamps to 0 -> log1p(0) = 0
    assert float(jnp.max(jnp.abs(y[1]))) == 0.0


def test_forward_auto_blocks():
    """block_*=None resolves through the autotuner and stays correct."""
    H, E, b, mask = _inputs(3, 40, 24, 120, seed=5)
    y, i_max = sparton_forward(H, E, b, mask, interpret=True)
    y_ref, i_ref = sparton_forward_ref(H, E, b, mask)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(i_max), np.asarray(i_ref))


@pytest.mark.parametrize("B,S,D,V,blocks", SHAPES[:4])
def test_backward_matches_fused_oracle(B, S, D, V, blocks):
    """v2 backward: raw dy + stored y in, (dH, dE, db) out — the
    activation-derivative factor is applied inside the kernels."""
    H, E, b, mask = _inputs(B, S, D, V, seed=3)
    bb, bs, bv = blocks
    y_ref, i_ref = sparton_forward_ref(H, E, b, mask)
    dy = jax.random.normal(jax.random.PRNGKey(9), (B, V))
    dH, dE, db = sparton_backward(dy, y_ref, i_ref, H, E, row_extents(mask),
                                  block_b=bb, block_s=bs, block_v=bv,
                                  interpret=True)
    dH_ref, dE_ref, db_ref = sparton_backward_fused_ref(
        dy, y_ref, i_ref, H, E)
    np.testing.assert_allclose(np.asarray(dH), np.asarray(dH_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(dE), np.asarray(dE_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(db), np.asarray(db_ref),
                               atol=1e-4, rtol=1e-4)


def test_backward_fused_factor_equals_manual_g():
    """The in-kernel g matches applying bwd_factor outside + v1-style
    contraction oracle (the refactor changed plumbing, not math)."""
    B, S, D, V = 3, 33, 24, 100
    H, E, b, mask = _inputs(B, S, D, V, seed=13)
    y_ref, i_ref = sparton_forward_ref(H, E, b, mask)
    dy = jax.random.normal(jax.random.PRNGKey(17), (B, V))
    g = jnp.where(y_ref > 0, dy * jnp.exp(-y_ref), 0.0)
    dH, dE, db = sparton_backward(dy, y_ref, i_ref, H, E, row_extents(mask),
                                  block_b=2, block_s=32, block_v=64,
                                  interpret=True)
    dH_ref, dE_ref = sparton_backward_ref(g, i_ref, H, E)
    np.testing.assert_allclose(np.asarray(dH), np.asarray(dH_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(dE), np.asarray(dE_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(db), np.asarray(jnp.sum(g, 0)),
                               atol=1e-4, rtol=1e-4)


def test_fused_db_matches_autodiff():
    """The kernel-accumulated bias grad vs autograd through the pure-JAX
    reference head (ISSUE satellite: fused-db backward vs autograd)."""
    B, S, D, V = 3, 48, 16, 96
    H, E, b, mask = _inputs(B, S, D, V, seed=7)

    def loss_kernel(b):
        y = sparton_head(H, E, b, mask, block_b=1, block_s=16,
                         block_v=32, interpret=True)
        return jnp.sum(jnp.tanh(y) * jnp.arange(V))

    def loss_ref(b):
        y, _ = sparton_forward_ref(H, E, b, mask)
        return jnp.sum(jnp.tanh(y) * jnp.arange(V))

    np.testing.assert_allclose(
        np.asarray(jax.grad(loss_kernel)(b)),
        np.asarray(jax.grad(loss_ref)(b)), atol=2e-4, rtol=2e-4)


def test_custom_vjp_grads_match_autodiff_oracle():
    B, S, D, V = 3, 48, 16, 96
    H, E, b, mask = _inputs(B, S, D, V, seed=7)

    def loss_kernel(H, E, b):
        y = sparton_head(H, E, b, mask, block_b=1, block_s=16,
                         block_v=32, interpret=True)
        return jnp.sum(jnp.sin(y))

    def loss_ref(H, E, b):
        y, _ = sparton_forward_ref(H, E, b, mask)
        return jnp.sum(jnp.sin(y))

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(H, E, b)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(H, E, b)
    for a, c in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   atol=2e-4, rtol=2e-4)


def test_custom_vjp_grads_bf16_inputs():
    """bf16 parity through the whole custom_vjp: grads come back in the
    input dtype and match the f32 oracle at bf16 resolution."""
    B, S, D, V = 2, 32, 16, 64
    H, E, b, mask = _inputs(B, S, D, V, dtype=jnp.bfloat16, seed=21)

    def loss_kernel(H, E, b):
        y = sparton_head(H, E, b, mask, block_b=2, block_s=16,
                         block_v=32, interpret=True)
        return jnp.sum(jnp.sin(y.astype(jnp.float32)))

    def loss_ref(H, E, b):
        y, _ = sparton_forward_ref(H.astype(jnp.float32),
                                   E.astype(jnp.float32), b, mask)
        return jnp.sum(jnp.sin(y))

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(H, E, b)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(H, E, b)
    assert gk[0].dtype == jnp.bfloat16 and gk[1].dtype == jnp.bfloat16
    for a, c in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(c, np.float32),
                                   atol=5e-2, rtol=5e-2)


def test_custom_vjp_grads_with_softcap():
    B, S, D, V = 2, 32, 8, 64
    H, E, b, mask = _inputs(B, S, D, V, seed=11)

    def loss_kernel(H):
        y = sparton_head(H, E, b, mask, block_b=2, block_s=16,
                         block_v=32, logit_softcap=4.0, interpret=True)
        return jnp.sum(y * y)

    def loss_ref(H):
        y, _ = sparton_forward_ref(H, E, b, mask, softcap=4.0)
        return jnp.sum(y * y)

    np.testing.assert_allclose(
        np.asarray(jax.grad(loss_kernel)(H)),
        np.asarray(jax.grad(loss_ref)(H)), atol=2e-4, rtol=2e-4)


def test_kernel_grads_match_lm_head_sparton_autograd():
    """Acceptance: sparton_lm_head_kernel grads == lm_head_sparton
    autograd to 1e-4."""
    from repro.core.lm_head import lm_head_sparton

    B, S, D, V = 4, 40, 16, 80
    H, E, b, mask = _inputs(B, S, D, V, seed=29)

    def loss_kernel(H, E, b):
        y = sparton_lm_head_kernel(H, E, b, mask, 2, 16, 32, None, True,
                                   None)
        return jnp.sum(jnp.tanh(y))

    def loss_jax(H, E, b):
        y = lm_head_sparton(H, E, b, mask, vocab_tile=32)
        return jnp.sum(jnp.tanh(y))

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(H, E, b)
    gj = jax.grad(loss_jax, argnums=(0, 1, 2))(H, E, b)
    for a, c in zip(gk, gj):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# length-ordered rows and the skipped all-pad sequence tiles
# ---------------------------------------------------------------------------

# the benchmark's document lengths, and the same scaled to S=40
with open(os.path.join(os.path.dirname(bench.traffic.__file__), "traffic",
                       "encode_docs_2560.json")) as f:
    DOCS = json.load(f)["doc"]
SMALL_DOCS = dict(DOCS, mean=round(DOCS["mean"] * 40 / DOCS["pad"]),
                  min=DOCS["min"] * 40 // DOCS["pad"], max=40, pad=40)


def _prefix_mask(lengths, S):
    return jnp.asarray(np.arange(S)[None] < np.asarray(lengths)[:, None],
                       jnp.int32)


def _ordered_mask(case):
    """(mask, blocks) of one parity case."""
    rng = np.random.default_rng(3)
    if case == "stratified":
        lengths = rng.permutation(stratified_lengths(12, SMALL_DOCS))
        return _prefix_mask(lengths, 40), (2, 8, 32)
    if case == "holes":
        mask = (rng.uniform(size=(12, 40)) > 0.4).astype(np.int32)
        mask[:, 30:] = 0
        mask[1, :] = 0
        mask[2, 5:] = 0
        return jnp.asarray(mask), (2, 8, 32)
    if case == "all_pad_row":
        lengths = rng.permutation(stratified_lengths(12, SMALL_DOCS))
        lengths[4] = 0
        return _prefix_mask(lengths, 40), (4, 8, 32)
    if case == "full":
        return jnp.ones((12, 40), jnp.int32), (2, 8, 32)
    # B and S not multiples of block_b and block_s
    lengths = rng.permutation(stratified_lengths(7, SMALL_DOCS))
    lengths = np.minimum(lengths, 37)
    return _prefix_mask(lengths, 37), (2, 16, 32)


@pytest.mark.parametrize("case", ["stratified", "holes", "all_pad_row",
                                  "full", "ragged"])
def test_length_ordered_head_parity(case):
    mask, (bb, bs, bv) = _ordered_mask(case)
    B, S = mask.shape
    D, V = 16, 64
    H, E, b, _ = _inputs(B, S, D, V, seed=31)
    kw = dict(block_b=bb, block_s=bs, block_v=bv, interpret=True)

    # skipping past each row block's extent changes no bit of the
    # forward, in the caller's order and in length order
    order = jnp.argsort(-row_extents(mask), stable=True)
    every_tile = live_tiles(jnp.full((B,), S), bb, bs)
    for rows in (jnp.arange(B), order):
        Hr, mr = H[rows], mask[rows]
        y, i_max = sparton_forward(Hr, E, b, mr, **kw)
        y_d, i_d = _forward_call(Hr, E, b, mr, every_tile, softcap=None,
                                 **kw)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_d))
        np.testing.assert_array_equal(np.asarray(i_max), np.asarray(i_d))

    def loss_kernel(H, E, b):
        y = sparton_head(H, E, b, mask, **kw)
        return jnp.sum(jnp.sin(y) * jnp.arange(V)), y

    def loss_naive(H, E, b):
        y = lm_head_naive(H, E, b, mask)
        return jnp.sum(jnp.sin(y) * jnp.arange(V)), y

    (gk, yk) = jax.grad(loss_kernel, argnums=(0, 1, 2), has_aux=True)(H, E, b)
    (gn, yn) = jax.grad(loss_naive, argnums=(0, 1, 2), has_aux=True)(H, E, b)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yn),
                               atol=1e-5, rtol=1e-5)
    for a, c in zip(gk, gn):   # dH, dE, db
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   atol=2e-4, rtol=2e-4)

    # the registry's kernel head hands rows back in the caller's order
    head = make_head(HeadSpec(impl="kernel", interpret=True))
    np.testing.assert_allclose(np.asarray(head(H, E, b, mask)),
                               np.asarray(yn), atol=1e-5, rtol=1e-5)


def test_row_extents_and_extent_table_match_brute_force():
    rng = np.random.default_rng(5)
    mask = (rng.uniform(size=(13, 45)) > 0.7).astype(np.int32)
    mask[3] = 0
    mask[7, 44] = 1
    ext = np.asarray(row_extents(jnp.asarray(mask)))
    for r in range(13):
        real = np.flatnonzero(mask[r])
        assert ext[r] == (real[-1] + 1 if real.size else 0)
    for bb, bs in [(1, 1), (2, 8), (4, 16), (8, 32), (16, 64)]:
        live = np.asarray(live_tiles(ext, bb, bs))
        n_tiles = -(-45 // bs)
        for i in range(len(live)):
            rows = mask[i * bb:(i + 1) * bb]
            # tiles at or before the block's last real position
            want = sum(rows[:, k * bs:].any() for k in range(n_tiles))
            assert live[i] == want, (bb, bs, i)


@pytest.mark.parametrize("B,S,bb,bs", [(256, 256, 16, 128), (256, 32, 64, 16),
                                       (13, 45, 8, 16), (3, 7, 2, 4)])
def test_full_mask_runs_every_tile(B, S, bb, bs):
    """A full mask leaves the tile set as it was without skipping."""
    ext = row_extents(jnp.ones((B, S), jnp.int32))
    assert live_tile_share(ext, bb, bs, S) == 1.0
    assert np.all(np.asarray(live_tiles(ext, bb, bs)) == -(-S // bs))


@pytest.mark.parametrize("n,bb,bs,share", [(256, 16, 32, 0.383),
                                           (2560, 64, 32, 0.375),
                                           (256, 16, 128, 0.562),
                                           (448, 16, 32, 0.379)])
def test_live_tile_share_of_the_traffic(n, bb, bs, share):
    """The document lengths of the benchmark's traffic, longest first:
    the share of (row block, sequence tile) pairs the kernels run."""
    ext = np.sort(stratified_lengths(n, DOCS))[::-1]
    assert live_tile_share(ext, bb, bs, 256) == pytest.approx(share,
                                                              abs=5e-4)


# ---------------------------------------------------------------------------
# property-based tests (hypothesis)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(
    B=st.integers(1, 5), S=st.integers(1, 40), D=st.integers(1, 24),
    V=st.integers(1, 70), seed=st.integers(0, 2**16),
)
def test_property_forward_equals_oracle(B, S, D, V, seed):
    H, E, b, mask = _inputs(B, S, D, V, seed=seed)
    y, _ = sparton_forward(H, E, b, mask, block_b=2, block_s=16,
                           block_v=32, interpret=True)
    y_ref, _ = sparton_forward_ref(H, E, b, mask)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-5)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_property_monotonicity_reordering(seed):
    """The paper's core identity: max_s f(l) == f(max_s l)."""
    H, E, b, mask = _inputs(2, 24, 8, 40, seed=seed)
    logits = jnp.einsum("bsd,vd->bsv", H, E) + b
    keep = mask.astype(bool)[:, :, None]
    f = lambda x: jnp.log1p(jax.nn.relu(x))
    lhs = jnp.max(jnp.where(keep, f(logits), 0.0), axis=1)
    rhs = f(jnp.max(jnp.where(keep, logits, -1e30), axis=1))
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs), atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_property_output_nonnegative_and_sparse_friendly(seed):
    H, E, b, mask = _inputs(2, 16, 8, 32, seed=seed)
    y, _ = sparton_forward(H, E, b, mask, block_b=2, block_s=16,
                           block_v=32, interpret=True)
    assert float(jnp.min(y)) >= 0.0  # log1p(relu(.)) >= 0 always
