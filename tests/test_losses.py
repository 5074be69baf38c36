"""Loss functions: InfoNCE, FLOPS, MarginMSE sanity + properties."""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.losses.contrastive import (flops_regularizer,
                                      gathered_infonce,
                                      infonce_from_scores, infonce_loss,
                                      l1_regularizer, margin_mse_loss,
                                      splade_loss)


def test_infonce_prefers_aligned_pairs():
    # orthogonal one-hot reps: perfect alignment -> low loss
    q = jnp.eye(4, 16)
    d_good = jnp.eye(4, 16) * 10
    d_bad = jnp.roll(jnp.eye(4, 16), 1, axis=0) * 10
    assert float(infonce_loss(q, d_good)) < float(infonce_loss(q, d_bad))


def test_infonce_matches_from_scores():
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    q = jax.random.normal(ks[0], (6, 32))
    d = jax.random.normal(ks[1], (6, 32))
    scores = jnp.einsum("qv,dv->qd", q, d)
    np.testing.assert_allclose(float(infonce_loss(q, d)),
                               float(infonce_from_scores(scores)),
                               atol=1e-6)


def test_flops_regularizer_prefers_sparse():
    dense = jnp.ones((8, 64))
    sparse = jnp.zeros((8, 64)).at[:, 0].set(8.0)  # same L1 per example
    assert float(flops_regularizer(sparse)) > 0
    assert float(flops_regularizer(dense)) < float(
        flops_regularizer(sparse) * 64)
    # uniform mass over dims beats concentrated mass for FLOPS
    spread = jnp.full((8, 64), 0.125)
    assert float(flops_regularizer(spread)) < float(
        flops_regularizer(sparse))


def test_margin_mse_zero_when_matching():
    q = jnp.ones((4, 8))
    dp = jnp.ones((4, 8)) * 2
    dn = jnp.ones((4, 8))
    margin = jnp.full((4,), float(jnp.sum(q[0] * (dp[0] - dn[0]))))
    assert float(margin_mse_loss(q, dp, dn, margin)) < 1e-9


def test_splade_loss_composition():
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    q = jax.nn.relu(jax.random.normal(ks[0], (4, 32)))
    d = jax.nn.relu(jax.random.normal(ks[1], (4, 32)))
    base = float(infonce_loss(q, d))
    full = float(splade_loss(q, d, lambda_q=1.0, lambda_d=1.0))
    assert full > base  # regularizers add


def test_gathered_infonce_no_axes_matches_local():
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    q = jax.random.normal(ks[0], (6, 32))
    d = jax.random.normal(ks[1], (6, 32))
    np.testing.assert_allclose(float(gathered_infonce(q, d)),
                               float(infonce_loss(q, d)), atol=1e-6)


def test_gathered_infonce_single_device_axis_matches_local():
    """Under a size-1 shard_map data axis the gathered negatives are
    exactly the local batch — loss must equal plain infonce."""
    from jax.sharding import Mesh, PartitionSpec as P

    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    q = jax.random.normal(ks[0], (8, 16))
    d = jax.random.normal(ks[1], (8, 16))
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    fn = jax.shard_map(
        lambda a, b: gathered_infonce(a, b, axis_names=("data",)),
        mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P(),
        check_vma=False)
    np.testing.assert_allclose(float(fn(q, d)),
                               float(infonce_loss(q, d)), atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_property_infonce_nonnegative_lower_bound(seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    q = jax.random.normal(ks[0], (5, 16))
    d = jax.random.normal(ks[1], (5, 16))
    # cross-entropy over 5 classes is >= 0 and finite
    l = float(infonce_loss(q, d))
    assert np.isfinite(l) and l >= 0


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**16), scale=st.floats(0.1, 10.0))
def test_property_flops_scale_quadratic(seed, scale):
    y = jax.nn.relu(jax.random.normal(jax.random.PRNGKey(seed), (6, 24)))
    r1 = float(flops_regularizer(y))
    r2 = float(flops_regularizer(y * scale))
    np.testing.assert_allclose(r2, r1 * scale ** 2, rtol=1e-4)
