"""Unified head API: HeadSpec + registry + mesh-aware make_head.

Covers the single dispatch seam (DESIGN.md §6): every registered
backend agrees with the naive oracle through the same factory call,
the registry is the live impl enumeration (``lm_head``'s error lists
it dynamically), the deprecated ``softcap=`` spelling warns, and — the
ROADMAP item this API unblocked — the Pallas kernel runs inside the
vocab-sharded shard_map body with blocks resolved per *local* shard.
"""

import os
import subprocess
import sys
import textwrap
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.head_api import (HeadSpec, available_impls, get_head_impl,
                                 make_head, register_head_impl)
from repro.core.lm_head import lm_head, lm_head_naive


def _inputs(B=3, S=20, D=16, V=64, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    H = jax.random.normal(ks[0], (B, S, D))
    E = jax.random.normal(ks[1], (V, D)) * 0.3
    b = jax.random.normal(ks[2], (V,)) * 0.1
    mask = (jax.random.uniform(ks[3], (B, S)) > 0.25).astype(jnp.int32)
    mask = mask.at[:, 0].set(1)
    return H, E, b, mask


def _spec(impl, **kw):
    # small pinned blocks so the kernel's interpret-mode grid stays tiny
    return HeadSpec(impl=impl, vocab_tile=16, interpret=True,
                    block_b=1, block_s=16, block_v=32, **kw)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_builtin_impls_registered():
    assert {"naive", "tiled", "sparton", "kernel"} <= set(available_impls())


def test_register_custom_impl_and_dynamic_error():
    name = "doubled-naive"
    try:
        register_head_impl(
            name, lambda H, E, b, mask, *, spec:
            2.0 * lm_head_naive(H, E, b, mask,
                                logit_softcap=spec.logit_softcap))
        assert name in available_impls()
        H, E, b, mask = _inputs()
        y = make_head(HeadSpec(impl=name))(H, E, b, mask)
        np.testing.assert_allclose(
            np.asarray(y), 2.0 * np.asarray(lm_head_naive(H, E, b, mask)),
            atol=1e-6)
        # lm_head dispatches through the registry too — and its error
        # message enumerates the live registry, not a stale list
        y2 = lm_head(H, E, b, mask, impl=name)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y2), atol=0)
        with pytest.raises(ValueError, match=name):
            lm_head(H, E, b, mask, impl="no-such-impl")
    finally:
        from repro.core import head_api
        head_api._REGISTRY.pop(name, None)


def test_kernel_in_user_facing_enumeration():
    assert "kernel" in available_impls()
    H, E, b, mask = _inputs()
    y = lm_head(H, E, b, mask, impl="kernel", interpret=True,
                block_b=1, block_s=32, block_v=32)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(lm_head_naive(H, E, b, mask)),
                               atol=1e-5, rtol=1e-5)


def test_get_head_impl_unknown_lists_registry():
    with pytest.raises(ValueError) as ei:
        get_head_impl("bogus")
    for name in available_impls():
        assert name in str(ei.value)


# ---------------------------------------------------------------------------
# one factory, every backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["naive", "tiled", "sparton", "kernel"])
@pytest.mark.parametrize("softcap", [None, 4.0])
def test_all_impls_match_naive_through_factory(impl, softcap):
    H, E, b, mask = _inputs(seed=3)
    y_ref = lm_head_naive(H, E, b, mask, logit_softcap=softcap)
    head = make_head(_spec(impl, logit_softcap=softcap))
    y = head(H, E, b, mask)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("impl", ["sparton", "kernel"])
def test_factory_grads_match_naive(impl):
    H, E, b, mask = _inputs(seed=7)
    head = make_head(_spec(impl, logit_softcap=3.0))

    def loss(fn):
        return lambda H, E, b: jnp.sum(fn(H, E, b, mask) ** 2)

    g = jax.grad(loss(head), argnums=(0, 1, 2))(H, E, b)
    g_ref = jax.grad(
        loss(lambda H, E, b, m: lm_head_naive(H, E, b, m,
                                              logit_softcap=3.0)),
        argnums=(0, 1, 2))(H, E, b)
    for a, c in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   atol=1e-4, rtol=1e-4)


def test_default_b_mask_and_out_dtype():
    H, E, _, _ = _inputs()
    head = make_head(_spec("sparton", out_dtype="bfloat16"))
    y = head(H, E)
    assert y.dtype == jnp.bfloat16
    y_ref = lm_head_naive(H, E)
    np.testing.assert_allclose(np.asarray(y, dtype=np.float32),
                               np.asarray(y_ref), atol=2e-2, rtol=2e-2)


# ---------------------------------------------------------------------------
# kwarg normalization / deprecation shims
# ---------------------------------------------------------------------------

def test_sparton_head_softcap_kwarg_deprecated():
    from repro.kernels.ops import sparton_head

    H, E, b, mask = _inputs()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        y_dep = sparton_head(H, E, b, mask, block_b=1, block_s=32,
                             block_v=32, softcap=4.0, interpret=True)
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    y = sparton_head(H, E, b, mask, block_b=1, block_s=32, block_v=32,
                     logit_softcap=4.0, interpret=True)
    np.testing.assert_allclose(np.asarray(y_dep), np.asarray(y), atol=0)
    with pytest.raises(ValueError, match="conflicting"):
        sparton_head(H, E, b, mask, logit_softcap=2.0, softcap=4.0,
                     interpret=True)


def test_lm_head_softcap_kwarg_deprecated():
    H, E, b, mask = _inputs()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        y_dep = lm_head(H, E, b, mask, impl="naive", softcap=4.0)
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    np.testing.assert_allclose(
        np.asarray(y_dep),
        np.asarray(lm_head_naive(H, E, b, mask, logit_softcap=4.0)),
        atol=0)


def test_kernel_interpret_default_follows_backend(monkeypatch):
    """interpret=None: interpreted on the CPU, compiled on a TPU, and an
    error on any other backend — never a silent interpreter fallback."""
    from repro.kernels import _common

    H, E, b, mask = _inputs()
    auto = make_head(_spec("kernel").replace(interpret=None))
    np.testing.assert_allclose(np.asarray(auto(H, E, b, mask)),
                               np.asarray(lm_head_naive(H, E, b, mask)),
                               atol=1e-5, rtol=1e-5)
    assert _common.interpret_mode() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _common.interpret_mode() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        _common.interpret_mode()


# ---------------------------------------------------------------------------
# config -> spec
# ---------------------------------------------------------------------------

def test_config_head_spec_translation():
    from repro.configs.base import TransformerConfig

    cfg = TransformerConfig(
        name="t", family="dense", n_layers=1, d_model=8, n_heads=2,
        n_kv_heads=2, d_head=4, d_ff=16, vocab_size=64,
        final_logit_softcap=30.0, head_block_b=2, head_vocab_tile=128)
    spec = cfg.head_spec()
    assert spec.impl == "sparton"          # "jax" is the legacy alias
    assert spec.logit_softcap == 30.0
    assert spec.block_b == 2 and spec.block_s is None
    assert spec.vocab_tile == 128
    assert cfg.head_spec(impl="kernel").impl == "kernel"
    import dataclasses
    assert dataclasses.replace(cfg, head_impl="kernel").head_spec().impl \
        == "kernel"


# ---------------------------------------------------------------------------
# sharded: the Pallas kernel inside the shard_map body
# ---------------------------------------------------------------------------

_SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ["SPARTON_AUTOTUNE_CACHE"] = os.path.join(
        os.environ.get("TMPDIR", "/tmp"), "sparton_headapi_test.json")
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.head_api import HeadSpec, make_head
    from repro.core.lm_head import lm_head_naive
    import repro.kernels.autotune as autotune

    assert jax.device_count() >= 2, jax.devices()

    B, S, D, V = 4, 24, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    H = jax.random.normal(ks[0], (B, S, D))
    E = jax.random.normal(ks[1], (V, D)) * 0.3
    b = jax.random.normal(ks[2], (V,)) * 0.1
    mask = (jax.random.uniform(ks[3], (B, S)) > 0.2).astype(jnp.int32)
    mask = mask.at[:, 0].set(1)

    # spy on block resolution: the kernel must be keyed on the LOCAL
    # vocab shard, not the global V
    seen_V = []
    _orig = autotune.resolve_blocks
    def _spy(B_, S_, D_, V_, dtype, bb, bs, bv, **kw):
        seen_V.append(V_)
        return _orig(B_, S_, D_, V_, dtype, bb, bs, bv, **kw)
    autotune.resolve_blocks = _spy

    E_all, b_all = E, b
    # V=63 on two shards: the vocab is padded to whole shards, never
    # demoted to the pure-JAX head
    for n_model, softcap, V in [(1, None, 64), (2, None, 64),
                                (2, 4.0, 64), (2, None, 63)]:
        E, b = E_all[:V], b_all[:V]
        mesh = jax.make_mesh(
            (n_model,), ("model",),
            devices=jax.devices()[:n_model])
        y_ref = lm_head_naive(H, E, b, mask, logit_softcap=softcap)
        spec_k = HeadSpec(impl="kernel", interpret=True,
                          logit_softcap=softcap)
        spec_s = HeadSpec(impl="sparton", vocab_tile=16,
                          logit_softcap=softcap)
        head_k = make_head(spec_k, mesh=mesh, batch_axes=())
        head_s = make_head(spec_s, mesh=mesh, batch_axes=())

        seen_V.clear()
        with jax.set_mesh(mesh):
            y_k = jax.jit(head_k)(H, E, b, mask)
            y_s = jax.jit(head_s)(H, E, b, mask)
        assert seen_V and all(v == -(-V // n_model) for v in seen_V), \\
            (n_model, seen_V)
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_s),
                                   atol=1e-5, rtol=1e-5)

        def loss(fn):
            return lambda H, E, b: jnp.sum(jnp.sin(fn(H, E, b, mask)))
        with jax.set_mesh(mesh):
            g_k = jax.jit(jax.grad(loss(head_k), (0, 1, 2)))(H, E, b)
            g_s = jax.jit(jax.grad(loss(head_s), (0, 1, 2)))(H, E, b)
        g_ref = jax.grad(
            loss(lambda H, E, b, m=mask: lm_head_naive(
                H, E, b, m, logit_softcap=softcap)), (0, 1, 2))(H, E, b)
        for a, c in zip(g_k, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       atol=1e-4, rtol=1e-4)
        for a, c in zip(g_k, g_s):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       atol=1e-4, rtol=1e-4)
        print(f"OK sharded kernel n_model={n_model} softcap={softcap} "
              f"V={V}")

    print("ALL_HEAD_API_SHARDED_PASSED")
""")


def test_sharded_kernel_head_subprocess():
    """make_head(spec, mesh) with impl='kernel': Pallas inside shard_map
    on 1- and 2-device meshes matches impl='sparton' and the unsharded
    naive oracle (values + grads, incl. softcap, and a vocab the mesh
    does not divide), with the autotuner keyed on the local vocab
    shard. Runs in a subprocess so the forced
    host-device count never leaks into the main pytest process."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT], env=env,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (
        f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr[-4000:]}")
    assert "ALL_HEAD_API_SHARDED_PASSED" in proc.stdout
