"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Interpret mode cannot see what the chip's compiler refuses: blocks off
the (8, 128) tiling, layouts Mosaic cannot cast, more VMEM than the
kernel may use. These tests compile, without a chip, for a described
``v5e:2x2`` topology:

* the Sparton head, forward and forward+backward, at D=768 and the two
  vocabularies of the paper's models (V=30522, V=250002), and at
  Moonlight's D=2048 over its 20480-row vocabulary slice, with the
  blocks the autotuner picks from a cold cache;
* the MoE's grouped matmuls (megablox), forward and backward, at
  Moonlight's widths (D=2048, expert width 1408, 8 of 64 experts held);
* the fused impact scorer, f32 and u4 variants, at a serving window.

The topology is described in a fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
test runner's workers import every test file.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune
from repro.kernels.impact_score import (fused_impact_topk,
                                        fused_quantized_topk)
from repro.kernels.ops import sparton_head

D = 768
B, S = 32, 256
# a serving window: 8 queries of 64 terms over posting windows of 512
# lanes, a 2^20-doc corpus, top-10
WB, WQ, WL, WN, WK = 8, 64, 512, 1 << 20, 10


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def cold_caches(tmp_path, monkeypatch):
    """A cold autotune cache, and no persistent compilation cache: what
    is compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setenv(autotune.CACHE_ENV, str(tmp_path / "tune.json"))
    autotune.clear_cache()
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()
    autotune.clear_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("V", [30522, 250002])
@pytest.mark.parametrize("backward", [False, True])
def test_sparton_head_compiles_for_v5e(one_chip, V, backward):
    _head_compiles(one_chip, V, D, backward)


@pytest.mark.parametrize("backward", [False, True])
def test_sparton_head_compiles_at_moonlight_width(one_chip, backward):
    """D=2048 over the 20480-row vocabulary slice: the dH kernel's
    (block_b, block_s, D) scratch is 2.7x that at D=768."""
    _head_compiles(one_chip, 20480, 2048, backward)


def _head_compiles(one_chip, V, D, backward):
    def fwd(H, E, b, mask):
        return sparton_head(H, E, b, mask, out_dtype=jnp.float32)

    def loss(H, E, b, mask):
        return jnp.sum(fwd(H, E, b, mask))

    fn = jax.grad(loss, (0, 1, 2)) if backward else fwd
    compiled = jax.jit(fn).lower(
        _sds((B, S, D), jnp.bfloat16, one_chip),
        _sds((V, D), jnp.bfloat16, one_chip),
        _sds((V,), jnp.float32, one_chip),
        _sds((B, S), jnp.int32, one_chip)).compile()
    # fwd alone, or fwd + dH + dE
    assert compiled.as_text().count("tpu_custom_call") >= (
        3 if backward else 1)


@pytest.mark.parametrize("variant", ["f32", "u4"])
def test_fused_impact_compiles_for_v5e(one_chip, variant):
    block_n, block_w = autotune.get_impact_blocks(WB, WQ, WL, WN,
                                                  variant=variant)
    kw = dict(n_docs=WN, k=WK, block_n=block_n, block_w=block_w)
    if variant == "f32":
        fn = lambda w, d: fused_impact_topk(w, d, **kw)
        args = (_sds((WB, WQ * WL), jnp.float32, one_chip),
                _sds((WB, WQ * WL), jnp.int32, one_chip))
    else:
        fn = lambda *a: fused_quantized_topk(*a, **kw)
        win = _sds((WB, WQ, WL), jnp.int32, one_chip)
        col_i = _sds((WB, WQ), jnp.int32, one_chip)
        col_f = _sds((WB, WQ), jnp.float32, one_chip)
        args = (win, win, col_i, col_i, col_f, col_f, col_f)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("backward", [False, True])
def test_held_experts_compile_for_v5e(one_chip, backward):
    from repro.models.moe import held_experts

    T, Dm, F, E, held, k = 2048, 2048, 1408, 64, 8, 6

    def fwd(x, gates, idx, real, w_gate, w_up, w_down):
        return held_experts(x, idx, gates, real, w_gate, w_up, w_down,
                            n_experts=E, offset=0, interpret=False)

    def loss(x, gates, *rest):
        return jnp.sum(fwd(x, gates, *rest).astype(jnp.float32))

    fn = jax.grad(loss, (0, 1, 4, 5, 6)) if backward else fwd
    compiled = jax.jit(fn).lower(
        _sds((T, Dm), jnp.bfloat16, one_chip),
        _sds((T, k), jnp.float32, one_chip),
        _sds((T, k), jnp.int32, one_chip),
        _sds((T,), jnp.bool_, one_chip),
        _sds((held, Dm, F), jnp.float32, one_chip),
        _sds((held, Dm, F), jnp.float32, one_chip),
        _sds((held, F, Dm), jnp.float32, one_chip)).compile()
    # gate, up and down; with the backward, each one's input and weight
    # gradients too
    assert compiled.as_text().count("tpu_custom_call") >= (
        9 if backward else 3)
