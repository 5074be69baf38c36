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
* the fused impact scorer, f32 and u4 variants, at a serving window;
* the backbone's fused attention, forward and backward, at the encoders'
  widths (12 heads of 64), Moonlight's latent attention (16 heads,
  q/k 192 and v 128 wide, causal) and a GQA decoder's (24 query heads
  over 8 kv heads of 128), at documents' and queries' lengths; and
  the instruction names and scopes its calls carry in a model's step.

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
from repro.kernels.attention import fused_attention
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


# (H, KV, dqk, dv, causal)
ATTENTION_WIDTHS = {"encoder": (12, 12, 64, 64, False),
                    "mla": (16, 16, 192, 128, True),
                    "gqa": (24, 8, 128, 128, True)}


@pytest.mark.parametrize("width", list(ATTENTION_WIDTHS))
@pytest.mark.parametrize("seq", [256, 32])
@pytest.mark.parametrize("backward", [False, True])
def test_fused_attention_compiles_for_v5e(one_chip, width, seq, backward):
    H, KV, dqk, dv, causal = ATTENTION_WIDTHS[width]

    def fwd(q, k, v, mask):
        return fused_attention(q, k, v, mask, causal, False)

    def loss(q, k, v, mask):
        return jnp.sum(fwd(q, k, v, mask).astype(jnp.float32))

    fn = jax.grad(loss, (0, 1, 2)) if backward else fwd
    compiled = jax.jit(fn).lower(
        _sds((B, seq, H, dqk), jnp.bfloat16, one_chip),
        _sds((B, seq, KV, dqk), jnp.bfloat16, one_chip),
        _sds((B, seq, KV, dv), jnp.bfloat16, one_chip),
        _sds((B, seq), jnp.int32, one_chip)).compile()
    # fwd alone, or fwd + dQ + dK/dV
    assert compiled.as_text().count("tpu_custom_call") >= (
        3 if backward else 1)


@pytest.mark.parametrize("arch,scopes", [
    ("splade_bert", ("backbone", "attention")),
    ("moonshot_v1_16b", ("backbone", "mla", "attention"))])
def test_fused_attention_calls_carry_the_model_scopes(one_chip, monkeypatch,
                                                      arch, scopes):
    """In a model's forward and backward on the chip every attention
    call is the kernel, named ``attention_fwd``, ``attention_dq`` or
    ``attention_dkv``, and its ``op_name`` holds the scopes that the
    benchmark's readers take it under."""
    import dataclasses
    import re

    from repro.configs import get_config
    from repro.models import attention as attn
    from repro.models import transformer as tfm

    # the chip's answer, on a process whose backend is the CPU
    monkeypatch.setattr(attn, "interpret_mode", lambda: False)
    cfg = dataclasses.replace(get_config(arch).SMOKE, remat=True)
    params = jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, one_chip),
        jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg)))
    toks = _sds((8, S), jnp.int32, one_chip)

    def loss(p, t, m):
        hidden, aux = tfm.forward_hidden(p, cfg, t, m)
        return jnp.sum(hidden.astype(jnp.float32)) + aux

    text = jax.jit(jax.grad(loss)).lower(params, toks, toks).compile() \
        .as_text()
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "op_name" in line]
    names = {re.match(r"\s*(?:ROOT\s+)?%?([a-z_]+)", c).group(1)
             for c in calls}
    assert names == {"attention_fwd", "attention_dq", "attention_dkv"}
    for call in calls:
        op_name = re.search(r'op_name="([^"]*)"', call).group(1)
        assert all(f"/{s}/" in op_name or f"({s})" in op_name
                   for s in scopes), op_name


def test_data_parallel_step_runs_the_kernel_on_local_rows(topo, monkeypatch):
    """A data-parallel train step on four chips: the partitioner cannot
    split a Mosaic kernel, so the attention kernel runs in a shard_map,
    on each chip's quarter of the rows."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.launch.steps import build_lsr_train_step, init_state
    from repro.models import attention as attn

    monkeypatch.setattr(attn, "interpret_mode", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    cfg = dataclasses.replace(get_config("splade_bert").SMOKE,
                              head_impl="jax")
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    state = jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, rep),
        jax.eval_shape(lambda: init_state(
            "splade_bert", jax.random.PRNGKey(0), smoke=True)[0]))
    batch = {k: _sds((16, S), jnp.int32, rows)
             for k in ("q_tokens", "q_mask", "d_tokens", "d_mask")}
    step = jax.jit(build_lsr_train_step(cfg, mesh, n_pairs=16))
    text = step.lower(state, batch).compile().as_text()
    kernels = [line for line in text.splitlines()
               if "custom_call_target=\"tpu_custom_call\"" in line
               and "attention_fwd" in line]
    assert kernels
    # each chip's call takes its own 4 of the 16 rows
    assert all("bf16[4,256," in line for line in kernels)
