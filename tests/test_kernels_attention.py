"""The fused attention kernel (``kernels/attention.py``) against
``models/attention.chunked_attention``, run by the Pallas interpreter;
its live-block count; the dispatch in ``models/attention.attention``;
and the model on both paths."""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.traffic import stratified_lengths
from repro.configs import get_config
from repro.kernels.attention import (attention_live_block_share,
                                     fused_attention)
from repro.models import attention as attn
from repro.models import transformer as tfm

with open("bench/traffic/encode_docs_2560.json") as f:
    DOCS = json.load(f)["doc"]


def _qkv(B, S, H, KV, dqk, dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, S, H, dqk), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, KV, dqk), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, KV, dv), jnp.bfloat16)
    do = jax.random.normal(ks[3], (B, S, H, dv), jnp.bfloat16)
    return q, k, v, do


def _mask(S, extents, holes=()):
    mask = (np.arange(S)[None] < np.asarray(extents)[:, None])
    mask = mask.astype(np.int32)
    for r, s in holes:
        mask[r, s] = 0
    return mask


def _f32(x):
    return np.asarray(x, np.float32)


# (B, S, H, KV, dqk, dv, causal, extents, holes): extents 0, 1, a block
# edge and one either side of it, and full; KV < H; q/k width != v width;
# short rows packed four to a block (B = 4, S = 32) and not (B = 3, or
# S not dividing the block)
CASES = {
    "s32-bidir-gqa-packed": (4, 32, 4, 2, 16, 8, False, [0, 1, 17, 32],
                             ((2, 3), (3, 0))),
    "s32-causal-widths-packed": (4, 32, 4, 4, 24, 16, True, [1, 31, 32, 0],
                                 ()),
    "s32-bidir-one-row": (3, 32, 2, 2, 16, 16, False, [0, 5, 32], ()),
    "s24-causal-one-row": (4, 24, 2, 1, 16, 8, True, [0, 1, 23, 24], ()),
    "s256-bidir-edges": (5, 256, 2, 1, 16, 16, False,
                         [0, 1, 127, 129, 256], ()),
    "s256-causal-edges": (5, 256, 2, 2, 24, 8, True,
                          [1, 127, 128, 129, 256], ()),
    "s256-bidir-holes": (2, 256, 2, 2, 16, 16, False, [200, 256],
                         ((0, 0), (0, 130), (1, 5), (1, 128))),
    "s200-causal-padded": (3, 200, 4, 1, 16, 24, True, [0, 129, 200], ()),
}


@pytest.mark.parametrize("case", list(CASES))
def test_fused_attention_matches_chunked(case):
    """Forward at real positions and dQ/dK/dV under a cotangent that is
    0 at pad positions (what the model's masked head sends back), against
    chunked_attention; every output is finite, pad positions included."""
    B, S, H, KV, dqk, dv, causal, extents, holes = CASES[case]
    q, k, v, do = _qkv(B, S, H, KV, dqk, dv)
    mask = _mask(S, extents, holes)
    real = mask[:, :, None, None] > 0
    do = do * jnp.asarray(real, do.dtype)
    pos = jnp.arange(S)

    def chunked(q, k, v):
        return attn.chunked_attention(
            q, k, v, q_positions=pos, k_positions=pos,
            kv_mask=jnp.asarray(mask), causal=causal, chunk_size=128)

    def fused(q, k, v):
        return fused_attention(q, k, v, jnp.asarray(mask), causal, True)

    o_ref, vjp_ref = jax.vjp(chunked, q, k, v)
    o_ker, vjp_ker = jax.vjp(fused, q, k, v)
    assert o_ker.shape == o_ref.shape and o_ker.dtype == o_ref.dtype
    assert np.all(np.isfinite(_f32(o_ker)))
    np.testing.assert_allclose(_f32(o_ker) * real, _f32(o_ref) * real,
                               atol=8e-3, rtol=8e-3)
    for got, want in zip(vjp_ker(do), vjp_ref(do)):
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(_f32(got), _f32(want), atol=2e-2,
                                   rtol=1e-2)


def test_fused_attention_under_jit_matches_eager():
    q, k, v, _ = _qkv(2, 256, 2, 2, 16, 16)
    mask = jnp.asarray(_mask(256, [9, 256]))
    eager = fused_attention(q, k, v, mask, False, True)
    jitted = jax.jit(fused_attention, static_argnums=(4, 5))(
        q, k, v, mask, False, True)
    np.testing.assert_array_equal(_f32(eager), _f32(jitted))
    # a q block past the extent is 0, one inside it is computed
    assert not np.any(_f32(eager)[0, 128:])
    assert np.all(np.any(_f32(eager)[:, :128] != 0, axis=(2, 3)))


# ---------------------------------------------------------------------------
# live blocks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,bq,bk,causal,share", [
    # live q / kv blocks per row: 0/0, 1/1, 1/1, 2/2, 2/2 of 2 x 2
    (256, 128, 128, False, (0 + 1 + 1 + 4 + 4) / 20),
    # causal: q block 1 sees kv blocks 0 and 1, q block 0 only 0
    (256, 128, 128, True, (0 + 1 + 1 + 3 + 3) / 20),
    # q blocks of 64 (4), kv blocks of 128 (2): live q 0, 1, 2, 3, 4
    (256, 64, 128, False, (0 + 1 * 1 + 2 * 1 + 3 * 2 + 4 * 2) / 40),
    # q block i reaches kv blocks 0..(64 (i + 1) - 1) // 128
    (256, 64, 128, True, (0 + 1 + (1 + 1) + (1 + 1 + 2)
                          + (1 + 1 + 2 + 2)) / 40),
])
def test_live_block_share_hand_counted(S, bq, bk, causal, share):
    ext = [0, 1, 128, 129, 256]
    assert attention_live_block_share(ext, S, bq, bk, causal) == \
        pytest.approx(share)


@pytest.mark.parametrize("S,b", [(256, 128), (32, 32), (200, 128)])
def test_live_block_share_of_full_rows(S, b):
    n = -(-S // b)
    ext = [S] * 3
    assert attention_live_block_share(ext, S, b, b, False) == 1.0
    assert attention_live_block_share(ext, S, b, b, True) == \
        pytest.approx((n + 1) / (2 * n))


@pytest.mark.parametrize("n,causal,share", [(2560, False, 0.3294),
                                            (448, False, 0.3287),
                                            (2560, True, 0.3029)])
def test_live_block_share_of_the_traffic(n, causal, share):
    """The benchmark's document lengths at blocks of 128: about a third
    of the (row, q block, kv block) triples run."""
    ext = stratified_lengths(n, DOCS)
    assert attention_live_block_share(ext, 256, 128, 128, causal) == \
        pytest.approx(share, abs=5e-4)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def _calls(monkeypatch, on_tpu):
    """Record which path ``attention`` takes; ``on_tpu`` stands in for
    the backend's answer."""
    calls = []
    monkeypatch.setattr(attn, "interpret_mode", lambda: not on_tpu)
    monkeypatch.setattr(attn, "fused_attention",
                        lambda q, *a: calls.append("fused") or q)
    real = attn.chunked_attention
    monkeypatch.setattr(attn, "chunked_attention",
                        lambda *a, **kw: calls.append("chunked")
                        or real(*a, **kw))
    return calls


@pytest.mark.parametrize("on_tpu,kw,path", [
    (True, {}, "fused"),
    (True, {"causal": True}, "fused"),
    (True, {"logit_softcap": 50.0}, "chunked"),
    (True, {"window": 8}, "chunked"),
    (True, {"unroll": 2}, "chunked"),
    (False, {}, "chunked"),
])
def test_attention_dispatch(monkeypatch, on_tpu, kw, path):
    calls = _calls(monkeypatch, on_tpu)
    q, k, v, _ = _qkv(2, 16, 2, 2, 8, 8)
    args = dict(positions=jnp.arange(16), kv_mask=jnp.ones((2, 16)),
                causal=False)
    attn.attention(q, k, v, **{**args, **kw})
    assert calls == [path]


_ROWS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models import attention as attn

    attn._fused_applies = lambda *a: True
    mesh = jax.make_mesh((2, 1), ("data", "model"))
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (4, 32, 2, 16), jnp.bfloat16)
               for kk in ks)
    mask = (jnp.arange(32)[None] < jnp.array([[3], [32], [17], [1]]))
    mask = mask.astype(jnp.int32)
    rows = NamedSharding(mesh, P("data"))

    def f(mesh):
        return jax.jit(lambda *a: attn.attention(
            *a[:3], positions=jnp.arange(32), kv_mask=a[3], causal=True,
            mesh=mesh))

    args = [jax.device_put(x, rows) for x in (q, k, v, mask)]
    split = f(mesh)(*args)
    whole = f(None)(q, k, v, mask)
    assert split.sharding.spec[0] == "data", split.sharding
    np.testing.assert_array_equal(np.asarray(split, np.float32),
                                  np.asarray(whole, np.float32))
    print("OK")
""")


def test_kernel_runs_on_each_devices_rows():
    """On a data-parallel mesh the kernel runs inside a shard_map over
    the batch axes, each device on its own rows, and reads as on one
    device (two host devices, in a process of its own)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(root, "src"))
    p = subprocess.run([sys.executable, "-c", _ROWS_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert p.stdout.strip().endswith("OK")


# ---------------------------------------------------------------------------
# the model on both paths
# ---------------------------------------------------------------------------

def _model_inputs(cfg, extents, S, seed=3):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (len(extents), S),
                              1, cfg.vocab_size)
    mask = jnp.asarray(_mask(S, extents))
    return toks * mask, mask


@pytest.mark.parametrize("arch", ["splade_bert", "moonshot_v1_16b"])
def test_model_on_both_attention_paths(monkeypatch, arch):
    """Hidden states at real positions, the reps, a loss on them and
    every parameter's gradient read the same through the fused kernel
    (chosen by the private selector) and through chunked_attention. In
    f32, so that the two paths' bf16 roundings do not hide a fault; the
    kernel's bf16 precision is the parity test's."""
    cfg = dataclasses.replace(get_config(arch).SMOKE,
                              compute_dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    # S past one block and not a multiple of it; extents on both sides
    # of the block edge
    toks, mask = _model_inputs(cfg, [5, 128, 129, 160], 160)
    real = np.asarray(mask)[:, :, None] > 0

    def run():
        hidden, _ = tfm.forward_hidden(params, cfg, toks, mask)

        def loss(p):
            y, aux = tfm.lsr_encode(p, cfg, toks, mask)
            return jnp.mean(jnp.square(y)) + aux, y

        (value, reps), grads = jax.value_and_grad(loss, has_aux=True)(
            params)
        return hidden, reps, value, grads

    chunked = run()
    monkeypatch.setattr(attn, "_fused_applies", lambda *a: True)
    fused = run()

    np.testing.assert_allclose(_f32(fused[0]) * real,
                               _f32(chunked[0]) * real, atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(_f32(fused[1]), _f32(chunked[1]),
                               atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(float(fused[2]), float(chunked[2]),
                               rtol=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(chunked[3])
    for (path, want), got in zip(flat, jax.tree.leaves(fused[3])):
        scale = max(float(np.max(np.abs(_f32(want)))), 1e-12)
        err = float(np.max(np.abs(_f32(got) - _f32(want))))
        assert err <= 1e-4 * scale, jax.tree_util.keystr(path)
