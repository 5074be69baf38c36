"""The plain reference against the model's equations at a small size:
its backbone against the program's in float32, its blocked head and the
head's arg-max gradient against the dense head."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, traffic
from bench.backbones import dense_encoder as dense
from bench.tests import _small

CFG = _small.config()
KW = dict(H=CFG["num_attention_heads"], eps=CFG["run"]["norm_eps"],
          theta=CFG["run"]["rope_theta"])


def _batch():
    spec = {"docs": 4, "doc": _small.DOC}
    return next(traffic.doc_batches(spec, CFG["vocab_size"], 3))


def test_backbone_matches_the_program_in_float32():
    from repro.models import transformer as tfm

    params = dense.init_params(CFG, 5)
    b = _batch()
    tok, mask = jnp.asarray(b["tokens"]), jnp.asarray(b["mask"])
    cfg = dataclasses.replace(dense.program_config(CFG),
                              compute_dtype="float32")
    with jax.default_matmul_precision("highest"):
        prog, _ = tfm.forward_hidden(params, cfg, tok, mask)
    ref = dense.hidden(params, tok, mask, quant=False, **KW)
    keep = np.asarray(b["mask"], bool)
    np.testing.assert_allclose(np.asarray(ref)[keep], np.asarray(prog)[keep],
                               rtol=2e-4, atol=2e-4)


def _dense_head(Hs, E, bias, mask):
    logits = jnp.einsum("bsd,vd->bsv", Hs, E, precision="highest") + bias
    logits = jnp.where(mask[:, :, None] > 0, logits, -1e30)
    return jnp.log1p(jnp.maximum(jnp.max(logits, axis=1), 0.0))


def test_blocked_head_and_its_gradient_match_the_dense_head():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    Hs = jax.random.normal(ks[0], (3, 7, 16))
    E = jax.random.normal(ks[1], (300, 16)) * 0.3
    bias = jax.random.normal(ks[2], (300,)) * 0.1
    mask = jnp.asarray(np.arange(7)[None] < np.array([[7], [3], [5]]),
                       jnp.int32)
    G = jax.random.normal(ks[3], (3, 300))
    blocked = lambda h, e, b: jnp.sum(
        reference.head(h, e, b, mask, tile=128, quant=False) * G)
    dense = lambda h, e, b: jnp.sum(_dense_head(h, e, b, mask) * G)
    np.testing.assert_allclose(
        reference.head(Hs, E, bias, mask, tile=128, quant=False),
        _dense_head(Hs, E, bias, mask), rtol=1e-5, atol=1e-6)
    for g_b, g_d in zip(jax.grad(blocked, (0, 1, 2))(Hs, E, bias),
                        jax.grad(dense, (0, 1, 2))(Hs, E, bias)):
        np.testing.assert_allclose(g_b, g_d, rtol=1e-4, atol=1e-5)


def test_float8_control_departs_from_the_reference():
    params = dense.init_params(CFG, 5)
    b = _batch()
    tok, mask = jnp.asarray(b["tokens"]), jnp.asarray(b["mask"])
    full = dense.hidden(params, tok, mask, quant=False, **KW)
    low = dense.hidden(params, tok, mask, quant=True, **KW)
    rel = float(jnp.linalg.norm(low - full) / jnp.linalg.norm(full))
    assert 1e-3 < rel < 0.5
