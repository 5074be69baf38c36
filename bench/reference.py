"""What every backbone's plain float32 reference shares: the matrix
product with its float8 control, RMSNorm and RoPE, the SPLADE head
blocked over the vocabulary, the contrastive loss, AdamW and the leaf
norms the checks compare. Each backbone's own equations are in its
module (``bench/backbones/``). Nothing here or there imports the
program.

The SPLADE head, on the backbone's final hidden states H:

    y[b, v] = log1p(relu(max_{s: mask[b, s]} (H[b, s] . E[v] + bias[v])))

Training adds InfoNCE over in-batch negatives plus the FLOPS regularizer
on both sides, global-norm gradient clipping and AdamW with linear
warm-up and cosine decay.

Every matrix product runs at ``Precision.HIGHEST``: on a TPU a float32
product is otherwise computed in one bfloat16 pass. ``quant=True`` is the
control: every operand of every matrix product is rounded to float8
(e4m3, scaled per tensor to its largest magnitude), the next precision
below the bfloat16 the configuration computes in.

The head works in vocabulary tiles, each recomputed in the backward
pass, so that the ``(B, S, V)`` logits are never built.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30
F8_MAX = 448.0   # largest finite float8_e4m3fn


def _fq(x):
    """Round to float8 e4m3 with one scale per tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(spec, a, b, quant):
    if quant:
        a, b = _fq(a), _fq(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x (B, S, N, dh): rotate the two halves of each head by position."""
    S, dh = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs      # (S, dh/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def head(Hs, E, bias, mask, *, tile, quant):
    """SPLADE head (B, V) over vocabulary tiles of ``tile`` rows."""
    B = Hs.shape[0]
    V, D = E.shape
    pad = (-V) % tile
    Et = jnp.pad(E, ((0, pad), (0, 0))).reshape(-1, tile, D)
    bt = jnp.pad(bias, (0, pad)).reshape(-1, tile)
    keep = (mask > 0)[:, :, None]

    def one(args):
        e, b = args
        logits = jnp.where(keep, mm("bsd,vd->bsv", Hs, e, quant) + b, NEG)
        return jnp.log1p(jnp.maximum(jnp.max(logits, axis=1), 0.0))

    y = jax.lax.map(jax.checkpoint(one), (Et, bt))               # (T, B, tile)
    return jnp.moveaxis(y, 0, 1).reshape(B, -1)[:, :V]


def _flops_reg(y):
    return jnp.sum(jnp.mean(jnp.abs(y), axis=0) ** 2)


def contrastive(yq, yd, *, quant, lambda_q, lambda_d):
    """InfoNCE over in-batch negatives plus the FLOPS regularizer on
    both sides, from the query and document reps (B, V)."""
    scores = mm("qv,dv->qd", yq, yd, quant)
    logp = jax.nn.log_softmax(scores, axis=-1)
    infonce = -jnp.mean(jnp.diagonal(logp))
    return infonce + lambda_q * _flops_reg(yq) + lambda_d * _flops_reg(yd)


def _lr(step, hp):
    s = step.astype(jnp.float32) + 1.0
    warm = hp["lr"] * s / hp["warmup_steps"]
    t = jnp.clip((s - hp["warmup_steps"])
                 / (hp["total_steps"] - hp["warmup_steps"]), 0.0, 1.0)
    cos = hp["lr"] * 0.5 * (1.0 + jnp.cos(jnp.pi * t))
    return jnp.where(s < hp["warmup_steps"], warm, cos)


def adamw(params, grads, mu, nu, step, hp):
    """One AdamW step after global-norm clipping; returns the clipped
    gradient too (what the optimizer's moments are built from)."""
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, hp["max_grad_norm"] / jnp.maximum(gn, 1e-12))
    grads = jax.tree.map(lambda g: g * clip, grads)
    t = step.astype(jnp.float32) + 1.0
    lr = _lr(step, hp)
    b1, b2 = hp["b1"], hp["b2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)

    def upd(p, m, v):
        delta = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + hp["eps"])
        return p - lr * (delta + hp["weight_decay"] * p)

    return jax.tree.map(upd, params, mu, nu), mu, nu, grads


def leaf_names(tree) -> List[str]:
    paths, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in paths]


def leaf_norms(tree) -> jax.Array:
    """Euclidean norm of each leaf, in ``leaf_names`` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def at(y, idx):
    """The weights of ``y`` (B, V) at the term ids ``idx`` (B, K)."""
    return jnp.take_along_axis(y, idx, axis=1)
