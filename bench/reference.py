"""Plain float32 reference of the SPLADE encoder, its loss and AdamW.

Written from the model's equations, importing nothing of the program:
token embedding; per layer RMSNorm, bidirectional multi-head attention
with RoPE and a key padding mask, a residual, RMSNorm, a SwiGLU FFN and
a residual; a final RMSNorm; then the SPLADE head

    y[b, v] = log1p(relu(max_{s: mask[b, s]} (H[b, s] . E[v] + bias[v])))

with the embedding tied as E. Training adds InfoNCE over in-batch
negatives plus the FLOPS regularizer on both sides, global-norm gradient
clipping and AdamW with linear warm-up and cosine decay.

Every matrix product runs at ``Precision.HIGHEST``: on a TPU a float32
product is otherwise computed in one bfloat16 pass. ``quant=True`` is the
control: every operand of every matrix product is rounded to float8
(e4m3, scaled per tensor to its largest magnitude), the next precision
below the bfloat16 the configuration computes in.

The head works in vocabulary tiles, each recomputed in the backward
pass, so that the ``(B, S, V)`` logits are never built.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30
F8_MAX = 448.0   # largest finite float8_e4m3fn


def _fq(x):
    """Round to float8 e4m3 with one scale per tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, a, b, quant):
    if quant:
        a, b = _fq(a), _fq(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (B, S, N, dh): rotate the two halves of each head by position."""
    S, dh = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs      # (S, dh/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def hidden(params, tokens, mask, *, H, eps, theta, quant):
    """Final hidden states (B, S, D) of the backbone, in float32."""
    B, S = tokens.shape
    x = params["embed"][tokens]
    D = x.shape[-1]
    dh = D // H
    key_ok = (mask > 0)[:, None, None, :]                        # (B,1,1,S)

    def layer(x, lp):
        h = _rms(x, lp["ln1"], eps)
        q = _rope(_mm("bsd,de->bse", h, lp["attn"]["wq"], quant)
                  .reshape(B, S, H, dh), theta)
        k = _rope(_mm("bsd,de->bse", h, lp["attn"]["wk"], quant)
                  .reshape(B, S, H, dh), theta)
        v = _mm("bsd,de->bse", h, lp["attn"]["wv"], quant).reshape(B, S, H, dh)
        s = _mm("bqhd,bkhd->bhqk", q, k, quant) / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(key_ok, s, NEG), axis=-1)
        o = _mm("bhqk,bkhd->bqhd", p, v, quant).reshape(B, S, D)
        x = x + _mm("bse,ed->bsd", o, lp["attn"]["wo"], quant)
        h = _rms(x, lp["ln2"], eps)
        g = _mm("bsd,df->bsf", h, lp["mlp"]["w_gate"], quant)
        u = _mm("bsd,df->bsf", h, lp["mlp"]["w_up"], quant)
        x = x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u,
                    lp["mlp"]["w_down"], quant)
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["layers"])
    return _rms(x, params["final_norm"], eps)


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
        logits = jnp.where(keep, _mm("bsd,vd->bsv", Hs, e, quant) + b, NEG)
        return jnp.log1p(jnp.maximum(jnp.max(logits, axis=1), 0.0))

    y = jax.lax.map(jax.checkpoint(one), (Et, bt))               # (T, B, tile)
    return jnp.moveaxis(y, 0, 1).reshape(B, -1)[:, :V]


def encode(params, tokens, mask, *, H, eps, theta, tile, quant):
    Hs = hidden(params, tokens, mask, H=H, eps=eps, theta=theta, quant=quant)
    return head(Hs, params["embed"], params["lm_head"]["b"], mask,
                tile=tile, quant=quant)


def _flops_reg(y):
    return jnp.sum(jnp.mean(jnp.abs(y), axis=0) ** 2)


def loss(params, batch, *, H, eps, theta, tile, quant, lambda_q, lambda_d):
    kw = dict(H=H, eps=eps, theta=theta, tile=tile, quant=quant)
    yq = encode(params, batch["q_tokens"], batch["q_mask"], **kw)
    yd = encode(params, batch["d_tokens"], batch["d_mask"], **kw)
    scores = _mm("qv,dv->qd", yq, yd, quant)
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


def _adamw(params, grads, mu, nu, step, hp):
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


def _model_kw(config: Dict, quant: bool) -> Dict:
    run = config["run"]
    return dict(H=config["num_attention_heads"], eps=run["norm_eps"],
                theta=run["rope_theta"],
                tile=config["reference"]["vocab_tile"],
                quant=quant)


@functools.partial(jax.jit, static_argnames=("H", "eps", "theta", "tile",
                                             "quant", "lambda_q", "lambda_d",
                                             "hp_items"))
def _train_step(params, mu, nu, step, batch, *, H, eps, theta, tile, quant,
                lambda_q, lambda_d, hp_items):
    hp = dict(hp_items)
    value, grads = jax.value_and_grad(loss)(
        params, batch, H=H, eps=eps, theta=theta, tile=tile, quant=quant,
        lambda_q=lambda_q, lambda_d=lambda_d)
    params, mu, nu, clipped = _adamw(params, grads, mu, nu, step, hp)
    return params, mu, nu, value, leaf_norms(clipped)


@functools.partial(jax.jit, static_argnames=("config_items",))
def _change_norms(params, key, *, config_items):
    config = dict(config_items)
    p0 = weights._params(key, **dict(config["sizes"]),
                         head_bias=config["head_bias"])
    return leaf_norms(jax.tree.map(lambda a, b: a - b, params, p0))


def change_norms(params, config: Dict, seed: int) -> np.ndarray:
    """Per-leaf norm of ``params`` minus the seed's initial weights; the
    initial weights are made again inside the call, never kept."""
    items = (("sizes", tuple(weights.sizes(config).items())),
             ("head_bias", float(config["init"]["head_bias"])))
    return np.asarray(_change_norms(params, weights.seed_key(seed),
                                    config_items=items))


def train_readings(config: Dict, seed: int, batches: Sequence[Dict],
                   quant: bool = False) -> Dict:
    """The reference's readings over the given first batches: each
    step's loss, the clipped first gradient's leaf norms, and the leaf
    norms of the parameters' change after the last step."""
    run, hp = config["run"], config["train"]
    params = weights.init_params(config, seed)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    names = leaf_names(params)
    losses, grad = [], None
    for i, batch in enumerate(batches):
        params, mu, nu, value, g_norms = _train_step(
            params, mu, nu, jnp.asarray(i, jnp.int32),
            {k: jnp.asarray(v) for k, v in batch.items()},
            **_model_kw(config, quant), lambda_q=run["lambda_q"],
            lambda_d=run["lambda_d"], hp_items=tuple(sorted(hp.items())))
        losses.append(float(value))
        if grad is None:
            grad = np.asarray(g_norms)
    del mu, nu
    change = change_norms(params, config, seed)
    return {"loss": losses, "grad": dict(zip(names, grad.tolist())),
            "change": dict(zip(names, change.tolist()))}


@functools.partial(jax.jit, static_argnames=("H", "eps", "theta", "tile",
                                             "quant", "k"))
def _encode_topk(params, tokens, mask, *, H, eps, theta, tile, quant, k):
    y = encode(params, tokens, mask, H=H, eps=eps, theta=theta, tile=tile,
               quant=quant)
    vals, idx = jax.lax.top_k(y, k)
    return y, vals, idx


@jax.jit
def _at(y, idx):
    return jnp.take_along_axis(y, idx, axis=1)


def encode_readings(config: Dict, seed: int, tokens: np.ndarray,
                    mask: np.ndarray, indices: np.ndarray, *, block: int,
                    quant: bool = False) -> Dict:
    """For each row: the reference's weights at the given term ids and its
    own K largest weights with their ids (K = the ids' width)."""
    k = indices.shape[1]
    params = weights.init_params(config, seed)
    kw = _model_kw(config, quant)
    at, top_v, top_i = [], [], []
    for r in range(0, tokens.shape[0], block):
        y, vals, idx = _encode_topk(params, jnp.asarray(tokens[r:r + block]),
                                    jnp.asarray(mask[r:r + block]), k=k, **kw)
        at.append(np.asarray(_at(y, jnp.asarray(indices[r:r + block]))))
        top_v.append(np.asarray(vals))
        top_i.append(np.asarray(idx))
    return {"at": np.concatenate(at), "values": np.concatenate(top_v),
            "indices": np.concatenate(top_i)}
