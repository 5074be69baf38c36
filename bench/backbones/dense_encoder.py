"""The dense bidirectional encoder of ``repro.models.transformer``: BERT-
and XLM-R-sized SPLADE encoders.

Layout, as the program's transformer takes it: stacked layers, each
RMSNorm, multi-head attention with RoPE and a key padding mask, a
residual, RMSNorm, a SwiGLU FFN and a residual; a final RMSNorm; the
embedding tied as the SPLADE head's ``E``. Every matrix is normal with a
1/sqrt(fan-in) scale, as in the program's own initializer. The head bias
is a constant from the configuration file: with random weights it sets
how many vocabulary terms a representation activates (see the
configuration's ``assumed``).

The plain reference here is written from those equations and imports
nothing of the program; the SPLADE head, the contrastive loss and AdamW
it shares with every backbone (``bench.reference``).

Model FLOPs (``bench.work`` has the head's): ``6 * P * T`` a training
step for the layers' matrices (P parameters, T real tokens), attention
over each sequence's real length (``4 * n^2 * D`` a layer forward, three
times that with the backward), the head forward and backward, and the
in-batch score matrix of InfoNCE. Recomputation does not count.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, weights, work

# configuration-file size keys (as published) -> the program's fields
SIZE_FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
               "num_attention_heads": "n_heads",
               "intermediate_size": "d_ff", "vocab_size": "vocab_size"}

SMALL = {"hidden_size": 64, "num_hidden_layers": 2,
         "num_attention_heads": 4, "intermediate_size": 128,
         "vocab_size": 512}


def program_config(config: Dict):
    """The program's ``TransformerConfig`` for a configuration file."""
    from repro.configs import get_config

    kw = {field: config[key] for key, field in SIZE_FIELDS.items()}
    kw["n_kv_heads"] = kw["n_heads"]
    kw["d_head"] = kw["d_model"] // kw["n_heads"]
    kw.update(config["run"])
    return dataclasses.replace(get_config(config["arch"]).CONFIG, **kw)


def sizes(config: Dict) -> Dict:
    """The model sizes of a configuration file, by short names."""
    D, H = config["hidden_size"], config["num_attention_heads"]
    return {"L": config["num_hidden_layers"], "D": D, "H": H,
            "dh": D // H, "F": config["intermediate_size"],
            "V": config["vocab_size"]}


# -- weights ---------------------------------------------------------------

def _params(key, L, D, H, dh, F, V, head_bias):
    ks = jax.random.split(key, 8)
    n = lambda k, shape, fan_in: (jax.random.normal(k, shape, jnp.float32)
                                  * fan_in ** -0.5)
    return {
        "embed": n(ks[0], (V, D), D),
        "layers": {
            "attn": {"wq": n(ks[1], (L, D, H * dh), D),
                     "wk": n(ks[2], (L, D, H * dh), D),
                     "wv": n(ks[3], (L, D, H * dh), D),
                     "wo": n(ks[4], (L, H * dh, D), H * dh)},
            "mlp": {"w_gate": n(ks[5], (L, D, F), D),
                    "w_up": n(ks[6], (L, D, F), D),
                    "w_down": n(ks[7], (L, F, D), F)},
            "ln1": jnp.ones((L, D), jnp.float32),
            "ln2": jnp.ones((L, D), jnp.float32),
        },
        "final_norm": jnp.ones((D,), jnp.float32),
        "lm_head": {"b": jnp.full((V,), head_bias, jnp.float32)},
    }


_STATIC = ("L", "D", "H", "dh", "F", "V", "head_bias")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _init_params(key, *, L, D, H, dh, F, V, head_bias):
    return _params(key, L, D, H, dh, F, V, head_bias)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _init_state(key, *, L, D, H, dh, F, V, head_bias):
    return weights.train_state(_params(key, L, D, H, dh, F, V, head_bias))


def init_params(config: Dict, seed: int):
    """f32 parameters of the configuration, from the seed."""
    return _init_params(weights.seed_key(seed), **sizes(config),
                        head_bias=float(config["init"]["head_bias"]))


def init_state(config: Dict, seed: int):
    """Train state (parameters, AdamW moments at zero, step 0)."""
    return _init_state(weights.seed_key(seed), **sizes(config),
                       head_bias=float(config["init"]["head_bias"]))


# -- plain reference -------------------------------------------------------

def hidden(params, tokens, mask, *, H, eps, theta, quant):
    """Final hidden states (B, S, D) of the backbone, in float32."""
    mm, rms, rope = reference.mm, reference.rms, reference.rope
    B, S = tokens.shape
    x = params["embed"][tokens]
    D = x.shape[-1]
    dh = D // H
    key_ok = (mask > 0)[:, None, None, :]                        # (B,1,1,S)

    def layer(x, lp):
        h = rms(x, lp["ln1"], eps)
        q = rope(mm("bsd,de->bse", h, lp["attn"]["wq"], quant)
                 .reshape(B, S, H, dh), theta)
        k = rope(mm("bsd,de->bse", h, lp["attn"]["wk"], quant)
                 .reshape(B, S, H, dh), theta)
        v = mm("bsd,de->bse", h, lp["attn"]["wv"], quant).reshape(B, S, H, dh)
        s = mm("bqhd,bkhd->bhqk", q, k, quant) / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(key_ok, s, reference.NEG), axis=-1)
        o = mm("bhqk,bkhd->bqhd", p, v, quant).reshape(B, S, D)
        x = x + mm("bse,ed->bsd", o, lp["attn"]["wo"], quant)
        h = rms(x, lp["ln2"], eps)
        g = mm("bsd,df->bsf", h, lp["mlp"]["w_gate"], quant)
        u = mm("bsd,df->bsf", h, lp["mlp"]["w_up"], quant)
        x = x + mm("bsf,fd->bsd", jax.nn.silu(g) * u,
                   lp["mlp"]["w_down"], quant)
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["layers"])
    return rms(x, params["final_norm"], eps)


def encode(params, tokens, mask, *, H, eps, theta, tile, quant):
    Hs = hidden(params, tokens, mask, H=H, eps=eps, theta=theta, quant=quant)
    return reference.head(Hs, params["embed"], params["lm_head"]["b"], mask,
                          tile=tile, quant=quant)


def loss(params, batch, *, H, eps, theta, tile, quant, lambda_q, lambda_d):
    kw = dict(H=H, eps=eps, theta=theta, tile=tile, quant=quant)
    yq = encode(params, batch["q_tokens"], batch["q_mask"], **kw)
    yd = encode(params, batch["d_tokens"], batch["d_mask"], **kw)
    return reference.contrastive(yq, yd, quant=quant, lambda_q=lambda_q,
                                 lambda_d=lambda_d)


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
    params, mu, nu, clipped = reference.adamw(params, grads, mu, nu, step,
                                              hp)
    return params, mu, nu, value, reference.leaf_norms(clipped)


@functools.partial(jax.jit, static_argnames=("config_items",))
def _change_norms(params, key, *, config_items):
    config = dict(config_items)
    p0 = _params(key, **dict(config["sizes"]), head_bias=config["head_bias"])
    return reference.leaf_norms(jax.tree.map(lambda a, b: a - b, params, p0))


def change_norms(params, config: Dict, seed: int) -> np.ndarray:
    """Per-leaf norm of ``params`` minus the seed's initial weights; the
    initial weights are made again inside the call, never kept."""
    items = (("sizes", tuple(sizes(config).items())),
             ("head_bias", float(config["init"]["head_bias"])))
    return np.asarray(_change_norms(params, weights.seed_key(seed),
                                    config_items=items))


def train_readings(config: Dict, seed: int, batches: Sequence[Dict],
                   quant: bool = False) -> Dict:
    """The reference's readings over the given first batches: each
    step's loss, the clipped first gradient's leaf norms, and the leaf
    norms of the parameters' change after the last step."""
    run, hp = config["run"], config["train"]
    params = init_params(config, seed)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    names = reference.leaf_names(params)
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


def encode_readings(config: Dict, seed: int, tokens: np.ndarray,
                    mask: np.ndarray, indices: np.ndarray, *, block: int,
                    quant: bool = False) -> Dict:
    """For each row: the reference's weights at the given term ids and its
    own K largest weights with their ids (K = the ids' width)."""
    k = indices.shape[1]
    params = init_params(config, seed)
    kw = _model_kw(config, quant)
    at, top_v, top_i = [], [], []
    for r in range(0, tokens.shape[0], block):
        y, vals, idx = _encode_topk(params, jnp.asarray(tokens[r:r + block]),
                                    jnp.asarray(mask[r:r + block]), k=k, **kw)
        at.append(np.asarray(reference.at(y, jnp.asarray(
            indices[r:r + block]))))
        top_v.append(np.asarray(vals))
        top_i.append(np.asarray(idx))
    return {"at": np.concatenate(at), "values": np.concatenate(top_v),
            "indices": np.concatenate(top_i)}


# -- work ------------------------------------------------------------------

def transformer_params(s: Dict) -> int:
    """Matrix parameters of the layers (the norms' scales excluded)."""
    L, D, H, dh, F = (s[k] for k in ("L", "D", "H", "dh", "F"))
    return L * (4 * D * H * dh + 3 * D * F)


def _attention_fwd(lengths: Sequence[int], s: Dict) -> float:
    n2 = float(np.sum(np.square(np.asarray(lengths, np.float64))))
    return 4.0 * n2 * s["H"] * s["dh"] * s["L"]


def encode_flops(lengths: Sequence[int], s: Dict) -> float:
    """Model FLOPs of encoding sequences of these real lengths."""
    T = float(np.sum(lengths))
    return (2.0 * transformer_params(s) * T + _attention_fwd(lengths, s)
            + 2.0 * T * s["V"] * s["D"])


def train_step_flops(q_lengths: Sequence[int], d_lengths: Sequence[int],
                     s: Dict) -> float:
    """Model FLOPs of one (query, document) contrastive step."""
    P, V, D = transformer_params(s), s["V"], s["D"]
    total = 0.0
    for lengths in (q_lengths, d_lengths):
        T = float(np.sum(lengths))
        total += 6.0 * P * T + 3.0 * _attention_fwd(lengths, s)
        total += 2.0 * T * V * D + 4.0 * len(lengths) * V * D
    B = len(q_lengths)
    return total + 6.0 * B * B * V


def step_work(config: Dict, batch: Dict[str, np.ndarray]) -> Dict:
    """Work of one training step: the head's kernels and the model
    FLOPs."""
    s = sizes(config)
    fwd, dh, de = work.head_train(batch, s["V"], s["D"])
    flops = train_step_flops(batch["q_mask"].sum(1), batch["d_mask"].sum(1),
                             s)
    return {"head_fwd": fwd, "head_dh": dh, "head_de": de,
            "model_flops": flops}


def encode_work(config: Dict, batches: Sequence[Dict[str, np.ndarray]]
                ) -> Dict:
    """Work of encoding the batches: the head's forward and the model
    FLOPs."""
    s = sizes(config)
    lengths = np.concatenate([b["mask"].sum(axis=1) for b in batches])
    return {"head_fwd": work.head_encode(batches, s["V"], s["D"]),
            "model_flops": encode_flops(lengths, s)}
