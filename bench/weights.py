"""Weights made from the seed, on the device, in one jitted call.

The benchmark makes the weights itself, in the layout the program's
transformer takes (stacked layers, tied embeddings), so that the plain
reference can make the same weights again from the same seed and never
takes anything the program made. Every matrix is normal with a 1/sqrt
(fan-in) scale, as in the program's own initializer. The head bias is a
constant from the configuration file: with random weights it sets how
many vocabulary terms a representation activates (see the
configuration's ``assumed``).
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp


def sizes(config: Dict) -> Dict:
    """The model sizes of a configuration file, by short names."""
    D, H = config["hidden_size"], config["num_attention_heads"]
    return {"L": config["num_hidden_layers"], "D": D, "H": H,
            "dh": D // H, "F": config["intermediate_size"],
            "V": config["vocab_size"]}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


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


@functools.partial(jax.jit, static_argnames=("L", "D", "H", "dh", "F", "V",
                                             "head_bias"))
def _init_params(key, *, L, D, H, dh, F, V, head_bias):
    return _params(key, L, D, H, dh, F, V, head_bias)


@functools.partial(jax.jit, static_argnames=("L", "D", "H", "dh", "F", "V",
                                             "head_bias"))
def _init_state(key, *, L, D, H, dh, F, V, head_bias):
    params = _params(key, L, D, H, dh, F, V, head_bias)
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)
    return {"params": params, "opt": {"mu": zeros(), "nu": zeros()},
            "step": jnp.zeros((), jnp.int32)}


def init_params(config: Dict, seed: int):
    """f32 parameters of the configuration, from the seed."""
    return _init_params(seed_key(seed), **sizes(config),
                        head_bias=float(config["init"]["head_bias"]))


def init_state(config: Dict, seed: int):
    """Train state (parameters, AdamW moments at zero, step 0)."""
    return _init_state(seed_key(seed), **sizes(config),
                       head_bias=float(config["init"]["head_bias"]))
