"""What every backbone's seeded weights share (``bench/backbones/``).

The benchmark makes the weights itself, on the device in one jitted
call, in the layout the program takes, so that the plain reference can
make the same weights again from the same seed and never takes anything
the program made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def train_state(params):
    """The program's train state for ``params``: AdamW moments at zero,
    step 0 (call it inside the backbone's jitted initializer)."""
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)
    return {"params": params, "opt": {"mu": zeros(), "nu": zeros()},
            "step": jnp.zeros((), jnp.int32)}
