"""Faults planted under a run's timed path, to show that ``correct``
catches them (``bench/tests/test_bench_faults.py``, and the readings of
``bench/calibrate.py`` on the chip). Each wraps one of the program's
builders the drivers call and returns a broken version of it."""

from __future__ import annotations

import jax.numpy as jnp


def unchanged_state(build):
    """A train step that returns the state it was given."""
    def broken(*args, **kw):
        step = build(*args, **kw)
        return lambda state, batch: (state, step(state, batch)[1])
    return broken


def half_batch(build):
    """A train step that leaves out half of the batch and takes the mean
    over the rest."""
    def broken(cfg, mesh, *, n_pairs, **kw):
        half = n_pairs // 2
        step = build(cfg, mesh, n_pairs=half, **kw)
        return lambda state, batch: step(
            state, {k: v[:half] for k, v in batch.items()})
    return broken


def altered_answer(make_encoder):
    """An encoder whose every row names a wrong term in its first slot."""
    def broken(params, cfg, **kw):
        encode = make_encoder(params, cfg, **kw)

        def wrong(tokens, mask):
            rep = encode(tokens, mask)
            idx = rep.indices.at[:, 0].set(
                (rep.indices[:, 0] + 1) % cfg.vocab_size)
            return type(rep)(rep.values, idx.astype(jnp.int32), rep.nnz)
        return wrong
    return broken
