"""Moonlight-16B-A3B's training path and encoder against the plain
reference at a CPU size: padding never reaches the real rows, the train
step and the config encoder match the reference in float32, the
selection bias is held, and the cell is correct where its float8
control is not."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, reference, traffic
from bench.backbones import moonlight as ml
from bench.drivers import train
from bench.tests import _small

CFG = _small.config("splade_moonlight")
HIGHEST = jax.default_matmul_precision("highest")


def _docs(n, seed):
    spec = {"docs": n, "doc": _small.DOC}
    b = next(traffic.doc_batches(spec, CFG["vocab_size"], seed))
    return jnp.asarray(b["tokens"]), jnp.asarray(b["mask"])


def _pairs(seed=11):
    spec = {"pairs": 8, "query": _small.QUERY, "doc": _small.DOC}
    b = next(traffic.pair_batches(spec, CFG["vocab_size"], seed))
    return {k: jnp.asarray(v) for k, v in b.items()}


@functools.lru_cache(maxsize=None)
def _forward(cfg):
    """The train step's forward: both sides' reps and its loss."""
    from repro.launch import steps
    from repro.losses.contrastive import splade_loss

    encode = steps._encode_fn(cfg, None, 8)

    def run(params, batch):
        yq, aux_q = encode(params, batch["q_tokens"], batch["q_mask"])
        yd, aux_d = encode(params, batch["d_tokens"], batch["d_mask"])
        return yq, yd, splade_loss(yq, yd, lambda_q=cfg.lambda_q,
                                   lambda_d=cfg.lambda_d,
                                   aux_loss=aux_q + aux_d,
                                   aux_weight=cfg.aux_weight)
    return jax.jit(run)


def _reps_and_loss(cfg, params, batch):
    yq, yd, loss = _forward(cfg)(params, batch)
    return np.concatenate([yq, yd]), float(loss)


@pytest.mark.parametrize("change", ["pad_ids", "pad_count"])
def test_padding_does_not_reach_the_real_rows(change):
    cfg = ml.program_config(CFG)
    params = ml.init_params(CFG, 5)
    batch = _pairs()
    reps, loss = _reps_and_loss(cfg, params, batch)
    if change == "pad_ids":
        other = dict(batch)
        for t, m in (("q_tokens", "q_mask"), ("d_tokens", "d_mask")):
            noise = jax.random.randint(jax.random.PRNGKey(9), batch[t].shape,
                                       0, CFG["vocab_size"])
            other[t] = jnp.where(batch[m] > 0, batch[t], noise)
        assert not np.array_equal(other["d_tokens"], batch["d_tokens"])
        reps2, loss2 = _reps_and_loss(cfg, params, other)
        assert np.array_equal(reps, reps2) and loss == loss2
    else:
        more = {k: jnp.pad(v, ((0, 0), (0, 16))) if k.startswith("d_")
                else v for k, v in batch.items()}
        reps2, loss2 = _reps_and_loss(cfg, params, more)
        assert np.array_equal(reps, reps2) and loss == loss2


def _cell(seed=2 ** 40 + 7, compute="bfloat16"):
    cell = _small.cell("train", seed=seed, name="splade_moonlight")
    cell.config["run"]["compute_dtype"] = compute
    return cell


def test_train_step_matches_train_readings_in_float32():
    cell = _cell(compute="float32")
    with HIGHEST:
        st = train.start(cell, train.build(cell))
    st.loader.close()
    ref = ml.train_readings(cell.config, cell.seed, st.batches)
    for p, r in zip(st.readings["loss"], ref["loss"]):
        assert p == pytest.approx(r, rel=1e-5)
    gaps = compare.leaf_gaps(st.readings["grad"], ref["grad"], ref["grad"])
    assert max(gaps.values()) < 1e-4, gaps
    # the selection bias: no gradient; the reference trains without it
    assert st.readings["grad"]["layers/mlp/router_bias"] == 0.0
    assert "layers/mlp/router_bias" not in ref["grad"]


def test_train_step_holds_the_selection_bias():
    """Neither the gradient nor AdamW's decay moves the bias; every
    other leaf moves."""
    from repro.launch import steps

    state = ml.init_state(CFG, 5)
    step = jax.jit(steps.build_lsr_train_step(ml.program_config(CFG), None,
                                              n_pairs=8, lr=1e-2,
                                              total_steps=10))
    new = state
    for i in range(2):
        new, _ = step(new, _pairs(seed=i))
    flags = dict(zip(reference.leaf_names(state["params"]),
                     (bool(jnp.any(a != b)) for a, b in zip(
                         jax.tree.leaves(state["params"]),
                         jax.tree.leaves(new["params"])))))
    assert flags.pop("layers/mlp/router_bias") is False
    assert all(flags.values()), flags
    mlp = new["opt"]["mu"]["layers"]["mlp"]
    assert not jnp.any(mlp["router_bias"]) and jnp.any(mlp["router"])


def test_config_encoder_matches_encode_readings():
    from repro.runtime import serving

    params = ml.init_params(CFG, 5)
    tokens, mask = _docs(8, 4)
    with HIGHEST:
        rep = serving.make_config_encoder(params, dataclasses.replace(
            ml.program_config(CFG), compute_dtype="float32"))(tokens, mask)
    values, idx = np.asarray(rep.values), np.asarray(rep.indices)
    ref = ml.encode_readings(CFG, 5, np.asarray(tokens), np.asarray(mask),
                             idx, block=CFG["reference"]["rows"])
    kept = values > 0
    assert kept.sum() > 0
    np.testing.assert_allclose(values[kept], ref["at"][kept], rtol=1e-4,
                               atol=1e-5)
    numbers = compare.encode_numbers(values, ref["at"], ref["values"])
    assert max(numbers.values()) < 1e-4


def test_the_cell_is_correct_and_its_control_is_not():
    cell = _cell()
    st = train.start(cell, train.build(cell))
    st.loader.close()
    ref = ml.train_readings(cell.config, cell.seed, st.batches)
    numbers = compare.train_numbers(st.readings, ref)
    assert compare.passed(compare.checks(numbers, cell.limits)), numbers
    ctrl = ml.train_readings(cell.config, cell.seed, st.batches, quant=True)
    bad = compare.train_numbers(ctrl, ref)
    assert not compare.passed(compare.checks(bad, cell.limits)), bad
