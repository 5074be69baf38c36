"""Moonlight-16B-A3B (``bench/backbones/moonlight.py``) against its plain
reference at a CPU size, layer by layer: latent attention, the sigmoid
router and its selection bias, one MoE layer, the chip's share of a
layer, expert parallelism; the cell's configuration, work and readers.
(The train step, the encoder and the cell end to end:
``test_bench_moonlight_train.py``.)"""

import copy
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import traffic
from bench.backbones import moonlight as ml
from bench.tests import _small

CFG = _small.config("splade_moonlight")
S = ml.sizes(CFG)
HIGHEST = jax.default_matmul_precision("highest")


def _docs(n=4, seed=3):
    spec = {"docs": n, "doc": _small.DOC}
    b = next(traffic.doc_batches(spec, CFG["vocab_size"], seed))
    return jnp.asarray(b["tokens"]), jnp.asarray(b["mask"])


def _hidden(seed=0, B=4):
    _, mask = _docs(B)
    h = jax.random.normal(jax.random.PRNGKey(seed),
                          (B, mask.shape[1], S["D"]), jnp.float32)
    return h, mask


def _layer(params, i=0):
    return jax.tree.map(lambda a: a[i], params["layers"])


def test_mla_matches_the_reference():
    from repro.models.attention import mla_attention

    lp = _layer(ml.init_params(CFG, 5))
    h, mask = _hidden()
    kw = dict(eps=CFG["rms_norm_eps"], theta=float(CFG["rope_theta"]))
    with HIGHEST:
        prog = mla_attention(
            h, lp["attn"], n_heads=S["H"], kv_lora_rank=S["r"],
            qk_nope_head_dim=S["dn"], qk_rope_head_dim=S["dr"],
            v_head_dim=S["dv"], positions=jnp.arange(h.shape[1]), mask=mask,
            causal=True, rope_theta=kw["theta"], norm_eps=kw["eps"])
    ref = ml.mla(h, lp["attn"], mask, H=S["H"], r=S["r"], dn=S["dn"],
                 dr=S["dr"], dv=S["dv"], quant=False, **kw)
    np.testing.assert_allclose(prog, ref, rtol=2e-5, atol=2e-5)


def test_bias_selects_experts_but_does_not_weight_them():
    from repro.models.moe import sigmoid_route

    logits = jnp.array([[2.0, 1.9, 0.5, -1.0, -2.0, 0.0, 0.1, -0.5]])
    eye = jnp.eye(8)
    s = jax.nn.sigmoid(logits[0])
    for bias, chosen in ((jnp.zeros(8), {0, 1}),
                         (jnp.zeros(8).at[2].set(2.0), {0, 2})):
        _, idx, gates = sigmoid_route(logits, eye, bias, top_k=2,
                                      routed_scale=2.446)
        assert set(np.asarray(idx[0]).tolist()) == chosen
        want = s[idx[0]] / s[idx[0]].sum() * 2.446
        np.testing.assert_allclose(gates[0], want, rtol=1e-6)
        # the reference: the same experts, the same gates
        _, ref_idx, ref_gate = ml.route(logits[None], eye, bias, k=2,
                                        scale=2.446, quant=False)
        assert set(np.asarray(ref_idx[0, 0]).tolist()) == chosen
        np.testing.assert_allclose(ref_gate[0, 0, idx[0]], want, rtol=1e-6)
        assert float(jnp.sum(ref_gate)) == pytest.approx(2.446, rel=1e-6)


def test_router_matches_the_reference_on_random_tokens():
    from repro.models.moe import sigmoid_route

    p = _layer(ml.init_params(CFG, 5))["mlp"]
    h, _ = _hidden(1)
    with HIGHEST:
        scores, idx, gates = sigmoid_route(
            h.reshape(-1, S["D"]), p["router"], p["router_bias"],
            top_k=S["k"], routed_scale=CFG["routed_scaling_factor"])
    s, ref_idx, ref_gate = ml.route(h, p["router"], p["router_bias"],
                                    k=S["k"], quant=False,
                                    scale=CFG["routed_scaling_factor"])
    np.testing.assert_allclose(scores, s.reshape(scores.shape), rtol=1e-5)
    assert np.array_equal(np.sort(idx, 1),
                          np.sort(ref_idx.reshape(idx.shape), 1))
    np.testing.assert_allclose(
        np.take_along_axis(ref_gate.reshape(-1, S["E"]), np.asarray(idx), 1),
        gates, rtol=1e-5)


def _program_moe(h, mask, p, *, offset, n_experts):
    from repro.models.moe import shared_experts, sigmoid_moe_ffn

    with HIGHEST:
        routed, aux = sigmoid_moe_ffn(
            h, mask, p, top_k=S["k"], n_experts=n_experts, offset=offset,
            routed_scale=CFG["routed_scaling_factor"], interpret=True)
        return routed, shared_experts(h, p["shared"]), aux


def test_moe_layer_matches_the_reference():
    p = _layer(ml.init_params(CFG, 5))["mlp"]
    h, mask = _hidden(2)
    routed, shared, aux = _program_moe(h, mask, p, offset=S["lo"],
                                       n_experts=S["E"])
    ref, ref_aux = ml.moe(h, p, p["router_bias"], mask, lo=S["lo"],
                          k=S["k"], scale=CFG["routed_scaling_factor"],
                          quant=False)
    np.testing.assert_allclose(routed + shared, ref, rtol=2e-5, atol=2e-5)
    # pads are not routed: the shared experts alone reach them
    pad = np.asarray(mask) == 0
    assert pad.any() and not np.asarray(routed)[pad].any()
    assert float(aux) == pytest.approx(float(ref_aux.mean()), rel=1e-5)
    assert float(aux) > 0


def test_shares_sum_to_the_uncut_layer():
    """Four chips' shares of a layer (2 of 8 experts each), with the
    shared experts counted once, add up to the uncut reference layer."""
    uncut = copy.deepcopy(CFG)
    uncut["n_routed_experts"] = S["E"]
    uncut["deployment"] = dict(uncut["deployment"], expert_offset=0)
    p = _layer(ml.init_params(uncut, 7))["mlp"]
    h, mask = _hidden(3)
    held = CFG["n_routed_experts"]
    total, shared = 0.0, None
    for lo in range(0, S["E"], held):
        share = dict(p, **{k: p[k][lo:lo + held]
                           for k in ("w_gate", "w_up", "w_down")})
        routed, shared, _ = _program_moe(h, mask, share, offset=lo,
                                         n_experts=S["E"])
        total = total + routed
    ref, _ = ml.moe(h, p, p["router_bias"], mask, lo=0, k=S["k"],
                    scale=CFG["routed_scaling_factor"], quant=False)
    np.testing.assert_allclose(total + shared, ref, rtol=2e-5, atol=2e-5)


def test_the_cell_runs_the_registry_model():
    """The cell's program config is the registry's Moonlight with the
    cut (depth, experts held, vocabulary slice) and the run settings."""
    from repro.configs import get_config

    with open(os.path.join(_small.BENCH, "configs",
                           "splade_moonlight.json")) as f:
        config = json.load(f)
    reg = get_config("moonshot_v1_16b").CONFIG
    cell = ml.program_config(config)
    cut = {"n_layers": 5, "n_experts_held": 8, "vocab_size": 20480}
    run = {k: v for k, v in config["run"].items()}
    for field in dataclasses.fields(reg):
        got = getattr(cell, field.name)
        if field.name in cut:
            assert got == cut[field.name]
        elif field.name in run:
            assert got == run[field.name]
        else:
            assert got == getattr(reg, field.name), field.name
    pub = config["published"]
    assert (reg.n_layers, reg.n_dense_layers, reg.d_ff, reg.moe_d_ff,
            reg.n_experts, reg.top_k, reg.n_shared_experts,
            reg.kv_lora_rank, reg.qk_nope_head_dim, reg.qk_rope_head_dim,
            reg.v_head_dim, reg.vocab_size, reg.routed_scale) == (
        pub["num_hidden_layers"], pub["first_k_dense_replace"],
        pub["intermediate_size"], pub["moe_intermediate_size"],
        pub["n_routed_experts"], pub["num_experts_per_tok"],
        pub["n_shared_experts"], pub["kv_lora_rank"],
        pub["qk_nope_head_dim"], pub["qk_rope_head_dim"],
        pub["v_head_dim"], pub["vocab_size"], pub["routed_scaling_factor"])
    assert config["deployment"]["router_experts"] == pub["n_routed_experts"]


def test_step_work_counts_the_chip_share():
    config = json.load(open(os.path.join(_small.BENCH, "configs",
                                         "splade_moonlight.json")))
    spec = _small.load("traffic", "train_pairs_96")
    batch = next(traffic.pair_batches(spec, config["vocab_size"], 1))
    w = ml.step_work(config, batch)
    s = ml.sizes(config)
    # the issue's reckoning: 13.76M a layer in MLA, 82.97M in the dense
    # layer, 100.40M in a MoE layer at 8 held experts, 83.89M of vocabulary
    assert ml._mla_params(s) == pytest.approx(13.76e6, rel=1e-3)
    moe_layer = (s["D"] * s["E"] + (s["Ns"] + s["Eh"]) * 3 * s["D"] * s["Fe"]
                 + ml._mla_params(s))
    assert moe_layer == pytest.approx(100.40e6, rel=1e-3)
    real = float(batch["q_mask"].sum() + batch["d_mask"].sum())
    expert = 3 * s["D"] * s["Fe"]
    per_token = s["D"] * s["E"] + 2 * expert + expert * 6 * 8 / 64
    assert w["moe"].flops == pytest.approx(6 * real * 4 * per_token)
    assert 0 < w["moe"].flops < w["model_flops"]
    assert w["head_fwd"].flops == pytest.approx(
        2 * float(batch["q_mask"].sum() + batch["d_mask"].sum())
        * s["V"] * s["D"])


def _reduced(scopes):
    return {"scope_seconds": scopes, "window_s": 20.0, "busy_s": 19.9}


@pytest.mark.parametrize("metric,scope", [("mla_ms.train", "mla"),
                                          ("moe_ms.train", "moe")])
def test_scope_readers(metric, scope):
    from bench import run

    reader = run._load_file(os.path.join(_small.BENCH, "metrics",
                                         metric + ".py"))
    ctx = {"reduced": _reduced({scope: 2.5, "backbone": 9.0}),
           "work": {"steps": 10}, "peaks": {}, "chips": 1}
    assert reader.read(ctx) == pytest.approx(250.0)
    # a program without the scope, as the parent commit: nothing
    ctx["reduced"] = _reduced({"backbone": 9.0})
    assert reader.read(ctx) is None


def test_moe_roofline_reader():
    from bench import run, work

    reader = run._load_file(os.path.join(_small.BENCH, "metrics",
                                         "moe_roofline.train.py"))
    peaks = {"bf16_flops_per_s": 1e14, "hbm_bytes_per_s": 1e12}
    ctx = {"reduced": _reduced({"moe": 2.0}), "peaks": peaks, "chips": 1,
           "work": {"steps": 4, "moe": work.Work(5e13, 1e11)}}
    # 0.5 s of compute at the peak in 2 s of scope time
    assert reader.read(ctx) == pytest.approx(25.0)
    ctx["reduced"] = _reduced({})
    assert reader.read(ctx) is None
    ctx["reduced"] = _reduced({"moe": 2.0})
    ctx["work"] = {"steps": 4}
    assert reader.read(ctx) is None


EXPERT_PARALLEL = """
import dataclasses, json, sys
import jax, jax.numpy as jnp, numpy as np
from bench.backbones import moonlight as ml
from bench.tests import _small
from repro.models import transformer as tfm

cfg = _small.config("splade_moonlight")
cfg["n_routed_experts"] = 8
cfg["deployment"] = dict(cfg["deployment"], expert_offset=0)
pc = dataclasses.replace(ml.program_config(cfg), compute_dtype="float32")
params = ml.init_params(cfg, 5)
tok = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 512)
mask = (jnp.arange(16)[None] < jnp.array([[16], [9], [12], [5]])).astype(
    jnp.int32)
with jax.default_matmul_precision("highest"):
    one, aux1 = tfm.forward_hidden(params, pc, tok, mask)
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with jax.set_mesh(mesh):
        four, aux4 = jax.jit(lambda p, t, m: tfm.forward_hidden(
            p, pc, t, m, moe_shard=(("data",), "model")))(params, tok, mask)
gap = np.max(np.abs(np.asarray(one) - np.asarray(four)))
print(json.dumps({"h": float(gap), "aux": [float(aux1), float(aux4)]}))
"""


def test_expert_parallel_shards_sum_to_one_chip():
    """On a data=2 x model=2 mesh of host devices each expert shard
    holds 4 of 8 experts; the trunk equals one device holding all 8."""
    root = os.path.dirname(_small.BENCH)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([root, os.path.join(root, "src")]))
    p = subprocess.run([sys.executable, "-c", EXPERT_PARALLEL], cwd=root,
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["h"] < 1e-4
    assert got["aux"][1] == pytest.approx(got["aux"][0], rel=1e-5)
