"""Moonlight-16B-A3B (DeepSeek-V3 layout) as a causal SPLADE encoder, at
one chip's share of a layer group (the configuration's ``deployment``).

Layout, as the program's transformer takes it (``repro.models``): every
layer is RMSNorm, multi-head latent attention (MLA) with a causal mask,
a residual, RMSNorm, an FFN and a residual; the first
``first_k_dense_replace`` layers have a dense SwiGLU FFN of
``intermediate_size``, the rest the MoE FFN; a final RMSNorm; an untied
SPLADE head ``E`` over this chip's slice of the vocabulary. MLA (``q``
a full projection): q = h Wq, split into 128 "nope" and 64 "rope" dims
a head; [c, k_r] = h Wkv_a, c the 512-wide latent, RMSNorm on c, k_r one
64-wide RoPE key every head shares; [k_nope, v] = c Wkv_b per head;
scores over [q_nope, rope(q_r)] . [k_nope, rope(k_r)] scaled by 192 **
-0.5. The MoE FFN: s = sigmoid(h W_router) over all the router's
experts, the top 6 of s + bias selected (the bias selects and never
weights), gates the selected s over their sum times 2.446; the output
is the held experts' SwiGLUs weighted by their gates plus the shared
experts' SwiGLU. Pad positions are not routed and do not count in the
sequence-wise balance loss (DeepSeek-V3 eq. 17-20), which the loss adds
at ``run.aux_weight``.

The plain reference here computes every held expert on every token and
weights it by its gate, zero where the expert was not selected, so it
depends on no dispatch; it runs in float32 at ``Precision.HIGHEST``,
over blocks of whole sequences (as many positions as ``reference.rows``
documents), each recomputed in its backward pass. It imports nothing of the program; the SPLADE head, the
contrastive loss and AdamW are shared (``bench.reference``). The
selection bias is held fixed: the reference trains the other leaves.

Model FLOPs (the head's are in ``bench.work``): ``6 * P * T`` a training
step over the real tokens T, P the parameters a real token passes
through at this chip's share: MLA, the dense FFN, the router, the shared
experts and the held experts at their expected load (6 selections a
token, held/routed of them land here); causal attention over each
sequence's real length, ``n (n + 1) H (nope + rope + v)`` a layer
forward and three times that with the backward; the head forward and
backward and InfoNCE's score matrix. The MoE layers' share is also
given as ``Work`` (``moe``): those FLOPs, and the bytes of their weights
read in bf16 forward and backward and their gradients written.
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

# a CPU size: one dense and one MoE layer, 8 routed experts of which 2
# are held here, small widths
SMALL = {"hidden_size": 64, "num_hidden_layers": 2,
         "first_k_dense_replace": 1, "num_attention_heads": 4,
         "num_key_value_heads": 4, "intermediate_size": 128,
         "moe_intermediate_size": 32, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "n_routed_experts": 2, "vocab_size": 512,
         "deployment": {"chips_per_layer": 4, "router_experts": 8,
                        "expert_offset": 2, "vocab_offset": 0}}


def sizes(config: Dict) -> Dict:
    """The model sizes of a configuration file, by short names."""
    dep = config["deployment"]
    return {"L": config["num_hidden_layers"],
            "Ld": config["first_k_dense_replace"],
            "D": config["hidden_size"], "H": config["num_attention_heads"],
            "r": config["kv_lora_rank"], "dn": config["qk_nope_head_dim"],
            "dr": config["qk_rope_head_dim"], "dv": config["v_head_dim"],
            "F": config["intermediate_size"],
            "Fe": config["moe_intermediate_size"],
            "Ns": config["n_shared_experts"],
            "E": dep["router_experts"], "Eh": config["n_routed_experts"],
            "lo": dep["expert_offset"], "k": config["num_experts_per_tok"],
            "V": config["vocab_size"]}


def program_config(config: Dict):
    """The program's ``TransformerConfig`` for a configuration file."""
    from repro.configs import get_config

    s = sizes(config)
    kw = dict(n_layers=s["L"], n_dense_layers=s["Ld"], d_model=s["D"],
              n_heads=s["H"], n_kv_heads=s["H"], d_head=s["dv"],
              d_ff=s["F"], moe_d_ff=s["Fe"], vocab_size=s["V"],
              n_experts=s["E"], n_experts_held=s["Eh"],
              expert_offset=s["lo"], top_k=s["k"],
              n_shared_experts=s["Ns"], router="sigmoid",
              routed_scale=config["routed_scaling_factor"],
              kv_lora_rank=s["r"], qk_nope_head_dim=s["dn"],
              qk_rope_head_dim=s["dr"], v_head_dim=s["dv"],
              rope_theta=float(config["rope_theta"]),
              norm_eps=config["rms_norm_eps"], tie_embeddings=False)
    kw.update(config["run"])
    return dataclasses.replace(get_config(config["arch"]).CONFIG, **kw)


# -- weights ---------------------------------------------------------------

def _params(key, L, Ld, D, H, r, dn, dr, dv, F, Fe, Ns, E, Eh, lo, k, V,
            head_bias, bias_std):
    ks = iter(jax.random.split(key, 24))
    n = lambda shape, fan_in: (jax.random.normal(next(ks), shape,
                                                 jnp.float32)
                               * fan_in ** -0.5)
    one = lambda *shape: jnp.ones(shape, jnp.float32)

    def group(n_layers, mlp):
        return {"attn": {"wq": n((n_layers, D, H * (dn + dr)), D),
                         "wkv_a": n((n_layers, D, r + dr), D),
                         "kv_norm": one(n_layers, r),
                         "wkv_b": n((n_layers, r, H * (dn + dv)), r),
                         "wo": n((n_layers, H * dv, D), H * dv)},
                "mlp": mlp, "ln1": one(n_layers, D), "ln2": one(n_layers, D)}

    Lm, Fs = L - Ld, Ns * Fe
    params = {
        "embed": n((V, D), D),
        "dense_layers": group(Ld, {"w_gate": n((Ld, D, F), D),
                                   "w_up": n((Ld, D, F), D),
                                   "w_down": n((Ld, F, D), F)}),
        "layers": group(Lm, {
            "router": n((Lm, D, E), D),
            "router_bias": jax.random.normal(next(ks), (Lm, E), jnp.float32)
            * bias_std,
            "w_gate": n((Lm, Eh, D, Fe), D), "w_up": n((Lm, Eh, D, Fe), D),
            "w_down": n((Lm, Eh, Fe, D), Fe),
            "shared": {"w_gate": n((Lm, D, Fs), D), "w_up": n((Lm, D, Fs), D),
                       "w_down": n((Lm, Fs, D), Fs)}}),
        "final_norm": one(D),
        "lm_head": {"E": n((V, D), D),
                    "b": jnp.full((V,), head_bias, jnp.float32)},
    }
    return params


_STATIC = ("sizes", "head_bias", "bias_std")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _init_params(key, *, sizes, head_bias, bias_std):
    return _params(key, **dict(sizes), head_bias=head_bias,
                   bias_std=bias_std)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _init_state(key, *, sizes, head_bias, bias_std):
    return weights.train_state(_params(key, **dict(sizes),
                                       head_bias=head_bias,
                                       bias_std=bias_std))


def _init_kw(config: Dict) -> Dict:
    return {"sizes": tuple(sizes(config).items()),
            "head_bias": float(config["init"]["head_bias"]),
            "bias_std": float(config["init"]["router_bias_std"])}


def init_params(config: Dict, seed: int):
    """f32 parameters of the configuration, from the seed."""
    return _init_params(weights.seed_key(seed), **_init_kw(config))


def init_state(config: Dict, seed: int):
    """Train state (parameters, AdamW moments at zero, step 0)."""
    return _init_state(weights.seed_key(seed), **_init_kw(config))


# -- plain reference -------------------------------------------------------

def mla(h, p, mask, *, H, r, dn, dr, dv, eps, theta, quant):
    """Causal MLA of one layer on normed inputs ``h`` (B, S, D)."""
    mm, rope = reference.mm, reference.rope
    B, S, _ = h.shape
    q = mm("bsd,de->bse", h, p["wq"], quant).reshape(B, S, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], theta)], -1)
    kv_a = mm("bsd,de->bse", h, p["wkv_a"], quant)
    latent = reference.rms(kv_a[..., :r], p["kv_norm"], eps)
    k_rope = rope(kv_a[..., None, r:], theta)                    # (B,S,1,dr)
    kv = mm("bsc,ce->bse", latent, p["wkv_b"], quant).reshape(
        B, S, H, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (B, S, H, dr))], -1)
    s = mm("bqhd,bkhd->bhqk", q, k, quant) / math.sqrt(dn + dr)
    pos = jnp.arange(S)
    keep = (pos[None, :] <= pos[:, None])[None, None] \
        & (mask > 0)[:, None, None, :]
    a = jax.nn.softmax(jnp.where(keep, s, reference.NEG), axis=-1)
    o = mm("bhqk,bkhd->bqhd", a, kv[..., dn:], quant).reshape(B, S, H * dv)
    return mm("bse,ed->bsd", o, p["wo"], quant)


def swiglu(h, w_gate, w_up, w_down, quant):
    mm = reference.mm
    g = mm("bsd,df->bsf", h, w_gate, quant)
    u = mm("bsd,df->bsf", h, w_up, quant)
    return mm("bsf,fd->bsd", jax.nn.silu(g) * u, w_down, quant)


def route(h, router, bias, *, k, scale, quant):
    """Sigmoid scores (B, S, E), the selected experts (B, S, k) and
    each expert's gate (B, S, E): zero where not selected."""
    s = jax.nn.sigmoid(reference.mm("bsd,de->bse", h, router, quant))
    _, idx = jax.lax.top_k(s + bias, k)
    picked = jnp.take_along_axis(s, idx, axis=-1)
    g = picked / jnp.sum(picked, axis=-1, keepdims=True) * scale
    onehot = jax.nn.one_hot(idx, s.shape[-1], dtype=jnp.float32)
    return s, idx, jnp.einsum("bsk,bske->bse", g, onehot)


def balance(s, idx, mask):
    """Per sequence, sum_i f_i P_i over the real tokens (DeepSeek-V3
    eq. 17-20): f_i = E / (k n) times the tokens that selected expert
    i, P_i the mean of its score normalised over the experts. (B,)"""
    E, k = s.shape[-1], idx.shape[-1]
    real = (mask > 0).astype(jnp.float32)
    n = jnp.maximum(real.sum(1), 1.0)[:, None]
    picks = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=2)
    f = jnp.einsum("bse,bs->be", picks, real) * (E / k) / n
    P = jnp.einsum("bse,bs->be", s / s.sum(-1, keepdims=True), real) / n
    return jnp.sum(jax.lax.stop_gradient(f) * P, axis=1)


def moe(h, p, bias, mask, *, lo, k, scale, quant):
    """This chip's part of the MoE FFN: every held expert on every
    token, weighted by its gate (zero where unselected or on a pad),
    plus the shared experts; and the balance loss per sequence."""
    mm = reference.mm
    s, idx, gate = route(h, p["router"], bias, k=k, scale=scale,
                         quant=quant)
    Eh = p["w_gate"].shape[0]
    gate = jax.lax.dynamic_slice_in_dim(gate, lo, Eh, axis=-1) \
        * (mask > 0)[..., None]
    g = mm("bsd,edf->bsef", h, p["w_gate"], quant)
    u = mm("bsd,edf->bsef", h, p["w_up"], quant)
    y = mm("bsef,efd->bsed", jax.nn.silu(g) * u, p["w_down"], quant)
    sh = p["shared"]
    out = mm("bsed,bse->bsd", y, gate, quant) \
        + swiglu(h, sh["w_gate"], sh["w_up"], sh["w_down"], quant)
    return out, balance(s, idx, mask)


def hidden(params, bias, tokens, mask, *, kw):
    """Final hidden states (B, S, D) in float32, and the balance loss
    summed over the MoE layers, per sequence (B,)."""
    a = dict(kw)
    eps, quant = a["eps"], a["quant"]
    attn_kw = {n: a[n] for n in ("H", "r", "dn", "dr", "dv", "eps",
                                 "theta", "quant")}
    x = params["embed"][tokens]

    def layer(x, lp, ffn):
        x = x + mla(reference.rms(x, lp["ln1"], eps), lp["attn"], mask,
                    **attn_kw)
        out, aux = ffn(reference.rms(x, lp["ln2"], eps), lp)
        return x + out, aux

    def dense(x, lp):
        m = lp["mlp"]
        return layer(x, lp, lambda h, _: (
            swiglu(h, m["w_gate"], m["w_up"], m["w_down"], quant),
            jnp.zeros(x.shape[0])))

    def sparse(x, lp_b):
        lp, b = lp_b
        return layer(x, lp, lambda h, _: moe(
            h, lp["mlp"], b, mask, lo=a["lo"], k=a["k"], scale=a["scale"],
            quant=quant))

    x, _ = jax.lax.scan(jax.checkpoint(dense), x, params["dense_layers"])
    x, aux = jax.lax.scan(jax.checkpoint(sparse), x,
                          (params["layers"], bias))
    return reference.rms(x, params["final_norm"], eps), aux.sum(0)


def block(params, bias, tokens, mask, *, kw):
    """Reps (B, V) of a block of sequences, and their balance loss
    summed over the sequences and the MoE layers."""
    a = dict(kw)
    Hs, aux = hidden(params, bias, tokens, mask, kw=kw)
    y = reference.head(Hs, params["lm_head"]["E"], params["lm_head"]["b"],
                       mask, tile=a["tile"], quant=a["quant"])
    return y, aux.sum()


_block = functools.partial(jax.jit, static_argnames=("kw",))(block)


def _model_kw(config: Dict, quant: bool):
    s = sizes(config)
    return tuple(sorted({
        "H": s["H"], "r": s["r"], "dn": s["dn"], "dr": s["dr"],
        "dv": s["dv"], "lo": s["lo"], "k": s["k"],
        "scale": float(config["routed_scaling_factor"]),
        "eps": float(config["rms_norm_eps"]),
        "theta": float(config["rope_theta"]),
        "tile": config["reference"]["vocab_tile"], "quant": quant}.items()))


@functools.partial(jax.jit, static_argnames=("kw",), donate_argnums=(2,))
def _add_block_grad(params, bias, acc, tokens, mask, dy, aux_ct, *, kw):
    """``acc`` plus the gradient of (reps . dy + aux_ct * balance) of one
    block."""
    _, vjp = jax.vjp(lambda p: block(p, bias, tokens, mask, kw=kw), params)
    return jax.tree.map(jnp.add, acc, vjp((dy, aux_ct))[0])


@functools.partial(jax.jit, static_argnames=("quant", "lambda_q",
                                             "lambda_d"))
def _contrastive(yq, yd, *, quant, lambda_q, lambda_d):
    return jax.value_and_grad(reference.contrastive, argnums=(0, 1))(
        yq, yd, quant=quant, lambda_q=lambda_q, lambda_d=lambda_d)


def _blocks(tokens, mask, block_tokens):
    """Blocks of whole sequences, about ``block_tokens`` positions each."""
    rows = max(1, min(tokens.shape[0], block_tokens // tokens.shape[1]))
    return rows, [(jnp.asarray(tokens[i:i + rows]),
                   jnp.asarray(mask[i:i + rows]))
                  for i in range(0, tokens.shape[0], rows)]


def loss_and_grads(params, bias, batch, *, config: Dict, quant: bool):
    """The training loss (InfoNCE and FLOPS on the reps, plus the
    balance loss at ``run.aux_weight``, per sequence, summed over the
    MoE layers, for the queries' and the documents' call) and its
    gradient. In blocks of whole sequences, as many positions as
    ``reference.rows`` documents: the reps first, then the
    loss's gradient at the reps, then each block's backward pass,
    recomputed, added into one gradient."""
    run = config["run"]
    kw = _model_kw(config, quant)
    # ``reference.rows`` documents a block, and queries of as many tokens
    block_tokens = config["reference"]["rows"] * batch["d_tokens"].shape[1]
    sides = [_blocks(batch[t], batch[m], block_tokens)
             for t, m in (("q_tokens", "q_mask"), ("d_tokens", "d_mask"))]
    reps, aux = [], 0.0
    for _, blocks in sides:
        out = [_block(params, bias, t, m, kw=kw) for t, m in blocks]
        reps.append(jnp.concatenate([y for y, _ in out]))
        aux += sum(float(a) for _, a in out)
    B = batch["q_tokens"].shape[0]
    value, dys = _contrastive(*reps, quant=quant, lambda_q=run["lambda_q"],
                              lambda_d=run["lambda_d"])
    aux_ct = jnp.asarray(run["aux_weight"] / B, jnp.float32)
    acc = jax.tree.map(jnp.zeros_like, params)
    for (rows, blocks), dy in zip(sides, dys):
        for i, (t, m) in enumerate(blocks):
            acc = _add_block_grad(params, bias, acc, t, m,
                                  dy[i * rows:(i + 1) * rows], aux_ct, kw=kw)
    return float(value) + run["aux_weight"] * aux / B, acc


def _split(params):
    """(the trained leaves, the held selection bias)."""
    mlp = dict(params["layers"]["mlp"])
    bias = mlp.pop("router_bias")
    return {**params, "layers": {**params["layers"], "mlp": mlp}}, bias


def _join(trained, bias):
    mlp = {**trained["layers"]["mlp"], "router_bias": bias}
    return {**trained, "layers": {**trained["layers"], "mlp": mlp}}


@functools.partial(jax.jit, static_argnames=("hp_items",),
                   donate_argnums=(0, 2, 3))
def _update(params, grads, mu, nu, step, *, hp_items):
    params, mu, nu, clipped = reference.adamw(params, grads, mu, nu, step,
                                              dict(hp_items))
    return params, mu, nu, reference.leaf_norms(clipped)


@functools.partial(jax.jit, static_argnames=("init_kw",))
def _change_norms(params, key, *, init_kw):
    kw = dict(init_kw)
    p0 = _params(key, **dict(kw["sizes"]), head_bias=kw["head_bias"],
                 bias_std=kw["bias_std"])
    return reference.leaf_norms(jax.tree.map(lambda a, b: a - b, params, p0))


def change_norms(params, config: Dict, seed: int) -> np.ndarray:
    """Per-leaf norm of ``params`` minus the seed's initial weights; the
    initial weights are made again inside the call, never kept."""
    return np.asarray(_change_norms(params, weights.seed_key(seed),
                                    init_kw=tuple(_init_kw(config).items())))


def train_readings(config: Dict, seed: int, batches: Sequence[Dict],
                   quant: bool = False) -> Dict:
    """The reference's readings over the given first batches: each
    step's loss, the clipped first gradient's leaf norms (of the trained
    leaves), and the leaf norms of the parameters' change after the last
    step (the held bias changes by 0). The program's compiled steps are
    dropped from jit's caches first: a loaded executable keeps the
    device memory of its temporaries, which the reference's f32
    parameters, moments and gradient need."""
    jax.clear_caches()
    params, bias = _split(init_params(config, seed))
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    names = reference.leaf_names(params)
    hp = tuple(sorted(config["train"].items()))
    losses, grad = [], None
    for i, batch in enumerate(batches):
        value, grads = loss_and_grads(params, bias, batch, config=config,
                                      quant=quant)
        params, mu, nu, g_norms = _update(params, grads, mu, nu,
                                          jnp.asarray(i, jnp.int32),
                                          hp_items=hp)
        del grads
        losses.append(value)
        if grad is None:
            grad = np.asarray(g_norms)
    del mu, nu
    full = _join(params, bias)
    change = change_norms(full, config, seed)
    return {"loss": losses, "grad": dict(zip(names, grad.tolist())),
            "change": dict(zip(reference.leaf_names(full),
                               change.tolist()))}


@functools.partial(jax.jit, static_argnames=("kw", "k"))
def _encode_topk(params, tokens, mask, *, kw, k):
    trained, bias = _split(params)
    y, _ = block(trained, bias, tokens, mask, kw=kw)
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
                                    jnp.asarray(mask[r:r + block]), kw=kw,
                                    k=k)
        at.append(np.asarray(reference.at(y, jnp.asarray(
            indices[r:r + block]))))
        top_v.append(np.asarray(vals))
        top_i.append(np.asarray(idx))
    return {"at": np.concatenate(at), "values": np.concatenate(top_v),
            "indices": np.concatenate(top_i)}


# -- work ------------------------------------------------------------------

def _mla_params(s: Dict) -> int:
    D, H, r = s["D"], s["H"], s["r"]
    return (D * H * (s["dn"] + s["dr"]) + D * (r + s["dr"])
            + r * H * (s["dn"] + s["dv"]) + H * s["dv"] * D)


def _moe_layer_params(s: Dict) -> float:
    """Matrix parameters a real token passes through in one MoE layer:
    the router, the shared experts and the held experts at their
    expected load (``k`` selections, ``Eh / E`` of them here)."""
    expert = 3 * s["D"] * s["Fe"]
    return (s["D"] * s["E"] + s["Ns"] * expert
            + expert * s["k"] * s["Eh"] / s["E"])


def active_params(s: Dict) -> float:
    """Matrix parameters a real token passes through at this chip's
    share (the norms' scales excluded)."""
    return (s["L"] * _mla_params(s) + s["Ld"] * 3 * s["D"] * s["F"]
            + (s["L"] - s["Ld"]) * _moe_layer_params(s))


def _attention_fwd(lengths: Sequence[int], s: Dict) -> float:
    n = np.asarray(lengths, np.float64)
    return (float(np.sum(n * (n + 1))) * s["H"]
            * (s["dn"] + s["dr"] + s["dv"]) * s["L"])


def moe_work(real_tokens: float, s: Dict) -> work.Work:
    """The MoE layers of a training step (forward and backward): their
    FLOPs over the real tokens, and the bytes of their weights (held and
    shared experts, router) read in bf16 forward and backward and their
    gradients written in bf16."""
    Lm = s["L"] - s["Ld"]
    expert = 3 * s["D"] * s["Fe"]
    weights_n = s["D"] * s["E"] + (s["Ns"] + s["Eh"]) * expert
    return work.Work(6.0 * real_tokens * Lm * _moe_layer_params(s),
                     float(Lm * weights_n * 3 * work.BF16))


def encode_flops(lengths: Sequence[int], s: Dict) -> float:
    """Model FLOPs of encoding sequences of these real lengths."""
    T = float(np.sum(lengths))
    return (2.0 * active_params(s) * T + _attention_fwd(lengths, s)
            + 2.0 * T * s["V"] * s["D"])


def train_step_flops(q_lengths: Sequence[int], d_lengths: Sequence[int],
                     s: Dict) -> float:
    """Model FLOPs of one (query, document) contrastive step."""
    P, V, D = active_params(s), s["V"], s["D"]
    total = 0.0
    for lengths in (q_lengths, d_lengths):
        T = float(np.sum(lengths))
        total += 6.0 * P * T + 3.0 * _attention_fwd(lengths, s)
        total += 2.0 * T * V * D + 4.0 * len(lengths) * V * D
    B = len(q_lengths)
    return total + 6.0 * B * B * V


def step_work(config: Dict, batch: Dict[str, np.ndarray]) -> Dict:
    """Work of one training step: the head's kernels, the MoE layers
    and the model FLOPs."""
    s = sizes(config)
    fwd, dh, de = work.head_train(batch, s["V"], s["D"])
    real = float(batch["q_mask"].sum() + batch["d_mask"].sum())
    flops = train_step_flops(batch["q_mask"].sum(1), batch["d_mask"].sum(1),
                             s)
    return {"head_fwd": fwd, "head_dh": dh, "head_de": de,
            "moe": moe_work(real, s), "model_flops": flops}


def encode_work(config: Dict, batches: Sequence[Dict[str, np.ndarray]]
                ) -> Dict:
    """Work of encoding the batches: the head's forward and the model
    FLOPs."""
    s = sizes(config)
    lengths = np.concatenate([b["mask"].sum(axis=1) for b in batches])
    return {"head_fwd": work.head_encode(batches, s["V"], s["D"]),
            "model_flops": encode_flops(lengths, s)}
