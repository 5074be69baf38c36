"""Functional transformer stack (params = pytrees, apply = functions).

Covers the five assigned LM-family architectures plus the paper's own
SPLADE encoders:

* dense GQA decoders (llama3.2-3b, phi3-mini),
* local/global alternating attention + logit softcaps (gemma2-27b),
* MoE trunks (phi3.5-moe: 16e top-2, softmax router),
* DeepSeek-V3-style trunks (moonshot-v1-16b-a3b): multi-head latent
  attention, a leading dense layer, then sigmoid-routed experts (64,
  top-6) with two shared experts,
* bidirectional encoders for SPLADE (bert / xlm-roberta backbones).

Layers are *stacked* (every leaf carries a leading ``n_layers`` dim)
and applied with ``lax.scan`` + optional ``jax.checkpoint`` so that the
HLO stays compact for 512-device SPMD compilation and activation
memory stays O(sqrt)-ish under remat. A config with leading dense
layers (``n_dense_layers``) stacks them apart, under
``params["dense_layers"]``: their FFN has other leaves than the MoE
layers', so each group is its own scan.

Heads:
* ``lsr_encode``     — backbone + **Sparton head** (the paper): returns
  ``(B, V)`` sparse lexical vectors.
* ``causal_lm_logits`` / decode path — standard next-token logits.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import TransformerConfig
from repro.models.attention import (apply_rope, attention,
                                    decode_attention, mla_attention,
                                    rms_norm)
from repro.models.moe import (init_moe_params, init_sigmoid_moe_params,
                             moe_ffn, moe_ffn_local_experts,
                             shared_experts, sigmoid_moe_ffn)

Array = jax.Array
MoeShard = Optional[Tuple[Tuple[str, ...], str]]  # (token_axes, expert_axis)


def _sigmoid_moe(x: Array, mask: Array, mlp: Params, cfg: TransformerConfig,
                 moe_shard: MoeShard) -> Tuple[Array, Array]:
    """DeepSeek-V3 MoE FFN on (B, S, D): the held routed experts plus
    the shared experts. On a mesh each expert shard holds its slice of
    the held experts (offset: ``expert_offset`` plus the shard's index
    times its count) and the routed parts are summed over the expert
    axis."""
    from repro.kernels._common import interpret_mode

    routed = functools.partial(
        sigmoid_moe_ffn, top_k=cfg.top_k, routed_scale=cfg.routed_scale,
        n_experts=cfg.n_experts, interpret=interpret_mode())
    if moe_shard is None:
        out, aux = routed(x, mask, mlp, offset=cfg.expert_offset)
    else:
        token_axes, expert_axis = moe_shard
        from jax.sharding import PartitionSpec as P

        def body(x, mask, mlp):
            held = mlp["w_gate"].shape[0]
            offset = cfg.expert_offset + jax.lax.axis_index(expert_axis) * held
            out, aux = routed(x, mask, mlp, offset=offset)
            if token_axes:
                aux = jax.lax.pmean(aux, token_axes)
            return jax.lax.psum(out, expert_axis), aux

        experts = P(expert_axis, None, None)
        spec = {"router": P(None, None), "router_bias": P(None),
                "w_gate": experts, "w_up": experts, "w_down": experts}
        routed_mlp = {k: mlp[k] for k in spec}
        out, aux = jax.shard_map(
            body, mesh=None,
            in_specs=(P(token_axes, None, None), P(token_axes, None), spec),
            out_specs=(P(token_axes, None, None), P()),
            # the grouped matmul's Pallas call declares no varying axes
            check_vma=False,
        )(x, mask, routed_mlp)
    return out + shared_experts(x, mlp["shared"]), aux


def _apply_moe(x2d: Array, mlp: Params, cfg: TransformerConfig,
               moe_shard: MoeShard) -> Tuple[Array, Array]:
    """MoE FFN: local (single device) or expert-parallel shard_map."""
    if moe_shard is None:
        return moe_ffn(
            x2d, mlp["router"], mlp["w_gate"], mlp["w_up"], mlp["w_down"],
            top_k=cfg.top_k, capacity_factor=cfg.capacity_factor)
    token_axes, expert_axis = moe_shard
    from jax.sharding import PartitionSpec as P
    body = functools.partial(
        moe_ffn_local_experts,
        top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
        expert_axis=expert_axis, token_axes=token_axes)
    fn = jax.shard_map(
        body, mesh=None,
        in_specs=(P(token_axes, None), P(None, None),
                  P(expert_axis, None, None), P(expert_axis, None, None),
                  P(expert_axis, None, None)),
        out_specs=(P(token_axes, None), P()),
    )
    return fn(x2d, mlp["router"], mlp["w_gate"], mlp["w_up"],
              mlp["w_down"])
Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_mla(key: jax.Array, cfg: TransformerConfig, L: int,
              dtype) -> Params:
    D, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    n = lambda k, shape, fan_in: (jax.random.normal(k, shape, dtype)
                                  * fan_in ** -0.5)
    return {"wq": n(ks[0], (L, D, H * (dn + dr)), D),
            "wkv_a": n(ks[1], (L, D, r + dr), D),
            "kv_norm": jnp.ones((L, r), dtype),
            "wkv_b": n(ks[2], (L, r, H * (dn + dv)), r),
            "wo": n(ks[3], (L, H * dv, D), H * dv)}


def _init_latent_moe(key: jax.Array, cfg: TransformerConfig) -> Params:
    """The DeepSeek-V3 layout: MLA in every layer; ``n_dense_layers``
    dense SwiGLU layers of ``d_ff``, then sigmoid-routed MoE layers with
    the held experts and the shared experts."""
    if not cfg.is_mla:
        raise ValueError("the sigmoid-routed trunk has latent attention "
                         "(kv_lora_rank > 0)")
    dtype = jnp.dtype(cfg.param_dtype)
    D, V, F = cfg.d_model, cfg.vocab_size, cfg.d_ff
    Ld = cfg.n_dense_layers
    Lm = cfg.n_layers - Ld
    ks = jax.random.split(key, 9)
    n = lambda k, shape, fan_in: (jax.random.normal(k, shape, dtype)
                                  * fan_in ** -0.5)
    group = lambda L, attn, mlp: {"attn": attn, "mlp": mlp,
                                  "ln1": jnp.ones((L, D), dtype),
                                  "ln2": jnp.ones((L, D), dtype)}
    params: Params = {
        "embed": n(ks[0], (V, D), D),
        "layers": group(Lm, _init_mla(ks[1], cfg, Lm, dtype),
                        init_sigmoid_moe_params(
                            ks[2], Lm, D, cfg.expert_width, cfg.n_experts,
                            cfg.experts_held, cfg.n_shared_experts, dtype)),
        "final_norm": jnp.ones((D,), dtype),
        "lm_head": {"E": n(ks[3], (V, D), D),
                    "b": jnp.zeros((V,), jnp.float32)},
    }
    if Ld:
        params["dense_layers"] = group(
            Ld, _init_mla(ks[4], cfg, Ld, dtype),
            {"w_gate": n(ks[5], (Ld, D, F), D),
             "w_up": n(ks[6], (Ld, D, F), D),
             "w_down": n(ks[7], (Ld, F, D), F)})
    return params


def init_params(key: jax.Array, cfg: TransformerConfig) -> Params:
    if cfg.router == "sigmoid":
        return _init_latent_moe(key, cfg)
    dtype = jnp.dtype(cfg.param_dtype)
    L, D, V = cfg.n_layers, cfg.d_model, cfg.vocab_size
    H, KV, dh, F = cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.d_ff
    keys = jax.random.split(key, 12)
    sc_d = D ** -0.5
    sc_a = (H * dh) ** -0.5
    sc_f = F ** -0.5

    attn = {
        "wq": jax.random.normal(keys[0], (L, D, H * dh), dtype) * sc_d,
        "wk": jax.random.normal(keys[1], (L, D, KV * dh), dtype) * sc_d,
        "wv": jax.random.normal(keys[2], (L, D, KV * dh), dtype) * sc_d,
        "wo": jax.random.normal(keys[3], (L, H * dh, D), dtype) * sc_a,
    }
    if cfg.is_moe:
        mlp = init_moe_params(keys[4], L, D, F, cfg.n_experts, dtype)
    else:
        mlp = {
            "w_gate": jax.random.normal(keys[5], (L, D, F), dtype) * sc_d,
            "w_up": jax.random.normal(keys[6], (L, D, F), dtype) * sc_d,
            "w_down": jax.random.normal(keys[7], (L, F, D), dtype) * sc_f,
        }
    params: Params = {
        "embed": jax.random.normal(keys[8], (V, D), dtype) * sc_d,
        "layers": {
            "attn": attn,
            "mlp": mlp,
            "ln1": jnp.ones((L, D), dtype),
            "ln2": jnp.ones((L, D), dtype),
        },
        "final_norm": jnp.ones((D,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "E": jax.random.normal(keys[9], (V, D), dtype) * sc_d,
            "b": jnp.zeros((V,), jnp.float32),
        }
    else:
        params["lm_head"] = {"b": jnp.zeros((V,), jnp.float32)}
    return params


def head_weights(params: Params, cfg: TransformerConfig):
    E = params["embed"] if cfg.tie_embeddings else params["lm_head"]["E"]
    return E, params["lm_head"]["b"]


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------

def _layer(
    x: Array,                 # (B, S, D)
    lp: Params,               # one layer's params (leading L dim removed)
    cfg: TransformerConfig,
    *,
    positions: Array,         # (S,)
    mask: Array,              # (B, S)
    causal: bool,
    window: Optional[int],
    moe_shard: MoeShard = None,
    mesh: Optional[Mesh] = None,
) -> Tuple[Array, Array]:
    B, S, D = x.shape
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    cdtype = jnp.dtype(cfg.compute_dtype)

    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if cfg.is_mla:
        with jax.named_scope("mla"):
            x = x + mla_attention(
                h, lp["attn"], n_heads=H, kv_lora_rank=cfg.kv_lora_rank,
                qk_nope_head_dim=cfg.qk_nope_head_dim,
                qk_rope_head_dim=cfg.qk_rope_head_dim,
                v_head_dim=cfg.v_head_dim, positions=positions, mask=mask,
                causal=causal, rope_theta=cfg.rope_theta,
                norm_eps=cfg.norm_eps, chunk_size=cfg.attn_chunk,
                unroll=cfg.attn_unroll, mesh=mesh)
    else:
        q = (h @ lp["attn"]["wq"].astype(cdtype)).reshape(B, S, H, dh)
        k = (h @ lp["attn"]["wk"].astype(cdtype)).reshape(B, S, KV, dh)
        v = (h @ lp["attn"]["wv"].astype(cdtype)).reshape(B, S, KV, dh)
        pos2d = jnp.broadcast_to(positions[None], (B, S))
        q = apply_rope(q, pos2d, cfg.rope_theta)
        k = apply_rope(k, pos2d, cfg.rope_theta)
        attn_out = attention(
            q, k, v, positions=positions, kv_mask=mask, causal=causal,
            window=window, logit_softcap=cfg.attn_logit_softcap,
            chunk_size=cfg.attn_chunk, unroll=cfg.attn_unroll, mesh=mesh)
        x = x + (attn_out.reshape(B, S, H * dh)
                 @ lp["attn"]["wo"].astype(cdtype))

    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    # the layer's FFN kind is in its leaves: a leading dense layer of a
    # MoE model has none of the experts'
    if "router_bias" in lp["mlp"]:
        with jax.named_scope("moe"):
            out, aux = _sigmoid_moe(h, mask, lp["mlp"], cfg, moe_shard)
        x = x + out
    elif "router" in lp["mlp"]:
        out, aux = _apply_moe(h.reshape(B * S, D), lp["mlp"], cfg,
                              moe_shard)
        x = x + out.reshape(B, S, D)
    else:
        g = h @ lp["mlp"]["w_gate"].astype(cdtype)
        u = h @ lp["mlp"]["w_up"].astype(cdtype)
        x = x + (jax.nn.silu(g) * u) @ lp["mlp"]["w_down"].astype(cdtype)
        aux = jnp.zeros((), jnp.float32)
    return x, aux


# ---------------------------------------------------------------------------
# trunk forward (scan over stacked layers)
# ---------------------------------------------------------------------------

# every op of the encoder body, forward and backward, carries "backbone"
# in its op_name, from the embedding gather to the final norm
@jax.named_scope("backbone")
def forward_hidden(
    params: Params,
    cfg: TransformerConfig,
    tokens: Array,            # (B, S) int32
    mask: Optional[Array] = None,
    *,
    causal: Optional[bool] = None,
    moe_shard: MoeShard = None,
    mesh: Optional[Mesh] = None,
    unroll: int = 1,
) -> Tuple[Array, Array]:
    """Returns (H (B, S, D) in compute dtype, aux_loss scalar).

    ``mesh``: the mesh of a data-parallel step, whose batch axes split
    the rows of the attention kernel. ``unroll``: lax.scan unroll factor
    over layers. The dry-run uses full unroll so ``cost_analysis()``
    counts every layer (a rolled scan reports its body cost only once);
    runtime uses 1."""
    B, S = tokens.shape
    cdtype = jnp.dtype(cfg.compute_dtype)
    if mask is None:
        mask = jnp.ones((B, S), jnp.int32)
    if causal is None:
        causal = not cfg.bidirectional_encoder
    positions = jnp.arange(S, dtype=jnp.int32)

    x = jnp.take(params["embed"], tokens, axis=0).astype(cdtype)

    def scan_body(carry, xs):
        x, aux = carry
        lp, layer_idx = xs

        def run(window):
            return _layer(x, lp, cfg, positions=positions, mask=mask,
                          causal=causal, window=window,
                          moe_shard=moe_shard, mesh=mesh)

        if cfg.local_global_alternating and cfg.sliding_window:
            # even layers local (sliding window), odd layers global —
            # static branch impossible inside scan => lax.cond.
            x2, aux2 = jax.lax.cond(
                layer_idx % 2 == 0,
                lambda: run(cfg.sliding_window),
                lambda: run(None),
            )
        else:
            x2, aux2 = run(cfg.sliding_window)
        return (x2, aux + aux2), None

    body = scan_body
    if cfg.remat:
        body = jax.checkpoint(
            scan_body, policy=jax.checkpoint_policies.nothing_saveable)
    carry, first = (x, jnp.zeros((), jnp.float32)), 0
    # leading dense layers (if any), then the rest: one scan each
    for group in ("dense_layers", "layers"):
        if group not in params:
            continue
        n = jax.tree.leaves(params[group])[0].shape[0]
        layer_ids = jnp.arange(first, first + n, dtype=jnp.int32)
        carry, _ = jax.lax.scan(body, carry, (params[group], layer_ids),
                                unroll=unroll)
        first += n
    x, aux = carry
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux


# ---------------------------------------------------------------------------
# heads
# ---------------------------------------------------------------------------

def lsr_encode(
    params: Params,
    cfg: TransformerConfig,
    tokens: Array,
    mask: Array,
    *,
    head_impl: Optional[str] = None,
) -> Tuple[Array, Array]:
    """SPLADE-style sparse encoding: backbone + Sparton head (Eq. 1).

    The head is built through the unified registry (``core.head_api``),
    so ``head_impl`` accepts any registered backend — including
    ``"kernel"`` — and defaults to the config's choice. Returns
    ((B, V) sparse lexical reps, aux_loss).
    """
    from repro.core.head_api import make_head

    spec = cfg.head_spec() if head_impl is None \
        else cfg.head_spec(impl=head_impl)
    head = make_head(spec)
    Hs, aux = forward_hidden(params, cfg, tokens, mask)
    E, b = head_weights(params, cfg)
    y = head(Hs, E.astype(Hs.dtype), b, mask)
    return y, aux


def causal_lm_logits(
    params: Params, cfg: TransformerConfig, tokens: Array,
    mask: Optional[Array] = None,
) -> Tuple[Array, Array]:
    """(B, S, V) next-token logits (standard LM head, softcap applied)."""
    Hs, aux = forward_hidden(params, cfg, tokens, mask, causal=True)
    E, b = head_weights(params, cfg)
    logits = jnp.einsum("bsd,vd->bsv", Hs, E.astype(Hs.dtype)) + b
    if cfg.final_logit_softcap is not None:
        cap = cfg.final_logit_softcap
        logits = cap * jnp.tanh(logits / cap)
    return logits, aux


# ---------------------------------------------------------------------------
# KV-cache decode
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: TransformerConfig, batch: int, max_len: int,
                  dtype=None) -> Dict[str, Array]:
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.d_head)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def decode_step(
    params: Params,
    cfg: TransformerConfig,
    cache: Dict[str, Array],
    tokens: Array,        # (B, 1) int32 — the newest token
    positions: Array,     # (B,) int32 — its position (0-based)
    moe_shard: MoeShard = None,
) -> Tuple[Array, Dict[str, Array]]:
    """One autoregressive step. Returns ((B, V) logits, updated cache).

    The layer loop is *unrolled* (python loop, not scan) and the cache
    stays one stacked buffer updated in place per layer: with the cache
    donated, XLA chains the dynamic-update-slices on a single buffer —
    a scan would return stacked cache outputs and force a second full
    cache allocation (measured ~2.7x cache bytes in temps on the
    decode_32k dry-run cell).
    """
    if cfg.is_mla:
        raise NotImplementedError(
            "decode caches k and v per head; latent attention (MLA) has "
            "no latent-cache decode path")
    B = tokens.shape[0]
    cdtype = jnp.dtype(cfg.compute_dtype)
    H, KV, dh, D = cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.d_model

    x = jnp.take(params["embed"], tokens[:, 0], axis=0).astype(cdtype)
    x = x[:, None, :]  # (B, 1, D)

    k_all, v_all = cache["k"], cache["v"]
    bidx = jnp.arange(B)

    for layer in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[layer], params["layers"])
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        q = (h @ lp["attn"]["wq"].astype(cdtype)).reshape(B, 1, H, dh)
        k = (h @ lp["attn"]["wk"].astype(cdtype)).reshape(B, 1, KV, dh)
        v = (h @ lp["attn"]["wv"].astype(cdtype)).reshape(B, 1, KV, dh)
        q = apply_rope(q, positions[:, None], cfg.rope_theta)
        k = apply_rope(k, positions[:, None], cfg.rope_theta)
        # write new k/v at `positions` (in place on the stacked buffer)
        k_all = k_all.at[layer, bidx, positions].set(k[:, 0])
        v_all = v_all.at[layer, bidx, positions].set(v[:, 0])

        if cfg.local_global_alternating and cfg.sliding_window:
            window = cfg.sliding_window if layer % 2 == 0 else None
        else:
            window = cfg.sliding_window
        attn_out = decode_attention(
            q, k_all[layer], v_all[layer], positions=positions,
            window=window, logit_softcap=cfg.attn_logit_softcap)
        x = x + attn_out.reshape(B, 1, H * dh) @ lp["attn"]["wo"].astype(cdtype)

        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        if cfg.is_moe:
            out, _ = _apply_moe(h.reshape(B, D), lp["mlp"], cfg,
                                moe_shard)
            x = x + out.reshape(B, 1, D)
        else:
            g = h @ lp["mlp"]["w_gate"].astype(cdtype)
            u = h @ lp["mlp"]["w_up"].astype(cdtype)
            x = x + (jax.nn.silu(g) * u) @ lp["mlp"]["w_down"].astype(cdtype)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    E, b = head_weights(params, cfg)
    logits = (x[:, 0, :] @ E.astype(x.dtype).T) + b
    if cfg.final_logit_softcap is not None:
        cap = cfg.final_logit_softcap
        logits = cap * jnp.tanh(logits / cap)
    return logits, {"k": k_all, "v": v_all}
