"""Functional transformer stack (params = pytrees, apply = functions).

Covers the five assigned LM-family architectures plus the paper's own
SPLADE encoders:

* dense GQA decoders (llama3.2-3b, phi3-mini),
* local/global alternating attention + logit softcaps (gemma2-27b),
* MoE trunks (moonshot-v1-16b-a3b: 64e top-6; phi3.5-moe: 16e top-2),
* bidirectional encoders for SPLADE (bert / xlm-roberta backbones).

Layers are *stacked* (every leaf carries a leading ``n_layers`` dim)
and applied with ``lax.scan`` + optional ``jax.checkpoint`` so that the
HLO stays compact for 512-device SPMD compilation and activation
memory stays O(sqrt)-ish under remat.

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

from repro.configs.base import TransformerConfig
from repro.models.attention import (apply_rope, chunked_attention,
                                    decode_attention)
from repro.models.moe import (init_moe_params, moe_ffn,
                             moe_ffn_local_experts)

Array = jax.Array
MoeShard = Optional[Tuple[Tuple[str, ...], str]]  # (token_axes, expert_axis)


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

def init_params(key: jax.Array, cfg: TransformerConfig) -> Params:
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

def rms_norm(x: Array, scale: Array, eps: float) -> Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


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
) -> Tuple[Array, Array]:
    B, S, D = x.shape
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    cdtype = jnp.dtype(cfg.compute_dtype)

    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    q = (h @ lp["attn"]["wq"].astype(cdtype)).reshape(B, S, H, dh)
    k = (h @ lp["attn"]["wk"].astype(cdtype)).reshape(B, S, KV, dh)
    v = (h @ lp["attn"]["wv"].astype(cdtype)).reshape(B, S, KV, dh)
    pos2d = jnp.broadcast_to(positions[None], (B, S))
    q = apply_rope(q, pos2d, cfg.rope_theta)
    k = apply_rope(k, pos2d, cfg.rope_theta)
    attn_out = chunked_attention(
        q, k, v,
        q_positions=positions, k_positions=positions, kv_mask=mask,
        causal=causal, window=window,
        logit_softcap=cfg.attn_logit_softcap,
        chunk_size=cfg.attn_chunk,
        unroll=cfg.attn_unroll,
    )
    x = x + attn_out.reshape(B, S, H * dh) @ lp["attn"]["wo"].astype(cdtype)

    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if cfg.is_moe:
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

def forward_hidden(
    params: Params,
    cfg: TransformerConfig,
    tokens: Array,            # (B, S) int32
    mask: Optional[Array] = None,
    *,
    causal: Optional[bool] = None,
    moe_shard: MoeShard = None,
    unroll: int = 1,
) -> Tuple[Array, Array]:
    """Returns (H (B, S, D) in compute dtype, aux_loss scalar).

    ``unroll``: lax.scan unroll factor over layers. The dry-run uses
    full unroll so ``cost_analysis()`` counts every layer (a rolled
    scan reports its body cost only once); runtime uses 1."""
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
                          moe_shard=moe_shard)

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
    layer_ids = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    (x, aux), _ = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)),
        (params["layers"], layer_ids), unroll=unroll)
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
