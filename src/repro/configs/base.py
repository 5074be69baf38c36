"""Config dataclasses for every architecture family in the framework.

Configs are frozen dataclasses; each architecture module in
``repro/configs/`` exports ``CONFIG`` (the exact assigned config),
``SMOKE`` (a reduced same-family config for CPU smoke tests) and
``SHAPES`` (the assigned input-shape set). ``repro.configs.get_config``
is the registry entry point used by ``--arch <id>`` everywhere.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    """One (architecture x input-shape) cell of the dry-run matrix."""

    name: str
    kind: str  # train | prefill | decode | full_graph | minibatch | serve | retrieval
    # LM shapes
    seq_len: int = 0
    global_batch: int = 0
    # GNN shapes
    n_nodes: int = 0
    n_edges: int = 0
    d_feat: int = 0
    batch_nodes: int = 0
    fanout: Tuple[int, ...] = ()
    n_graphs: int = 0
    # RecSys shapes
    batch: int = 0
    n_candidates: int = 0
    # bookkeeping
    skip: bool = False
    skip_reason: str = ""


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str
    family: str  # "dense" | "moe"
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab_size: int
    # MoE
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    # "softmax": top-k of softmax probabilities, capacity-dropping
    # dispatch, Switch load-balance loss. "sigmoid" (DeepSeek-V3): top-k
    # of sigmoid scores plus a selection bias, gates the selected scores
    # normalised to sum 1 times ``routed_scale``, dropless dispatch, a
    # sequence-wise balance loss over the real tokens.
    router: str = "softmax"
    routed_scale: float = 1.0
    moe_d_ff: int = 0              # routed expert width (0: d_ff)
    n_shared_experts: int = 0      # one SwiGLU of n * moe_d_ff, every token
    n_dense_layers: int = 0        # leading layers with a dense d_ff FFN
    # the routed experts this chip holds: [expert_offset, expert_offset
    # + n_experts_held) of the router's n_experts (0 held: all of them)
    expert_offset: int = 0
    n_experts_held: int = 0
    # multi-head latent attention (MLA) when kv_lora_rank > 0: a full q
    # projection to n_heads x (nope + rope) dims, a kv_lora_rank latent
    # plus one rope key shared by the heads, values v_head_dim wide
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # gemma-2 features
    sliding_window: Optional[int] = None   # local attention window
    local_global_alternating: bool = False
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    # common
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    bidirectional_encoder: bool = False  # SPLADE-style encoders
    # execution
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    remat: bool = True
    grad_accum_steps: int = 1
    # LSR head (the paper's technique)
    lsr_head: bool = True          # train objective: LSR contrastive
    # LSR objective weights (Unified-LSR: effectiveness is dominated by
    # these regularization choices — keep them per-config, not global)
    lambda_q: float = 5e-4         # FLOPS weight on query reps
    lambda_d: float = 3e-4         # FLOPS weight on doc reps
    l1_weight: float = 0.0         # optional L1 on both rep sides
    aux_weight: float = 1e-2       # MoE load-balance aux weight
    distill_weight: float = 0.0    # MarginMSE weight (needs distill batch)
    # Head backend, resolved against the head_api registry by
    # ``head_spec()``: "jax" is the legacy alias for "sparton"; any
    # registered name ("naive" | "tiled" | "sparton" | "kernel" | ...)
    # is valid.
    head_impl: str = "jax"
    # Pallas head block sizes. None = resolve per call shape via the
    # autotuner (kernels/autotune.py): cached measured winner if one
    # exists, else the analytic heuristic. Ints pin the blocks.
    head_block_b: Optional[int] = None
    head_block_s: Optional[int] = None
    head_block_v: Optional[int] = None
    head_vocab_tile: int = 4096    # pure-JAX streaming tile
    # Rep sparsification (Unified-LSR-style model knob): applied to the
    # (B, V) head output on-device by encoders built via
    # head_api.make_encoder. Both None = dense reps (the default).
    rep_topk: Optional[int] = None
    rep_threshold: Optional[float] = None
    rep_max_nnz: int = 256         # threshold-only static slot budget
    attn_unroll: int = 1           # KV-chunk scan unroll (cost probes)
    attn_chunk: int = 512          # KV chunk of chunked_attention

    def head_blocks(self, batch: int, seq_len: int,
                    dtype: Optional[str] = None
                    ) -> Tuple[int, int, int]:
        """Resolved Pallas head blocks for a run shape.

        Pinned config values win; unset (None) components come from the
        autotuner's cache/heuristic for (batch, seq_len, d_model, V).
        """
        pinned = (self.head_block_b, self.head_block_s, self.head_block_v)
        if all(p is not None for p in pinned):
            return pinned  # type: ignore[return-value]
        from repro.kernels.autotune import blocks_for_config

        # Partial pins are resolved *jointly* (pins fixed, free
        # components re-enumerated) so the combined triple still
        # respects the kernel VMEM budget.
        return blocks_for_config(self.vocab_size, self.d_model, batch,
                                 seq_len, dtype or self.compute_dtype,
                                 pinned=pinned)

    def head_spec(self, **overrides):
        """The config's head as a ``HeadSpec`` for ``make_head``.

        The single translation point from config fields to the unified
        head API: ``head_impl`` ("jax" → "sparton"), pinned/auto Pallas
        blocks, the streaming tile and ``final_logit_softcap`` all land
        in one spec. ``overrides`` replace individual fields (e.g.
        ``head_spec(impl="kernel")``).
        """
        from repro.core.head_api import HeadSpec

        spec = HeadSpec(
            impl=self.head_impl,
            block_b=self.head_block_b,
            block_s=self.head_block_s,
            block_v=self.head_block_v,
            vocab_tile=self.head_vocab_tile,
            logit_softcap=self.final_logit_softcap,
            rep_topk=self.rep_topk,
            rep_threshold=self.rep_threshold,
            rep_max_nnz=self.rep_max_nnz,
        )
        if overrides:
            spec = spec.replace(**overrides)
        if spec.impl == "jax":
            spec = spec.replace(impl="sparton")
        return spec

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def expert_width(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def experts_held(self) -> int:
        return self.n_experts_held or self.n_experts

    def _attn_params(self) -> int:
        d, H = self.d_model, self.n_heads
        if self.is_mla:
            r, dn = self.kv_lora_rank, self.qk_nope_head_dim
            dr, dv = self.qk_rope_head_dim, self.v_head_dim
            return (d * H * (dn + dr) + d * (r + dr) + r
                    + r * H * (dn + dv) + H * dv * d)
        return d * H * self.d_head * 2 + d * self.n_kv_heads * self.d_head * 2

    def _params(self, experts: int) -> int:
        d, L, V = self.d_model, self.n_layers, self.vocab_size
        moe_layers = L - self.n_dense_layers if self.is_moe else 0
        ffn = (L - moe_layers) * 3 * d * self.d_ff
        if moe_layers:
            fe = self.expert_width
            ffn += moe_layers * (experts * 3 * d * fe + d * self.n_experts
                                 + self.n_shared_experts * 3 * d * fe)
        embed = V * d * (1 if self.tie_embeddings else 2)
        return L * (self._attn_params() + 2 * d) + ffn + embed

    @property
    def n_params(self) -> int:
        """Approximate parameter count (embeddings + trunk + head), at
        the routed experts this chip holds."""
        return self._params(self.experts_held)

    @property
    def n_active_params(self) -> int:
        """Active parameters per token (MoE counts top_k experts)."""
        return self._params(self.top_k) if self.is_moe else self.n_params


@dataclasses.dataclass(frozen=True)
class DimeNetConfig:
    name: str
    family: str = "gnn"
    n_blocks: int = 6
    d_hidden: int = 128
    n_bilinear: int = 8
    n_spherical: int = 7
    n_radial: int = 6
    d_feat: int = 0                 # input node features (0 => atom types)
    n_atom_types: int = 95
    cutoff: float = 5.0
    envelope_exponent: int = 5
    max_triplets_per_edge: int = 0  # 0 => exact triplets
    n_targets: int = 1
    param_dtype: str = "float32"
    compute_dtype: str = "float32"


@dataclasses.dataclass(frozen=True)
class RecSysConfig:
    name: str
    family: str = "recsys"
    interaction: str = "dot"  # dot | cin | augru | concat
    n_dense: int = 0
    n_sparse: int = 26
    embed_dim: int = 128
    table_sizes: Tuple[int, ...] = ()
    bot_mlp: Tuple[int, ...] = ()
    top_mlp: Tuple[int, ...] = ()
    mlp: Tuple[int, ...] = ()
    cin_layers: Tuple[int, ...] = ()
    # DIEN
    seq_len: int = 0
    gru_dim: int = 0
    param_dtype: str = "float32"
    compute_dtype: str = "float32"

    @property
    def total_rows(self) -> int:
        return sum(self.table_sizes)


def shapes_lm(long_ok: bool, long_skip_reason: str = "") -> Dict[str, ShapeSpec]:
    """The assigned LM-family shape set (4 cells)."""
    return {
        "train_4k": ShapeSpec("train_4k", "train", seq_len=4096,
                              global_batch=256),
        "prefill_32k": ShapeSpec("prefill_32k", "prefill", seq_len=32768,
                                 global_batch=32),
        "decode_32k": ShapeSpec("decode_32k", "decode", seq_len=32768,
                                global_batch=128),
        "long_500k": ShapeSpec(
            "long_500k", "decode", seq_len=524288, global_batch=1,
            skip=not long_ok, skip_reason=long_skip_reason,
        ),
    }


SHAPES_GNN: Dict[str, ShapeSpec] = {
    "full_graph_sm": ShapeSpec("full_graph_sm", "full_graph",
                               n_nodes=2708, n_edges=10556, d_feat=1433),
    "minibatch_lg": ShapeSpec("minibatch_lg", "minibatch",
                              n_nodes=232965, n_edges=114615892,
                              batch_nodes=1024, fanout=(15, 10)),
    "ogb_products": ShapeSpec("ogb_products", "full_graph",
                              n_nodes=2449029, n_edges=61859140, d_feat=100),
    "molecule": ShapeSpec("molecule", "batched_graphs",
                          n_nodes=30, n_edges=64, n_graphs=128),
}

SHAPES_RECSYS: Dict[str, ShapeSpec] = {
    "train_batch": ShapeSpec("train_batch", "train", batch=65536),
    "serve_p99": ShapeSpec("serve_p99", "serve", batch=512),
    "serve_bulk": ShapeSpec("serve_bulk", "serve", batch=262144),
    "retrieval_cand": ShapeSpec("retrieval_cand", "retrieval", batch=1,
                                n_candidates=1_000_000),
}
