import os
if "512" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Roofline analysis (EXPERIMENTS.md §Roofline).

The dry-run lowers every cell with scans ROLLED (fast compile, exact
memory analysis, true collective schedule) — but ``cost_analysis()``
counts a scan body once, so its FLOP/byte totals undercount by the
trip counts. This module recovers exact per-device totals by
compiling small *probes* with their scans fully unrolled and composing
them analytically:

  LM train   total = n_micro x (2 x Σ_layers P_layer + P_head+loss)
                     + P_opt
  LM prefill total = Σ_layers P_layer_fwd + P_head_fwd
  decode / GNN / recsys(-DIEN) / retrieval — already scan-free or
  unrolled in the step itself => dry-run numbers are exact.
  DIEN       re-lowered with its GRU scans unrolled (cheap model).

Each probe is lowered UNDER THE MESH with the same shardings as the
full step, so per-layer collectives (TP all-reduces, EP psums, head
psum) are captured per-device, exactly.

Usage:
  PYTHONPATH=src python -m benchmarks.roofline \
      --dryrun dryrun_single_pod.json --out roofline_single_pod.json
"""

import argparse
import dataclasses
import json
import sys
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.configs.base import RecSysConfig, TransformerConfig
from repro.configs.specs import cell_spec
from repro.core.head_api import make_head
from repro.core.sharded import sharded_flops_reg, sharded_infonce
from repro.launch import hlo_analysis as hlo
from repro.launch.mesh import batch_axes, make_production_mesh
from repro.launch.sharding import batch_axes_for, transformer_param_specs
from repro.launch.steps import _moe_shard, arch_config_for_cell
from repro.losses.contrastive import flops_regularizer, infonce_loss
from repro.models import transformer as tfm
from repro.models.transformer import _layer

S = jax.ShapeDtypeStruct


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    wire: float = 0.0

    def __add__(self, o):
        return Cost(self.flops + o.flops, self.bytes + o.bytes,
                    self.wire + o.wire)

    def __mul__(self, k):
        return Cost(self.flops * k, self.bytes * k, self.wire * k)
    __rmul__ = __mul__


def _measure(fn, args_abs, mesh, static_argnums=()) -> Cost:
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn).lower(*args_abs).compile()
    flops, byts = hlo.cost_analysis_terms(compiled)
    coll = hlo.parse_collectives(compiled.as_text())
    return Cost(flops, byts, coll.total_wire_bytes)


def _layer_param_abs(cfg: TransformerConfig, mesh):
    """Abstract one-layer params with the (L-stripped) shardings."""
    m = "model"
    dt = jnp.dtype(cfg.param_dtype)
    D, H, KV, dh, F = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                       cfg.d_head, cfg.d_ff)

    def ns(spec):
        return NamedSharding(mesh, spec)

    def ok(dim):
        return dim % mesh.shape[m] == 0

    kv_aligned = KV % mesh.shape[m] == 0   # see launch/sharding.py
    attn = {
        "wq": S((D, H * dh), dt, sharding=ns(
            P(None, m) if ok(H * dh) else P(None, None))),
        "wk": S((D, KV * dh), dt, sharding=ns(
            P(None, m) if kv_aligned else P(None, None))),
        "wv": S((D, KV * dh), dt, sharding=ns(
            P(None, m) if kv_aligned else P(None, None))),
        "wo": S((H * dh, D), dt, sharding=ns(
            P(m, None) if ok(H * dh) else P(None, None))),
    }
    if cfg.is_moe:
        E = cfg.n_experts
        espec = P(m, None, None) if E % mesh.shape[m] == 0 \
            else P(None, None, None)
        mlp = {
            "router": S((D, E), dt, sharding=ns(P(None, None))),
            "w_gate": S((E, D, F), dt, sharding=ns(espec)),
            "w_up": S((E, D, F), dt, sharding=ns(espec)),
            "w_down": S((E, F, D), dt, sharding=ns(espec)),
        }
    else:
        mlp = {
            "w_gate": S((D, F), dt, sharding=ns(
                P(None, m) if ok(F) else P(None, None))),
            "w_up": S((D, F), dt, sharding=ns(
                P(None, m) if ok(F) else P(None, None))),
            "w_down": S((F, D), dt, sharding=ns(
                P(m, None) if ok(F) else P(None, None))),
        }
    return {
        "attn": attn, "mlp": mlp,
        "ln1": S((D,), dt, sharding=ns(P(None))),
        "ln2": S((D,), dt, sharding=ns(P(None))),
    }


def _probe_layer(cfg: TransformerConfig, mesh, B_local_total: int,
                 seq: int, *, train: bool, window, causal: bool) -> Cost:
    """Per-device cost of ONE transformer layer at the (micro)batch
    shape, attention chunk scan fully unrolled."""
    n_chunks = max(1, seq // min(cfg.attn_chunk, seq))
    cfg_u = dataclasses.replace(cfg, attn_unroll=n_chunks)
    moe_shard = _moe_shard(cfg, mesh)
    baxes = batch_axes_for(mesh, B_local_total)
    cdt = jnp.dtype(cfg.compute_dtype)
    lp_abs = _layer_param_abs(cfg, mesh)
    x_abs = S((B_local_total, seq, cfg.d_model), cdt,
              sharding=NamedSharding(mesh, P(baxes, None, None)))
    mask_abs = S((B_local_total, seq), jnp.int32,
                 sharding=NamedSharding(mesh, P(baxes, None)))

    positions = jnp.arange(seq, dtype=jnp.int32)

    def layer_fn(lp, x, mask):
        return _layer(x, lp, cfg_u, positions=positions, mask=mask,
                      causal=causal, window=window, moe_shard=moe_shard)

    if train and cfg.remat:
        # the real step remats every layer: the probe must count the
        # recompute forward too
        layer_fn = jax.checkpoint(
            layer_fn, policy=jax.checkpoint_policies.nothing_saveable)

    def fwd(lp, x, mask):
        out, aux = layer_fn(lp, x, mask)
        return jnp.sum(out.astype(jnp.float32)) + aux

    if train:
        fn = jax.value_and_grad(fwd, argnums=(0, 1))
    else:
        fn = fwd
    return _measure(fn, (lp_abs, x_abs, mask_abs), mesh)


def _probe_head_loss(cfg: TransformerConfig, mesh, pairs_local_total: int,
                     seq: int, *, train: bool) -> Cost:
    """Per-device cost of both encoders' Sparton heads + the InfoNCE
    and FLOPS losses at the micro shape (vocab scan fully unrolled)."""
    m = "model"
    vocab_ok = cfg.vocab_size % mesh.shape[m] == 0
    baxes = batch_axes_for(mesh, pairs_local_total)
    cdt = jnp.dtype(cfg.compute_dtype)
    V, D = cfg.vocab_size, cfg.d_model
    v_local = V // mesh.shape[m] if vocab_ok else V
    n_tiles = max(1, v_local // cfg.head_vocab_tile)
    n_shards = 1
    for ax in baxes:
        n_shards *= mesh.shape[ax]
    b_local = max(1, pairs_local_total // n_shards)

    # The probe always counts the pure-JAX scan body (pallas_call has
    # no cost_analysis), with the scans fully unrolled for exact totals.
    spec = cfg.head_spec(impl="sparton", unroll=n_tiles,
                         bwd_batch_chunk=max(8, b_local))
    if vocab_ok:
        head = make_head(spec, mesh=mesh, batch_axes=baxes)
        infonce = sharded_infonce(mesh, batch_axes=baxes)
        flops_r = sharded_flops_reg(mesh, batch_axes=baxes)
    else:
        head = make_head(spec)
        infonce = infonce_loss
        flops_r = flops_regularizer

    e_spec = P(m, None) if vocab_ok else P(None, None)
    b_spec = P(m) if vocab_ok else P(None)
    Hq = S((pairs_local_total, seq, D), cdt,
           sharding=NamedSharding(mesh, P(baxes, None, None)))
    E_abs = S((V, D), cdt, sharding=NamedSharding(mesh, e_spec))
    b_abs = S((V,), jnp.float32, sharding=NamedSharding(mesh, b_spec))
    mask_abs = S((pairs_local_total, seq), jnp.int32,
                 sharding=NamedSharding(mesh, P(baxes, None)))

    def headloss(Hq_, Hd_, E_, bb, mq, md):
        yq = head(Hq_, E_, bb, mq)
        yd = head(Hd_, E_, bb, md)
        if vocab_ok:
            loss = infonce(yq, yd)
        else:
            loss = infonce(yq, yd)
        return loss + cfg.lambda_q * flops_r(yq) \
            + cfg.lambda_d * flops_r(yd)

    if train:
        fn = jax.value_and_grad(headloss, argnums=(0, 1, 2, 3))
    else:
        def fn(Hq_, Hd_, E_, bb, mq, md):
            return head(Hq_, E_, bb, mq)
    return _measure(fn, (Hq, Hq, E_abs, b_abs, mask_abs, mask_abs), mesh)


def _probe_opt(arch_id, cfg, mesh, cell) -> Cost:
    """Optimizer update cost (incl. ZeRO reduce-scatter/all-gather)."""
    from repro.launch.dryrun import _abstract_state
    from repro.optim.optimizers import adamw, apply_updates

    state_abs, param_sh, zero_sh = _abstract_state(arch_id, mesh, cell)
    params_abs = state_abs["params"]
    grads_abs = jax.tree.map(
        lambda l: S(l.shape, l.dtype, sharding=l.sharding), params_abs)
    opt = adamw(1e-4)

    def optstep(params, mu, nu, grads):
        grads = jax.lax.with_sharding_constraint(grads, zero_sh)
        updates, st = opt.update(grads, {"mu": mu, "nu": nu}, params,
                                 jnp.zeros((), jnp.int32))
        updates = jax.lax.with_sharding_constraint(updates, param_sh)
        return apply_updates(params, updates), st

    return _measure(
        fn=optstep,
        args_abs=(params_abs, state_abs["opt"]["mu"],
                  state_abs["opt"]["nu"], grads_abs),
        mesh=mesh)


def corrected_lm_cost(arch_id: str, shape_name: str, mesh) -> Cost:
    cell = cell_spec(arch_id, shape_name)
    cfg = arch_config_for_cell(arch_id, cell)
    L = cfg.n_layers

    if cell.step_kind == "lsr_train":
        pairs, seq = cell.batch["q_tokens"].shape
        micro_pairs = max(1, pairs // cell.n_micro)
        causal = not cfg.bidirectional_encoder
        if cfg.local_global_alternating and cfg.sliding_window:
            p_local = _probe_layer(cfg, mesh, micro_pairs, seq, train=True,
                                   window=cfg.sliding_window, causal=causal)
            p_global = _probe_layer(cfg, mesh, micro_pairs, seq,
                                    train=True, window=None, causal=causal)
            layers = (L // 2 + L % 2) * p_local + (L // 2) * p_global
        else:
            p = _probe_layer(cfg, mesh, micro_pairs, seq, train=True,
                             window=cfg.sliding_window, causal=causal)
            layers = L * p
        headloss = _probe_head_loss(cfg, mesh, micro_pairs, seq,
                                    train=True)
        opt = _probe_opt(arch_id, cfg, mesh, cell)
        return cell.n_micro * (2 * layers + headloss) + opt

    if cell.step_kind == "lsr_prefill":
        B, seq = cell.batch["tokens"].shape
        causal = not cfg.bidirectional_encoder
        if cfg.local_global_alternating and cfg.sliding_window:
            p_local = _probe_layer(cfg, mesh, B, seq, train=False,
                                   window=cfg.sliding_window, causal=causal)
            p_global = _probe_layer(cfg, mesh, B, seq, train=False,
                                    window=None, causal=causal)
            layers = (L // 2 + L % 2) * p_local + (L // 2) * p_global
        else:
            p = _probe_layer(cfg, mesh, B, seq, train=False,
                             window=cfg.sliding_window, causal=causal)
            layers = L * p
        head = _probe_head_loss(cfg, mesh, B, seq, train=False)
        return layers + head

    raise ValueError(cell.step_kind)


def corrected_dien_cost(arch_id: str, shape_name: str, mesh) -> Cost:
    """Re-lower the DIEN step with its GRU scans unrolled (T=100)."""
    from repro.launch import dryrun as dr
    from repro.launch.sharding import batch_shardings
    from repro.models import recsys as recsys_model
    from repro.optim.optimizers import adagrad, apply_updates

    cell = cell_spec(arch_id, shape_name)
    cfg = get_config(arch_id).CONFIG
    state_abs, param_sh, zero_sh = dr._abstract_state(arch_id, mesh, cell)
    bsh = batch_shardings(mesh, cell.batch,
                          dr._batch_overrides(arch_id, cell, mesh))
    batch_abs = {k: S(v.shape, v.dtype, sharding=bsh[k])
                 for k, v in cell.batch.items()}
    opt = adagrad(1e-2)
    T = cfg.seq_len

    if cell.step_kind == "recsys_train":
        def loss_fn(params, batch):
            logits = recsys_model.forward(params, cfg, batch, unroll=T)
            label = batch["label"]
            l = jnp.maximum(logits, 0) - logits * label \
                + jnp.log1p(jnp.exp(-jnp.abs(logits)))
            return jnp.mean(l)

        def step(state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(state["params"],
                                                      batch)
            grads = jax.lax.with_sharding_constraint(grads, zero_sh)
            updates, st = opt.update(grads, state["opt"],
                                     state["params"], state["step"])
            return loss
        return _measure(step, (state_abs, batch_abs), mesh)

    def serve(params, batch):
        return jax.nn.sigmoid(
            recsys_model.forward(params, cfg, batch, unroll=T))
    return _measure(serve, (state_abs["params"], batch_abs), mesh)


def fused_hbm_estimate(arch_id: str, shape_name: str, mesh) -> float:
    """Analytic LOWER bound on per-device HBM traffic per step, assuming
    perfect elementwise fusion (TPU-like): weights are read once per
    (micro x fwd+bwd use), opt state read+written once, saved
    activations written+read once. cost_analysis() bytes are the
    UNFUSED upper bound; the truth lies between.
    """
    cell = cell_spec(arch_id, shape_name)
    cfg = arch_config_for_cell(arch_id, cell)
    n_dev = mesh.devices.size
    if not isinstance(cfg, TransformerConfig):
        return 0.0
    p_bytes = cfg.n_params * jnp.dtype(cfg.param_dtype).itemsize / \
        mesh.shape["model"]
    cdt = jnp.dtype(cfg.compute_dtype).itemsize
    if cell.step_kind == "lsr_train":
        pairs, seq = cell.batch["q_tokens"].shape
        tokens_local = 2 * pairs * seq / max(
            1, n_dev // mesh.shape["model"])
        act = cfg.n_layers * tokens_local * cfg.d_model * cdt
        opt = 2 * cfg.n_params * 4 / n_dev * 2      # mu+nu r/w (ZeRO)
        grads = cfg.n_params * 4 / n_dev * 2 * cell.n_micro
        # fwd read + bwd read (+ remat fwd re-read) per micro
        weights = 3 * p_bytes * cell.n_micro
        return weights + act * 3 + opt + grads
    if cell.step_kind == "lsr_prefill":
        B, seq = cell.batch["tokens"].shape
        tokens_local = B * seq / max(1, n_dev // mesh.shape["model"])
        act = cfg.n_layers * tokens_local * cfg.d_model * cdt
        return p_bytes + act * 2
    if cell.step_kind == "decode":
        B = cell.batch["tokens"].shape[0]
        cache = (2 * cfg.n_layers * B * cell.cache_len * cfg.n_kv_heads
                 * cfg.d_head * cdt) / n_dev
        return p_bytes + cache
    return 0.0


# (B, Q, L, N, k) operating points for the fused impact-scorer probe:
# a serving batch against a CI-sized, a mid, and a paper-scale corpus.
_IMPACT_PROBE_SHAPES = (
    (16, 32, 256, 16384, 100),
    (16, 32, 1024, 131072, 100),
    (16, 32, 4096, 1 << 20, 100),
)


def impact_probe(shapes=_IMPACT_PROBE_SHAPES) -> list:
    """Analytic bytes-moved vs FLOPs for the impact scorer, unfused vs
    fused (kernels/impact_score), per variant.

    The unfused path reads the gathered posting windows, materializes
    the (B, N) score matrix in HBM (one write + one read back by
    top_k), and writes (B, k); the u4 variant additionally writes and
    re-reads the dequantized window. The fused kernel reads the same
    windows once and writes (B, k) — but pays the one-hot contraction:
    every posting lane is multiplied against every doc column of its
    tile, 2*B*W*N_pad MACs of MXU work. The probe makes that trade
    explicit: fused swaps O(B*N) HBM traffic for O(B*W*N) cheap MXU
    FLOPs, which wins whenever the unfused path is memory-bound —
    exactly the Sparton argument on the encode side.
    """
    from repro.kernels.autotune import heuristic_impact_blocks
    from repro.kernels.impact_score import fused_window_bytes

    out = []
    for B, Q, L, N, k in shapes:
        W = Q * L
        topk_out = B * k * 8
        for variant in ("f32", "u4"):
            window = fused_window_bytes(B, Q, L, variant)
            unfused = window + 2 * B * N * 4 + topk_out
            if variant == "u4":
                unfused += 2 * B * W * 8   # dequant materialization
            bn, bw = heuristic_impact_blocks(B, Q, L, N,
                                             variant=variant)
            n_pad = -(-N // bn) * bn
            fused = window + topk_out
            flops_unfused = 2.0 * B * W + float(B) * N
            flops_fused = 2.0 * B * W * n_pad
            for path, byts, flops in (
                    ("unfused", unfused, flops_unfused),
                    ("fused", fused, flops_fused)):
                mem_s = byts / hlo.HBM_BW
                compute_s = flops / hlo.PEAK_FLOPS
                out.append({
                    "probe": "impact_scorer",
                    "shape": {"B": B, "Q": Q, "L": L, "N": N, "k": k},
                    "variant": variant,
                    "path": path,
                    "blocks": ([bn, bw] if path == "fused" else None),
                    "hbm_bytes": int(byts),
                    "flops": flops,
                    "intensity_flops_per_byte": round(flops / byts, 3),
                    "roof_memory_s": mem_s,
                    "roof_compute_s": compute_s,
                    "roof_bottleneck": ("memory" if mem_s >= compute_s
                                        else "compute"),
                })
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", default=None,
                    help="dry-run json (rolled lowering records)")
    ap.add_argument("--out", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--only-arch", default=None)
    ap.add_argument("--impact-probe", action="store_true",
                    help="emit the analytic fused-impact-scorer "
                         "bytes/FLOPs records instead of correcting a "
                         "dry-run (no mesh, no lowering)")
    args = ap.parse_args(argv)

    if args.impact_probe:
        recs = impact_probe()
        with open(args.out, "w") as f:
            json.dump(recs, f, indent=1)
        for r in recs:
            s = r["shape"]
            print(f"N={s['N']} {r['variant']:>3} {r['path']:>7}: "
                  f"{r['hbm_bytes'] / 1e6:9.1f} MB, "
                  f"{r['flops'] / 1e9:9.2f} GFLOP "
                  f"-> {r['roof_bottleneck']}")
        print(f"wrote {args.out}")
        return 0
    if not args.dryrun:
        ap.error("--dryrun is required unless --impact-probe is set")

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    records = json.load(open(args.dryrun))
    out = []
    for rec in records:
        if rec.get("status") != "ok":
            out.append(rec)
            continue
        arch, shape = rec["arch"], rec["shape"]
        if args.only_arch and arch != args.only_arch:
            out.append(rec)
            continue
        kind = rec["step_kind"]
        try:
            if kind in ("lsr_train", "lsr_prefill"):
                cost = corrected_lm_cost(arch, shape, mesh)
            elif arch == "dien":
                cost = corrected_dien_cost(arch, shape, mesh)
            else:
                cost = Cost(rec["flops_per_device"],
                            rec["hbm_bytes_per_device"],
                            rec["collective_wire_bytes"])
        except Exception as e:  # record + keep going
            rec["roofline_error"] = repr(e)
            out.append(rec)
            print(f"PROBE FAILED {arch}/{shape}: {e!r}", flush=True)
            continue

        stats = hlo.CollectiveStats({}, {}, {})
        stats.total_wire_bytes = cost.wire
        roof = hlo.roofline_terms(
            cost.flops, cost.bytes, stats,
            model_flops=rec.get("model_flops_per_device", 0.0))
        fused = fused_hbm_estimate(arch, shape, mesh)
        rec.update({
            "corrected_flops_per_device": cost.flops,
            "corrected_hbm_bytes_per_device": cost.bytes,
            "corrected_collective_wire_bytes": cost.wire,
            "roof_compute_s": roof.compute_s,
            "roof_memory_s": roof.memory_s,
            "roof_memory_s_fused_est": fused / hlo.HBM_BW if fused else None,
            "roof_collective_s": roof.collective_s,
            "roof_bottleneck": roof.bottleneck,
            "roof_useful_ratio": roof.useful_ratio,
        })
        print(f"{arch}/{shape}: compute {roof.compute_s:.3e}s "
              f"memory {roof.memory_s:.3e}s coll {roof.collective_s:.3e}s"
              f" -> {roof.bottleneck} (useful {roof.useful_ratio:.2f})",
              flush=True)
        out.append(rec)

    with open(args.out, "w") as f:
        json.dump(out, f, indent=1, default=str)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
