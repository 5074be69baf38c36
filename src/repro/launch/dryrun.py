import os
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (architecture x input
shape) cell on the production meshes and record memory / cost /
collective analysis (EXPERIMENTS.md §Dry-run, §Roofline).

MUST be the very first thing this module does: force 512 placeholder
CPU devices (above), before any jax import, so ``jax.make_mesh`` can
build the (2, 16, 16) production mesh.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2_27b
    PYTHONPATH=src python -m repro.launch.dryrun --multi-pod    # 512 chips
    PYTHONPATH=src python -m repro.launch.dryrun --json out.json
"""

import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import (DimeNetConfig, RecSysConfig,
                                TransformerConfig)
from repro.configs.specs import CellSpec, cell_spec
from repro.launch import hlo_analysis as hlo
from repro.launch.mesh import batch_axes, make_production_mesh
from repro.launch.sharding import (batch_shardings, batch_spec,
                                   dimenet_param_specs, recsys_param_specs,
                                   state_shardings,
                                   transformer_param_specs)
from repro.launch.steps import build_step, init_state
from repro.models import dimenet as dimenet_model
from repro.models import recsys as recsys_model
from repro.models import transformer as tfm


def _abstract_state(arch_id: str, mesh, cell: Optional[CellSpec] = None
                    ) -> Any:
    """Abstract (ShapeDtypeStruct) train state with shardings attached."""
    from repro.launch.steps import arch_config_for_cell
    if cell is not None:
        cfg = arch_config_for_cell(arch_id, cell)
    else:
        cfg = get_config(arch_id).CONFIG

    if isinstance(cfg, TransformerConfig):
        init = lambda k: tfm.init_params(k, cfg)
        specs = transformer_param_specs(cfg, mesh)
        layout = "adamw"
    elif isinstance(cfg, DimeNetConfig):
        init = lambda k: dimenet_model.init_params(k, cfg)
        specs = dimenet_param_specs(cfg, mesh)
        layout = "adamw"
    else:
        init = lambda k: recsys_model.init_params(k, cfg)
        specs = recsys_param_specs(cfg, mesh)
        layout = "adagrad"

    params_shape = jax.eval_shape(init, jax.ShapeDtypeStruct((2,),
                                                             jnp.uint32))
    shardings = state_shardings(specs, params_shape, layout, mesh)

    def to_f32(l):
        return jax.ShapeDtypeStruct(l.shape, jnp.float32)

    params_abs = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        params_shape, shardings["params"])
    if layout == "adamw":
        opt_abs = {
            "mu": jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, jnp.float32,
                                                  sharding=s),
                params_shape, shardings["opt"]["mu"]),
            "nu": jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, jnp.float32,
                                                  sharding=s),
                params_shape, shardings["opt"]["nu"]),
        }
    else:
        opt_abs = {
            "acc": jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, jnp.float32,
                                                  sharding=s),
                params_shape, shardings["opt"]["acc"]),
        }
    step_abs = jax.ShapeDtypeStruct((), jnp.int32,
                                    sharding=shardings["step"])
    state_abs = {"params": params_abs, "opt": opt_abs, "step": step_abs}
    zero_sh = (shardings["opt"]["mu"] if layout == "adamw"
               else shardings["opt"]["acc"])
    return state_abs, shardings["params"], zero_sh


def _abstract_params_only(arch_id: str, mesh,
                          cell: Optional[CellSpec] = None) -> Any:
    return _abstract_state(arch_id, mesh, cell)[0]["params"]


def _batch_overrides(arch_id: str, cell: CellSpec, mesh
                     ) -> Dict[str, P]:
    """Non-default input shardings (caches, candidates, graph arrays)."""
    cfg = get_config(arch_id).CONFIG
    axes = tuple(mesh.axis_names)
    baxes = batch_axes(mesh)
    ov: Dict[str, P] = {}
    if cell.step_kind == "decode":
        B = cell.batch["tokens"].shape[0]
        # model axis goes on KV heads when divisible, else on d_head —
        # keeps the per-position cache scatter local (sequence-sharded
        # caches force an all-gather around the update; DESIGN.md §5)
        if cfg.n_kv_heads % mesh.shape["model"] == 0:
            head_part = ("model", None)
        elif cfg.d_head % mesh.shape["model"] == 0:
            head_part = (None, "model")
        else:
            head_part = (None, None)
        if B == 1:
            # long-context single stream: batch axes are free — put
            # them on the sequence dim (bounded local cache slices)
            seq_axes = baxes
            ov["cache_k"] = P(None, None, seq_axes, *head_part)
            ov["cache_v"] = P(None, None, seq_axes, *head_part)
            ov["tokens"] = P(None, None)
            ov["positions"] = P(None)
        else:
            ov["cache_k"] = P(None, baxes, None, *head_part)
            ov["cache_v"] = P(None, baxes, None, *head_part)
    elif cell.step_kind == "retrieval":
        ov["candidates"] = P(axes, None)
        for k in ("dense", "sparse_idx", "hist_idx", "target_idx"):
            if k in cell.batch:
                ov[k] = P(*([None] * cell.batch[k].ndim))
    elif cell.step_kind == "gnn_train":
        # edge/triplet arrays shard over every axis; node arrays too
        # when padded-divisible (specs pad to 512)
        n_dev = 1
        for a in axes:
            n_dev *= mesh.shape[a]
        for k, sds in cell.batch.items():
            if sds.shape[0] % n_dev == 0:
                ov[k] = P(axes, *([None] * (sds.ndim - 1)))
            else:
                ov[k] = P(*([None] * sds.ndim))
    return ov


def _out_shardings(cell: CellSpec, state_abs, mesh):
    if cell.step_kind.endswith("_train"):
        state_sh = jax.tree.map(lambda l: l.sharding, state_abs)
        return (state_sh, {"loss": NamedSharding(mesh, P())})
    return None  # serve paths: let the partitioner choose outputs


def run_cell(arch_id: str, shape_name: str, mesh,
             *, verbose: bool = True) -> Dict[str, Any]:
    mod = get_config(arch_id)
    spec = mod.SHAPES[shape_name]
    rec: Dict[str, Any] = {"arch": arch_id, "shape": shape_name,
                           "mesh": "x".join(str(s) for s in
                                            tuple(mesh.devices.shape))}
    if spec.skip:
        rec["status"] = "skipped"
        rec["reason"] = spec.skip_reason
        return rec

    t0 = time.time()
    cell = cell_spec(arch_id, shape_name)
    # NOTE: scans stay rolled here (fast compile, exact memory analysis,
    # real collective schedule). cost_analysis() counts each scan body
    # once — benchmarks/roofline.py recovers exact totals with unrolled
    # per-layer/per-head probes and composes them analytically.
    needs_state = cell.step_kind.endswith("_train")
    param_sh = zero_sh = None
    state_abs = None
    if needs_state:
        state_abs, param_sh, zero_sh = _abstract_state(arch_id, mesh, cell)
    step = build_step(arch_id, cell, mesh, unroll=False,
                      param_specs=param_sh, zero_specs=zero_sh)

    overrides = _batch_overrides(arch_id, cell, mesh)
    batch_sh = batch_shardings(mesh, cell.batch, overrides)
    batch_abs = {
        k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=batch_sh[k])
        for k, v in cell.batch.items()
    }

    with jax.set_mesh(mesh):
        if needs_state:
            # donate the train state: params/opt update in place
            jitted = jax.jit(step, donate_argnums=(0,),
                             out_shardings=_out_shardings(cell, state_abs,
                                                          mesh))
            lowered = jitted.lower(state_abs, batch_abs)
        else:
            params_abs = _abstract_params_only(arch_id, mesh, cell)
            # donate the batch on decode (KV cache updates in place)
            donate = (1,) if cell.step_kind == "decode" else ()
            jitted = jax.jit(step, donate_argnums=donate)
            lowered = jitted.lower(params_abs, batch_abs)
        compiled = lowered.compile()

    rec["compile_s"] = round(time.time() - t0, 1)
    flops, hbm = hlo.cost_analysis_terms(compiled)
    coll = hlo.parse_collectives(compiled.as_text())
    mem = hlo.memory_analysis_bytes(compiled)

    model_flops = _model_flops(arch_id, cell, mesh)
    roof = hlo.roofline_terms(flops, hbm, coll, model_flops=model_flops)

    rec.update({
        "status": "ok",
        "step_kind": cell.step_kind,
        "n_micro": cell.n_micro,
        "flops_per_device": flops,
        "hbm_bytes_per_device": hbm,
        "collective_operand_bytes": coll.total_operand_bytes,
        "collective_wire_bytes": coll.total_wire_bytes,
        "collective_ops": coll.op_counts,
        "memory_analysis": mem,
        "compute_s": roof.compute_s,
        "memory_s": roof.memory_s,
        "collective_s": roof.collective_s,
        "bottleneck": roof.bottleneck,
        "model_flops_per_device": model_flops,
        "useful_ratio": roof.useful_ratio,
    })
    if verbose:
        peak = (mem or {}).get("peak_estimate_bytes", float("nan"))
        print(f"  [{rec['mesh']}] {arch_id}/{shape_name}: "
              f"compile {rec['compile_s']}s  "
              f"flops/dev {flops:.3e}  hbm/dev {hbm:.3e}  "
              f"coll wire {coll.total_wire_bytes:.3e}  "
              f"peak {peak:.3e}  bottleneck {roof.bottleneck}",
              flush=True)
    return rec


def _model_flops(arch_id: str, cell: CellSpec, mesh) -> float:
    """Useful model flops per device: 6*N*D (train) / 2*N*D (fwd) for
    LMs (N = active params); family-appropriate estimates otherwise."""
    cfg = get_config(arch_id).CONFIG
    n_dev = mesh.devices.size
    if isinstance(cfg, TransformerConfig):
        n_active = cfg.n_active_params
        if cell.step_kind == "lsr_train":
            B, S = cell.batch["q_tokens"].shape
            tokens = 2 * B * S  # queries + docs
            return 6.0 * n_active * tokens / n_dev
        if cell.step_kind == "lsr_prefill":
            B, S = cell.batch["tokens"].shape
            return 2.0 * n_active * B * S / n_dev
        if cell.step_kind == "decode":
            B = cell.batch["tokens"].shape[0]
            # one token per sequence + attention over the cache
            attn = (2 * cfg.n_layers * cell.cache_len
                    * cfg.n_heads * cfg.d_head * 2)
            return (2.0 * n_active + attn) * B / n_dev
        return 0.0
    if isinstance(cfg, DimeNetConfig):
        # per block, per edge: msg_in/msg_out/out projections (~6 d^2)
        # + the factored bilinear (2 K nb d + 2 nb d^2); K-sum layout
        d, nb = cfg.d_hidden, cfg.n_bilinear
        K = max(1, cell.n_triplets // max(1, cell.n_edges))
        per_edge = cfg.n_blocks * (6 * d * d + 2 * K * nb * d
                                   + 2 * nb * d * d)
        fwd = cell.n_edges * per_edge
        return 3.0 * fwd / n_dev  # fwd+bwd ~ 3x fwd
    # recsys: interaction op + MLPs (embedding gathers are bytes,
    # not flops)
    if cell.step_kind == "retrieval":
        return 2.0 * cell.n_candidates * cfg.embed_dim / n_dev
    B = next(iter(cell.batch.values())).shape[0]
    d = cfg.embed_dim
    per_ex = 0.0
    if cfg.interaction == "dot":
        dims = cfg.bot_mlp + (cfg.n_sparse + 1 + 351,) + cfg.top_mlp
        n_f = cfg.n_sparse + 1
        per_ex += 2 * n_f * n_f * d            # pairwise dots
        for i in range(len(cfg.bot_mlp) - 1):
            per_ex += 2 * cfg.bot_mlp[i] * cfg.bot_mlp[i + 1]
        tops = (479,) + cfg.top_mlp
        for i in range(len(tops) - 1):
            per_ex += 2 * tops[i] * tops[i + 1]
    elif cfg.interaction == "cin":
        m_f = cfg.n_sparse
        h_prev = m_f
        for h_k in cfg.cin_layers:
            per_ex += 2 * h_prev * m_f * d     # z outer products
            per_ex += 2 * h_prev * m_f * h_k * d
            h_prev = h_k
        dnn = (m_f * d,) + cfg.mlp
        for i in range(len(dnn) - 1):
            per_ex += 2 * dnn[i] * dnn[i + 1]
    elif cfg.interaction == "augru":
        g = cfg.gru_dim
        per_ex += cfg.seq_len * 2 * (2 * 3 * g * (d + g))  # 2 GRU passes
        mlp = (2 * g + d,) + cfg.mlp + (1,)
        for i in range(len(mlp) - 1):
            per_ex += 2 * mlp[i] * mlp[i + 1]
    else:  # concat
        mlp = (cfg.n_sparse * d,) + cfg.mlp + (1,)
        for i in range(len(mlp) - 1):
            per_ex += 2 * mlp[i] * mlp[i + 1]
    mult = 3.0 if cell.step_kind.endswith("train") else 1.0
    return mult * B * per_ex / n_dev


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="single architecture (default: all)")
    ap.add_argument("--shape", default=None, help="single shape")
    ap.add_argument("--multi-pod", action="store_true",
                    help="use the 2x16x16 (512 chip) mesh")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--json", default=None, help="write records here")
    args = ap.parse_args(argv)

    meshes = []
    if args.both_meshes:
        meshes = [make_production_mesh(multi_pod=False),
                  make_production_mesh(multi_pod=True)]
    else:
        meshes = [make_production_mesh(multi_pod=args.multi_pod)]

    archs = [args.arch] if args.arch else ARCH_IDS[:10]
    records = []
    failed = 0
    for mesh in meshes:
        for arch in archs:
            mod = get_config(arch)
            shapes = [args.shape] if args.shape else list(mod.SHAPES)
            for shape in shapes:
                try:
                    records.append(run_cell(arch, shape, mesh))
                except Exception:
                    failed += 1
                    records.append({
                        "arch": arch, "shape": shape,
                        "mesh": "x".join(
                            str(s) for s in tuple(mesh.devices.shape)),
                        "status": "FAILED",
                        "error": traceback.format_exc(limit=20),
                    })
                    print(f"  FAILED {arch}/{shape}", flush=True)
                    traceback.print_exc(limit=8)

    ok = sum(1 for r in records if r.get("status") == "ok")
    sk = sum(1 for r in records if r.get("status") == "skipped")
    print(f"\ndry-run: {ok} ok, {sk} skipped, {failed} failed, "
          f"{len(records)} total", flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=1, default=str)
        print(f"wrote {args.json}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
