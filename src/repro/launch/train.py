"""Training driver: ``python -m repro.launch.train --arch <id> ...``

Wires configs -> data pipeline -> jitted train step (with shardings
when devices allow a mesh) -> fault-tolerant runner (checkpoint/
restart, straggler policy, elastic re-mesh).

By default this runs the SMOKE config; ``--full`` takes the published
config (e.g. ``splade_bert`` at 12L, d=768, V=30522). On the CPU the
Pallas head runs in the interpreter, on a TPU it is compiled. The run
exits non-zero when a step was skipped or the last loss is not finite;
a failure of the first step (where the step compiles) is raised.

XLA flags for collective overlap (latency-hiding scheduler) are set
before jax initializes when --overlap is passed.
"""

import argparse
import math
import os
import sys
import tempfile


def _set_overlap_flags() -> None:
    flags = os.environ.get("XLA_FLAGS", "")
    flags += (
        " --xla_tpu_enable_async_collective_fusion=true"
        " --xla_tpu_enable_async_collective_fusion_fuse_all_gather=true"
        " --xla_tpu_overlap_compute_collective_tc=true"
        " --xla_enable_async_all_gather=true"
        " --xla_enable_async_all_reduce=true"
    )
    os.environ["XLA_FLAGS"] = flags


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--ckpt-dir",
                    default=os.path.join(tempfile.gettempdir(),
                                         "repro_ckpt"))
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--full", action="store_true",
                    help="use the full (assigned) config, not SMOKE")
    ap.add_argument("--overlap", action="store_true",
                    help="set XLA latency-hiding scheduler flags")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--lr", type=float, default=2e-4)
    ap.add_argument("--lambda-q", type=float, default=None,
                    help="FLOPS regularizer weight on query reps "
                         "(default: config's lambda_q)")
    ap.add_argument("--lambda-d", type=float, default=None,
                    help="FLOPS regularizer weight on doc reps "
                         "(default: config's lambda_d)")
    ap.add_argument("--l1-weight", type=float, default=None,
                    help="L1 rep regularizer weight "
                         "(default: config's l1_weight)")
    ap.add_argument("--eval-every", type=int, default=0,
                    help="every N steps, run retrieval eval (MRR@10/"
                         "nDCG@10 on a held-out paired batch) and log "
                         "it; also evals the untrained init and prints "
                         "the improvement at the end. 0 = off")
    ap.add_argument("--eval-queries", type=int, default=32,
                    help="held-out (query, positive-doc) pairs scored "
                         "by --eval-every")
    ap.add_argument("--head-impl", default=None,
                    help="LSR head implementation (default: config's; "
                         "any registered backend — validated against "
                         "repro.core.head_api.available_impls after "
                         "startup so runtime-registered impls work)")
    ap.add_argument("--autotune-head", action="store_true",
                    help="measure Pallas head block candidates for this "
                         "run shape and persist the winner before "
                         "building the train step")
    args = ap.parse_args(argv)

    if args.overlap:
        _set_overlap_flags()

    import jax
    import jax.numpy as jnp

    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()

    from repro.configs import get_config
    from repro.configs.base import (DimeNetConfig, RecSysConfig,
                                    TransformerConfig)
    from repro.data.loader import HostShardedLoader
    from repro.data.synthetic import lsr_pair_batches, recsys_batches
    from repro.launch.steps import (build_lsr_train_step,
                                    build_recsys_train_step, init_state)
    from repro.runtime.fault_tolerance import (FaultTolerantRunner,
                                               RunnerConfig)

    mod = get_config(args.arch)
    cfg = mod.CONFIG if args.full else mod.SMOKE
    state, _ = init_state(args.arch, jax.random.PRNGKey(0),
                          smoke=not args.full)

    if isinstance(cfg, TransformerConfig):
        import dataclasses

        reg = {name: getattr(args, name) for name in
               ("lambda_q", "lambda_d", "l1_weight")
               if getattr(args, name) is not None}
        if reg:
            cfg = dataclasses.replace(cfg, **reg)

    if isinstance(cfg, TransformerConfig) and args.head_impl:
        import dataclasses

        from repro.core.head_api import available_impls
        if args.head_impl not in ("jax",) + available_impls():
            raise SystemExit(
                f"--head-impl {args.head_impl!r}: unknown head impl; "
                f"one of {('jax',) + available_impls()}")
        cfg = dataclasses.replace(cfg, head_impl=args.head_impl)

    if isinstance(cfg, TransformerConfig) and args.autotune_head:
        import dataclasses

        from repro.kernels.autotune import autotune_kernel_blocks
        if cfg.head_impl != "kernel":
            # tuned blocks are only read by the Pallas head — don't
            # spend a timing sweep on a config that would ignore them
            print("--autotune-head implies --head-impl kernel "
                  f"(config had {cfg.head_impl!r})")
            cfg = dataclasses.replace(cfg, head_impl="kernel")
        # Per-kernel winners (fwd vs dH vs dE) land in the autotune
        # cache, where ops.sparton_head's per-kernel resolution reads
        # them — the config's head_block_* stay unpinned on purpose
        # (pinning would force one joint triple onto all three).
        # timed on the data's own document mask, so the kernels skip
        # the tiles they will skip in the run
        sample = next(lsr_pair_batches(
            batch=args.batch, q_len=args.seq_len, d_len=args.seq_len,
            vocab=cfg.vocab_size))
        winners = autotune_kernel_blocks(
            args.batch, args.seq_len, cfg.d_model, cfg.vocab_size,
            dtype=jnp.dtype(cfg.compute_dtype),
            softcap=cfg.final_logit_softcap,
            mask=jnp.asarray(sample["d_mask"]))
        print(f"autotuned head blocks (B={args.batch} S={args.seq_len} "
              f"D={cfg.d_model} V={cfg.vocab_size}): " +
              ", ".join(f"{kn}={blk}" for kn, blk in winners.items()))

    eval_hook = None
    run_eval = None
    eval_log = []
    if isinstance(cfg, TransformerConfig):
        step = build_lsr_train_step(cfg, None, n_micro=1,
                                    n_pairs=args.batch, lr=args.lr)

        def make_iter(shard, n_shards):
            it = lsr_pair_batches(
                batch=args.batch, q_len=args.seq_len, d_len=args.seq_len,
                vocab=cfg.vocab_size, shard=shard)
            for b in it:
                yield {"q_tokens": b["q_tokens"], "q_mask": b["q_mask"],
                       "d_tokens": b["d_tokens"], "d_mask": b["d_mask"]}

        if args.eval_every:
            from repro.eval import MethodSpec, Qrels, evaluate_retrieval
            from repro.launch.steps import _encode_fn

            # held-out pairs: a seed no training shard ever draws, so
            # eval measures generalization, not batch memorization
            held_out = next(lsr_pair_batches(
                batch=args.eval_queries, q_len=args.seq_len,
                d_len=args.seq_len, vocab=cfg.vocab_size, seed=9173))
            corpus = {"doc_tokens": held_out["d_tokens"],
                      "doc_mask": held_out["d_mask"],
                      "q_tokens": held_out["q_tokens"],
                      "q_mask": held_out["q_mask"],
                      "vocab_size": cfg.vocab_size}
            qrels = Qrels.paired(args.eval_queries)
            enc_batch = min(32, args.eval_queries)
            encode = _encode_fn(cfg, None, enc_batch)
            enc_jit = jax.jit(lambda p, t, m: encode(p, t, m)[0])

            def run_eval(state):
                params = state["params"]
                res = evaluate_retrieval(
                    lambda t, m: enc_jit(params, t, m), corpus, qrels,
                    methods=(MethodSpec("exact"),), ks=(10,),
                    metrics=("mrr", "ndcg"), batch=enc_batch)
                return res["exact"]

            def eval_hook(step_idx, state):
                done = step_idx + 1
                if done % args.eval_every and done != args.steps:
                    return None
                m = run_eval(state)
                eval_log.append((done, m))
                print(f"eval @ step {done}: " + " ".join(
                    f"{k} {v:.4f}" for k, v in m.items()))
                return {f"eval_{k}": v for k, v in m.items()}
    elif isinstance(cfg, RecSysConfig):
        step = build_recsys_train_step(cfg)

        def make_iter(shard, n_shards):
            return recsys_batches(
                batch=args.batch, n_dense=cfg.n_dense,
                n_sparse=cfg.n_sparse, table_sizes=cfg.table_sizes,
                seq_len=cfg.seq_len, shard=shard)
    else:
        raise SystemExit(
            "use examples/train_dimenet.py for the GNN family")

    loader = HostShardedLoader(make_iter)
    jitted = jax.jit(step, donate_argnums=(0,))

    def place(batch):
        return {k: jnp.asarray(v) for k, v in batch.items()}

    runner = FaultTolerantRunner(
        jitted, state, iter(loader),
        config=RunnerConfig(ckpt_dir=args.ckpt_dir,
                            ckpt_every=args.ckpt_every,
                            max_steps=args.steps, log_every=1),
        place_batch=place,
        on_step=eval_hook,
    )
    if args.resume and runner.try_resume():
        print(f"resumed from step {runner.start_step}")
    init_metrics = run_eval(state) if run_eval is not None else None
    if init_metrics:
        print("eval @ init: " + " ".join(
            f"{k} {v:.4f}" for k, v in init_metrics.items()))
    runner.run()
    loss_entries = [m for m in runner.metrics_log if "loss" in m]
    if loss_entries:
        print(f"step {loss_entries[-1]['step']}: "
              f"loss {float(loss_entries[-1]['loss']):.6g} "
              f"(first {float(loss_entries[0]['loss']):.6g})")
    if init_metrics and eval_log:
        final = eval_log[-1][1]
        print("eval improvement over init: " + " ".join(
            f"{k} {init_metrics[k]:.4f}->{final[k]:.4f}"
            f"({final[k] - init_metrics[k]:+.4f})" for k in final))
    print(f"done: {args.steps} steps, "
          f"{len(runner.skipped_steps)} skipped, "
          f"{len(runner.remesh_events)} re-mesh events")
    loader.close()
    # a skipped step or a diverged loss is a failed run, not a warning
    last_loss = float(loss_entries[-1]["loss"]) if loss_entries else 0.0
    if runner.skipped_steps or not math.isfinite(last_loss):
        print(f"FAILED: skipped steps {runner.skipped_steps}, "
              f"last loss {last_loss}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
