"""Serving driver: ``python -m repro.launch.serve --arch <id>``.

Runs the sparse-native LSR serving pipeline end-to-end:

1. index  — encode a synthetic corpus through backbone + Sparton head,
            sparsify on-device (``rep_topk``), build the inverted
            impact index (no dense (N, V) corpus matrix anywhere).
            With ``--engine`` the corpus is grown *online* through the
            incremental ``CorpusEngine``/``IndexBuilder`` (batches are
            added and flushed as they arrive; ``--remove-frac``
            tombstones a slice mid-stream to exercise the lifecycle),
            optionally compressed (``--quantize``) or served through
            the two-tier pruned scorer (``--prune-margin``).
2. serve  — stream queries through the deadline/size micro-batching
            loop (results popped via ``take``), reporting latency and
            achieved batch sizes. ``--deadline-ms`` attaches an SLO to
            every request (the hardened loop may shed; shed/failed
            uids are reported and excluded from retrieval) and
            ``--max-queue`` bounds the admission queue;
3. retrieve — top-k via the unified dispatcher (``--method`` selects
            the path; see repro.retrieval.retrieve's dispatch table).
            ``--shard-axis doc|term|2d|auto`` picks the placement for
            ``--method sharded`` builds and ``--engine`` bases: doc
            ranges with a top-k merge, vocab ranges with the
            partial-sum (psum) merge (DESIGN.md §9), the (doc x term)
            grid composing both, or the ShardPlan planner sizing the
            grid from posting mass vs the O(V) directory
            (DESIGN.md §14).
"""

import argparse
import sys
import time


def _grid_plan(n_shards: int):
    """The most balanced (doc x term) factorization of an explicit
    ``--shard-axis 2d`` request: largest doc divisor <= sqrt(n), the
    term axis takes the rest (prime counts degenerate to 1 x n)."""
    from repro.retrieval import ShardPlan

    d = max(f for f in range(1, int(n_shards ** 0.5) + 1)
            if n_shards % f == 0)
    return ShardPlan(doc_shards=d, term_shards=n_shards // d,
                     reason=f"--shard-axis 2d: balanced factorization "
                            f"of {n_shards} devices")


def main(argv=None) -> int:
    from repro.retrieval import METHODS

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="splade_bert")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--corpus", type=int, default=1000)
    ap.add_argument("--topk", type=int, default=10)
    ap.add_argument("--rep-topk", type=int, default=64,
                    help="per-row term budget of the on-device rep "
                         "sparsifier; 0 = dense reps (legacy path)")
    ap.add_argument("--method", default="auto", choices=list(METHODS),
                    help="retrieval path (see repro.retrieval.retrieve)")
    ap.add_argument("--check-method", default=None, choices=list(METHODS),
                    help="also retrieve through this method on the same "
                         "index and exit non-zero unless its top-k ids "
                         "equal --method's")
    ap.add_argument("--full", action="store_true",
                    help="use the full (published) config, not SMOKE")
    ap.add_argument("--shards", type=int, default=2,
                    help="--method sharded/term_sharded: shard count "
                         "(single-device vmap path unless a mesh is "
                         "wired in)")
    ap.add_argument("--shard-axis", default="doc",
                    choices=("auto", "doc", "term", "2d"),
                    help="sharding axis for --method sharded or an "
                         "--engine base: doc = contiguous doc ranges "
                         "(all_gather+re-top-k merge), term = vocab "
                         "ranges with full posting lists (partial-sum "
                         "psum merge; the huge-|V| regime), 2d = the "
                         "(doc x term) grid composing both, auto = "
                         "let engine.shard2d.plan_placement pick the "
                         "(doc_shards, term_shards, replicas) grid "
                         "from posting bytes vs the O(V) directory "
                         "(frozen builds size the real index; "
                         "--engine plans from the requested corpus "
                         "size and rep budget)")
    ap.add_argument("--index-batch", type=int, default=64,
                    help="corpus encoding batch size")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    metavar="MS",
                    help="per-request SLO: the loop sheds requests "
                         "whose estimated or actual queue delay blows "
                         "this deadline (default: best-effort, never "
                         "shed)")
    ap.add_argument("--max-queue", type=int, default=1024,
                    help="admission bound on queue depth; submits "
                         "beyond it are shed with a ShedResult")
    ap.add_argument("--head-impl", default=None,
                    help="override the config's head backend (any "
                         "registered impl; see "
                         "repro.core.head_api.available_impls)")
    ap.add_argument("--engine", action="store_true",
                    help="grow the corpus online through the "
                         "incremental IndexBuilder instead of one "
                         "frozen build")
    ap.add_argument("--quantize", action="store_true",
                    help="engine mode: serve the base segment as a "
                         "compressed QuantizedIndex")
    ap.add_argument("--prune-margin", type=float, default=None,
                    metavar="M",
                    help="engine mode: retrieve through the two-tier "
                         "pruned scorer with this margin (0 = safe)")
    ap.add_argument("--remove-frac", type=float, default=0.0,
                    help="engine mode: tombstone this fraction of the "
                         "corpus mid-stream (exercises remove + "
                         "compaction)")
    ap.add_argument("--cache-mb", type=float, default=0.0,
                    metavar="MB",
                    help="engine mode: serve searches through the "
                         "frontier result cache (plus a hot-posting-"
                         "window cache) with this byte budget "
                         "(DESIGN.md §13); 0 = off")
    ap.add_argument("--tenants", type=int, default=0, metavar="N",
                    help="engine mode: serve N weighted tenants over "
                         "one encoder through the TenantPool "
                         "scheduler instead of a single corpus")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching: admit requests into "
                         "the next batch in earliest-deadline-first "
                         "order instead of FIFO one-batch-per-tick")
    args = ap.parse_args(argv)
    # method/rep compatibility is knowable before spending minutes
    # encoding the corpus — reject bad combinations at argparse time
    if args.method in ("dense", "streaming") and args.rep_topk > 0:
        ap.error(f"--method {args.method} needs the dense corpus "
                 f"matrix; pass --rep-topk 0 to keep it (or use "
                 f"--method impact/auto with the sparse index)")
    if args.method in ("impact", "pruned", "quantized", "sharded",
                       "term_sharded", "shard2d") and args.rep_topk <= 0:
        ap.error(f"--method {args.method} needs SparseRep queries and "
                 f"an index; pass a positive --rep-topk")
    if args.shard_axis in ("term", "2d") and args.quantize:
        ap.error(f"--shard-axis {args.shard_axis} and --quantize are "
                 "exclusive (the base segment is either partitioned "
                 "or compressed)")
    if (args.quantize or args.prune_margin is not None
            or args.remove_frac) and not args.engine:
        ap.error("--quantize/--prune-margin/--remove-frac need "
                 "--engine")
    if args.engine and args.rep_topk <= 0:
        ap.error("--engine needs sparse reps; pass a positive "
                 "--rep-topk")
    if args.engine and args.quantize and args.prune_margin is not None:
        ap.error("--quantize and --prune-margin are exclusive (the "
                 "pruned rescorer reads raw forward rows)")
    if args.engine and args.method != "auto":
        ap.error("--engine picks its retrieval path from "
                 "--quantize/--prune-margin; drop --method (the "
                 "builder's segments are searched via 'auto')")
    if (args.cache_mb > 0 or args.tenants > 0) and not args.engine:
        ap.error("--cache-mb/--tenants need --engine (cache keys and "
                 "tenant corpora live on the IndexBuilder)")
    if args.tenants < 0:
        ap.error("--tenants must be >= 0")
    if args.check_method and args.engine:
        ap.error("--check-method compares two methods on one frozen "
                 "index; drop --engine")

    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()

    from repro.configs import get_config
    from repro.launch.steps import init_state
    from repro.retrieval import build_inverted_index, retrieve, stack_rows
    from repro.runtime.serving import (AdmissionPolicy, BatchedEncoder,
                                       BatchPolicy, CorpusEngine,
                                       Request, ServingLoop,
                                       make_config_encoder)

    mod = get_config(args.arch)
    cfg = mod.CONFIG if args.full else mod.SMOKE
    overrides = {}
    if args.head_impl:
        overrides["head_impl"] = args.head_impl
    if args.rep_topk > 0:
        overrides["rep_topk"] = args.rep_topk
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    sparse = args.rep_topk > 0
    state, _ = init_state(args.arch, jax.random.PRNGKey(0),
                          smoke=not args.full)
    params = state["params"]

    # Built from the config via the unified head factory: head_impl,
    # final_logit_softcap and the rep-sparsify knobs are all honored.
    encode = make_config_encoder(params, cfg)

    rng = np.random.default_rng(0)
    bs = args.index_batch

    # --- tenant mode: N corpora over one encoder (DESIGN.md §13) -----
    if args.tenants > 0:
        from repro.runtime.frontier import TenantPool, TenantQuota

        cache_bytes = int(args.cache_mb * 2**20)
        pool = TenantPool(
            BatchedEncoder(encode, policy=BatchPolicy(max_batch=bs)),
            cache_bytes=cache_bytes,
            hot_cache_bytes=cache_bytes // 4,
            continuous=args.continuous)
        names = [f"t{i}" for i in range(args.tenants)]
        for i, name in enumerate(names):
            pool.add_tenant(name, cfg.vocab_size,
                            quota=TenantQuota(weight=float(i + 1)),
                            keep_forward=args.prune_margin is not None)
        t0 = time.monotonic()
        per = max(1, args.corpus // args.tenants)
        for name in names:
            pool.add_docs(name, [
                rng.integers(1, cfg.vocab_size, size=16)
                .astype(np.int32) for _ in range(per)])
        print(f"provisioned {args.tenants} tenants x {per} docs in "
              f"{(time.monotonic() - t0) * 1e3:.1f} ms "
              f"({pool.memory_bytes() / 2**20:.2f} MiB pooled)")
        deadline = (args.deadline_ms / 1e3
                    if args.deadline_ms is not None else None)
        for uid in range(args.requests):
            n = int(rng.integers(4, 24))
            pool.submit(names[uid % args.tenants],
                        Request(uid=uid, tokens=rng.integers(
                            1, cfg.vocab_size, size=n)
                            .astype(np.int32), deadline_s=deadline))
            pool.tick()
        pool.drain()
        from repro.runtime.serving import FailedResult, ShedResult

        by_tenant = {name: [] for name in names}
        for uid in range(args.requests):
            res = pool.take(names[uid % args.tenants], uid)
            if not isinstance(res, (ShedResult, FailedResult)):
                by_tenant[names[uid % args.tenants]].append(res)
        # search twice per tenant: the second pass demonstrates (and
        # reports) result-cache hits when --cache-mb is set. Forcing
        # the fused path (auto would pick impact at demo corpus sizes)
        # also engages the hot-posting-window cache.
        for name in names:
            rows = by_tenant[name][:4]
            if not rows:
                continue
            for _ in range(2 if cache_bytes else 1):
                pool.search(name, stack_rows(rows), args.topk,
                            method="fused")
        st = pool.stats()
        for name in names:
            t = st["tenants"][name]
            line = (f"tenant {name}: weight {t['weight']}, "
                    f"{t['live_docs']} docs, served {t['served']} / "
                    f"shed {t['shed']} / failed {t['failed']}")
            if "cache" in t:
                c = t["cache"]["results"]
                line += (f", cache hits {c['hits']}/"
                         f"{c['hits'] + c['misses']}")
                if "hot" in t["cache"]:
                    line += (f", {t['cache']['hot']['bytes_pinned']} "
                             f"B pinned")
            print(line)
        if "result_cache" in st:
            rc = st["result_cache"]
            print(f"shared result cache: hit ratio {rc['hit_rate']}, "
                  f"{rc['bytes_used']}/{rc['capacity_bytes']} B used, "
                  f"{rc['evictions']} evictions, "
                  f"{rc['invalidations']} invalidations")
        return 0

    # --- 1. index the corpus (batched; never a dense (N, V) matrix) --
    t0 = time.monotonic()
    engine = None
    if args.engine:
        plan = None
        if args.shard_axis == "auto" and not args.quantize:
            # no corpus exists before the build, but the planner only
            # needs sizes: the requested doc count and the sparsifier's
            # per-row term budget bound the posting mass
            from repro.retrieval import CorpusStats, plan_placement

            est = CorpusStats(
                posting_bytes=8 * args.corpus * min(16, args.rep_topk),
                vocab_size=cfg.vocab_size, n_docs=args.corpus)
            plan = plan_placement(est, args.shards)
            print(f"auto shard plan (estimated stats) -> "
                  f"{plan.describe()}: {plan.reason}")
        elif args.shard_axis == "auto":
            print("auto shard axis with --quantize: the base is "
                  "compressed, not partitioned -> doc (single-index "
                  "base)")
        elif args.shard_axis == "2d":
            plan = _grid_plan(args.shards)
            print(f"2d shard plan -> {plan.describe()}")
        engine = CorpusEngine(
            BatchedEncoder(encode,
                           policy=BatchPolicy(max_batch=bs)),
            cfg.vocab_size, quantize=args.quantize,
            keep_forward=args.prune_margin is not None,
            **({"plan": plan} if plan is not None else
               {"shard_axis": ("term" if args.shard_axis == "term"
                               else "doc"),
                "n_shards": args.shards}))
        for lo in range(0, args.corpus, bs):
            n = min(bs, args.corpus - lo)
            toks = [rng.integers(1, cfg.vocab_size, size=16)
                    .astype(np.int32) for _ in range(n)]
            engine.add_docs(toks)
            engine.flush()       # online growth: visible batch by batch
        if args.remove_frac > 0:
            drop = rng.choice(args.corpus,
                              size=int(args.remove_frac * args.corpus),
                              replace=False)
            engine.remove_docs(drop.tolist())
            engine.flush()
        st = engine.stats()
        print(f"engine-indexed {st['n_alive']} live docs "
              f"({st['n_dead']} tombstoned, "
              f"{st['n_compactions']} compactions, "
              f"quantized base: {st['quantized_base']}, "
              f"term shards: {st['term_shards']}) in "
              f"{(time.monotonic() - t0) * 1e3:.1f} ms")
    else:
        doc_parts, dense_parts = [], []
        for lo in range(0, args.corpus, bs):
            n = min(bs, args.corpus - lo)
            toks = rng.integers(1, cfg.vocab_size,
                                size=(n, 16)).astype(np.int32)
            reps = encode(jnp.asarray(toks), jnp.ones((n, 16), jnp.int32))
            if sparse:
                doc_parts.append(reps)
            else:
                dense_parts.append(np.asarray(reps))
        if sparse:
            corpus_rep = stack_rows(doc_parts)
            index = build_inverted_index(
                corpus_rep, cfg.vocab_size,
                keep_forward=args.method == "pruned")
            corpus = index
            st = index.stats()
            print(f"indexed {st['n_docs']} docs in "
                  f"{(time.monotonic() - t0) * 1e3:.1f} ms: "
                  f"{st['n_postings']} postings over "
                  f"{st['active_terms']} terms, "
                  f"{st['memory_bytes'] / 2**20:.2f} MiB "
                  f"(dense (N, V) would be "
                  f"{args.corpus * cfg.vocab_size * 4 / 2**20:.2f} MiB)")
            if args.method == "quantized":
                from repro.retrieval import quantize_index

                corpus = quantize_index(index)
                print(f"quantized index: "
                      f"{corpus.memory_bytes() / 2**20:.2f} MiB "
                      f"(1/{index.memory_bytes() / corpus.memory_bytes():.2f} "
                      f"of raw)")
            elif args.method in ("sharded", "term_sharded", "shard2d"):
                plan = None
                axis = {"term_sharded": "term",
                        "shard2d": "2d"}.get(args.method,
                                             args.shard_axis)
                if axis == "auto":
                    from repro.retrieval import (CorpusStats,
                                                 plan_placement)

                    plan = plan_placement(CorpusStats.from_index(index),
                                          args.shards)
                    axis = plan.axis
                    print(f"auto shard plan -> {plan.describe()}: "
                          f"{plan.reason}")
                if axis == "2d":
                    from repro.retrieval import shard2d_index

                    if plan is None:
                        plan = _grid_plan(args.shards)
                    corpus = shard2d_index(
                        corpus_rep, cfg.vocab_size, plan.doc_shards,
                        plan.term_shards)
                    args.method = "shard2d"
                    print(f"2d-sharded index: {plan.doc_shards} doc "
                          f"chunks x {plan.term_shards} vocab ranges "
                          f"(psum over terms, top-k merge over docs)")
                elif axis == "term":
                    from repro.retrieval import term_shard_index

                    corpus = term_shard_index(corpus_rep,
                                              cfg.vocab_size,
                                              args.shards)
                    args.method = "term_sharded"
                    print(f"term-sharded index: {args.shards} shards "
                          f"x {corpus.local_vocab} vocab terms "
                          f"(partial-sum merge)")
                else:
                    from repro.retrieval import shard_index

                    corpus = shard_index(corpus_rep, cfg.vocab_size,
                                         args.shards)
                    args.method = "sharded"
                    print(f"sharded index: {args.shards} shards x "
                          f"{corpus.docs_per_shard} docs")
        else:
            corpus = jnp.asarray(np.concatenate(dense_parts))
            print(f"indexed {corpus.shape[0]} docs dense in "
                  f"{(time.monotonic() - t0) * 1e3:.1f} ms "
                  f"({corpus.nbytes / 2**20:.2f} MiB)")

    # the frontier cache fronts the engine: repeated searches hit the
    # result cache, the fused path reads pinned hot posting windows
    cached = None
    if args.cache_mb > 0:
        from repro.runtime.frontier import (CachedEngine,
                                            HotPostingCache,
                                            QueryResultCache)

        cache_bytes = int(args.cache_mb * 2**20)
        cached = CachedEngine(
            engine, result_cache=QueryResultCache(cache_bytes),
            hot_cache=HotPostingCache(cache_bytes // 4))

    # --- 2. serve queries through the batching loop ------------------
    loop = ServingLoop(
        BatchedEncoder(encode, policy=BatchPolicy(max_batch=16,
                                                  max_wait_s=0.002)),
        admission=AdmissionPolicy(max_queue_depth=args.max_queue),
        continuous=args.continuous)
    deadline = (args.deadline_ms / 1e3
                if args.deadline_ms is not None else None)
    t0 = time.monotonic()
    for uid in range(args.requests):
        n = int(rng.integers(4, 24))
        loop.submit(Request(uid=uid, tokens=rng.integers(
            1, cfg.vocab_size, size=n).astype(np.int32),
            deadline_s=deadline))
        loop.tick()
    loop.drain()
    dt = time.monotonic() - t0
    # the hardened loop completes every uid, but under a deadline some
    # may carry ShedResult/FailedResult — retrieval gets the served reps
    from repro.runtime.serving import FailedResult, ShedResult

    outcomes = {uid: loop.take(uid) for uid in range(args.requests)}
    assert not loop.completed, "take() must leave nothing behind"
    results = [r for r in outcomes.values()
               if not isinstance(r, (ShedResult, FailedResult))]
    st = loop.stats()
    print(f"encoded {len(results)}/{args.requests} requests in "
          f"{dt*1e3:.1f} ms ({st['shed']} shed, {st['failed']} "
          f"failed), batches: {list(loop.batch_sizes)}, "
          f"occupancy {st['batch_occupancy']:.2f}, "
          f"p99 {st['p99_latency_s'] * 1e3:.1f} ms")
    if st["failed"]:
        # an encode that raised (a kernel that does not compile, a lost
        # device) fails the run; shedding under a deadline does not
        print(f"FAILED: {st['failed']} requests failed to encode",
              file=sys.stderr)
        return 1
    if not results:
        print("every request shed — deadline too tight for this "
              "host; nothing to retrieve")
        return 0

    # --- 3. retrieval through the unified dispatcher ------------------
    n_q = len(results)
    if sparse:
        queries = stack_rows(results)
    else:
        queries = jnp.asarray(np.stack(results))
    t0 = time.monotonic()
    if engine is not None:
        kw = {}
        if args.prune_margin is not None:
            kw = {"method": "pruned",
                  "prune_margin": args.prune_margin}
        surface = cached if cached is not None else engine
        if cached is not None and not kw:
            # force the fused path (auto picks impact at demo corpus
            # sizes) so the hot-posting-window cache engages too
            kw = {"method": "fused"}
        vals, idx = surface.search(queries, args.topk, **kw)
        if cached is not None:
            # the second pass is pure cache: every row keyed identically
            vals, idx = surface.search(queries, args.topk, **kw)
        tag = "engine" + ("/pruned" if args.prune_margin is not None
                          else "")
        tag += "/cached" if cached is not None else ""
    else:
        vals, idx = retrieve(queries, corpus, args.topk,
                             method=args.method)
        tag = args.method
    jax.block_until_ready(vals)
    print(f"retrieval[{tag}]: top-{args.topk} for {n_q} queries "
          f"in {(time.monotonic() - t0) * 1e3:.1f} ms, "
          f"best scores {np.asarray(vals)[:8, 0].round(2).tolist()}")
    if args.check_method:
        _, ref_idx = retrieve(queries, corpus, args.topk,
                              method=args.check_method)
        equal = bool(np.array_equal(np.asarray(idx), np.asarray(ref_idx)))
        print(f"retrieval[{tag}] ids equal to "
              f"retrieval[{args.check_method}]: {equal}")
        if not equal:
            return 1
    if cached is not None:
        cs = cached.stats()
        rc, hot = cs["results"], cs.get("hot")
        line = (f"frontier cache: hit ratio {rc['hit_rate']}, "
                f"{rc['bytes_used']}/{rc['capacity_bytes']} B used, "
                f"{rc['evictions']} evictions, "
                f"{rc['invalidations']} invalidations")
        if hot is not None:
            line += (f"; hot windows: {hot['pinned_terms']} terms, "
                     f"{hot['bytes_pinned']} B pinned")
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
