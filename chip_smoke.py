"""Smoke test of the main path on a TPU: ``python chip_smoke.py``.

Runs, in this one process, on one chip:

  parity  the Pallas Sparton head (``ops.sparton_head``), forward and
          gradients, against the f32 ``naive`` head on the chip, at
          V=30522 (B=32, S=256) and at V=250002 (B=4, S=256), D=768;
  train   ``repro.launch.train`` at the full width of ``splade_bert``
          (12L, d=768, V=30522) with the kernel head for a few steps,
          its step-0 loss checked against ``--head-impl naive``;
  serve   ``repro.launch.serve`` at the same width: a corpus encoded
          through the kernel head and indexed, a few dozen requests
          served, their fused top-k ids checked against ``impact``;
          then the index compressed to u4 and ``quantized`` ids
          checked against the fused kernel's in-kernel decode.

``--chips 4`` runs instead, and only, the paths that exist across
chips: one data-parallel ``build_lsr_train_step`` on a (data=4,
model=1) mesh against the same global batch on one chip, and the 2D
(doc x term) sharded index on a 2x2 mesh against single-device
``impact`` ids.

Weights and data are random, made from fixed seeds. Every result goes
to earlier lines; the last line is one JSON object naming the device,
printed only when every phase passed. Without a TPU the script exits
non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import os
import re
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, ".smoke")

# (name, B, S, V) of the head parity cases; D is the model width.
PARITY_CASES = (("bert", 32, 256, 30522), ("xlmr", 4, 256, 250002))
PARITY_D = 768
TRAIN_ARGS = ["--arch", "splade_bert", "--full", "--steps", "3",
              "--batch", "32", "--seq-len", "256", "--ckpt-every", "0"]
SERVE_ARGS = ["--arch", "splade_bert", "--full", "--head-impl", "kernel",
              "--corpus", "4096", "--requests", "32"]
# (--method, --check-method): the fused kernel against the plain
# scorer on the raw index, then (the index compressed to u4) the plain
# quantized scorer against the fused kernel's in-kernel decode
SERVE_CHECKS = (("fused", "impact"), ("quantized", "fused"))
# four-chip cases: global batch of the data-parallel step, and the
# corpus of the 2D index
DP_BATCH, DP_SEQ = 32, 128
GRID_DOCS, GRID_NNZ, GRID_QUERIES, GRID_Q_NNZ = 16384, 64, 32, 32

# bf16 operands: the kernel and the f32 reference see the same values,
# so the forward agrees to f32 rounding; gradients come back in bf16
# and a near-tie in the argmax may route one (b, v) to another
# position, so they are compared by relative norm.
FWD_TOL, GRAD_TOL, LOSS_TOL = 1e-3, 2e-2, 2e-2


def rel_err(a, b):
    """(max abs error, max abs error / max |b|, relative norm error)."""
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    diff = np.abs(a - b)
    scale = max(float(np.max(np.abs(b))), 1e-30)
    norm = max(float(np.linalg.norm(b)), 1e-30)
    return (float(diff.max()), float(diff.max()) / scale,
            float(np.linalg.norm(a - b)) / norm)


def phase_parity():
    import jax
    import jax.numpy as jnp

    from repro.core.lm_head import lm_head_naive
    from repro.kernels.ops import sparton_head

    ok = True
    for name, B, S, V in PARITY_CASES:
        ks = jax.random.split(jax.random.PRNGKey(11), 5)
        H = jax.random.normal(ks[0], (B, S, PARITY_D), jnp.bfloat16)
        E = (jax.random.normal(ks[1], (V, PARITY_D)) * 0.05).astype(
            jnp.bfloat16)
        b = jax.random.normal(ks[2], (V,), jnp.float32) * 0.1
        mask = (jax.random.uniform(ks[3], (B, S)) > 0.1).astype(jnp.int32)
        mask = mask.at[:, 0].set(1)
        G = jax.random.normal(ks[4], (B, V), jnp.float32)

        def kernel_loss(H, E, b):
            y = sparton_head(H, E, b, mask, out_dtype=jnp.float32)
            return jnp.sum(y * G), y

        def naive_loss(H, E, b):
            y = lm_head_naive(H.astype(jnp.float32), E.astype(jnp.float32),
                              b, mask)
            return jnp.sum(y * G), y

        grad = lambda f: jax.jit(jax.grad(f, (0, 1, 2), has_aux=True))
        (gk, yk), (gn, yn) = grad(kernel_loss)(H, E, b), \
            grad(naive_loss)(H, E, b)
        fwd = rel_err(yk, yn)
        print(f"parity {name} V={V} B={B} S={S}: y max abs "
              f"{fwd[0]:.3e}, max rel {fwd[1]:.3e}")
        ok &= fwd[1] <= FWD_TOL
        for gname, a, r in zip(("dH", "dE", "db"), gk, gn):
            e = rel_err(a, r)
            print(f"parity {name} {gname}: max abs {e[0]:.3e}, max rel "
                  f"{e[1]:.3e}, rel norm {e[2]:.3e}")
            ok &= e[2] <= GRAD_TOL
    return ok


class _Tee(io.TextIOBase):
    """Copies what an entry point prints into a buffer as well."""

    def __init__(self, stream):
        self.stream, self.buf = stream, io.StringIO()

    def write(self, s):
        self.stream.write(s)
        self.buf.write(s)
        return len(s)

    def flush(self):
        self.stream.flush()


def _run_main(main, argv):
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        rc = main(argv)
    return rc, tee.buf.getvalue()


def _train(head_impl, steps):
    from repro.launch import train

    ckpt = os.path.join(WORK, f"ckpt_{head_impl}")
    shutil.rmtree(ckpt, ignore_errors=True)
    argv = TRAIN_ARGS + ["--head-impl", head_impl, "--ckpt-dir", ckpt]
    argv[argv.index("--steps") + 1] = str(steps)
    rc, out = _run_main(train.main, argv)
    shutil.rmtree(ckpt, ignore_errors=True)
    m = re.search(r"loss (\S+) \(first (\S+)\)", out)
    if rc != 0 or m is None:
        print(f"train --head-impl {head_impl}: rc {rc}, no loss line")
        return None
    return float(m.group(2)), float(m.group(1))


def phase_train():
    import math

    kernel = _train("kernel", 3)
    naive = _train("naive", 1)
    if kernel is None or naive is None:
        return False
    (k0, k_last), (n0, _) = kernel, naive
    rel = abs(k0 - n0) / max(abs(n0), 1.0)
    print(f"train: kernel head loss {k0!r} -> "
          f"{k_last!r}; naive head step-0 loss {n0!r}; rel diff "
          f"{rel:.3e}")
    return math.isfinite(k_last) and rel <= LOSS_TOL


def phase_serve():
    from repro.launch import serve

    ok = True
    for method, check in SERVE_CHECKS:
        rc, out = _run_main(serve.main, SERVE_ARGS + [
            "--method", method, "--check-method", check])
        equal = f"ids equal to retrieval[{check}]: True" in out
        print(f"serve --method {method}: rc {rc}, ids equal {check} "
              f"ids: {equal}")
        ok &= rc == 0 and equal
    return ok


def _check_spmd(compiled, n, what, kernel):
    """Every argument and result spans all n devices; with ``kernel``
    the Pallas kernel is in the (one, SPMD) program."""
    import jax

    shardings = jax.tree.leaves((compiled.input_shardings,
                                 compiled.output_shardings))
    alone = [s for s in shardings if len(s.device_set) != n]
    has_kernel = "tpu_custom_call" in compiled.as_text()
    print(f"{what}: {len(shardings)} arrays, {len(alone)} not on all "
          f"{n} devices; Pallas kernel in the program: {has_kernel}")
    return not alone and (has_kernel or not kernel)


def phase_dp_train(n):
    import jax
    import numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.data.synthetic import lsr_pair_batches
    from repro.launch.steps import build_lsr_train_step, init_state

    cfg = dataclasses.replace(get_config("splade_bert").CONFIG,
                              head_impl="kernel")
    state, _ = init_state("splade_bert", jax.random.PRNGKey(0))
    raw = next(lsr_pair_batches(batch=DP_BATCH, q_len=DP_SEQ,
                                d_len=DP_SEQ, vocab=cfg.vocab_size))
    batch = {k: raw[k] for k in ("q_tokens", "q_mask", "d_tokens",
                                 "d_mask")}

    one = jax.devices()[0]
    step1 = jax.jit(build_lsr_train_step(cfg, None, n_pairs=DP_BATCH))
    ref_state, ref_m = step1(jax.device_put(state, one),
                             jax.device_put(batch, one))

    mesh = jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    stepm = jax.jit(build_lsr_train_step(cfg, mesh, n_pairs=DP_BATCH))
    placed_state = jax.device_put(state, NamedSharding(mesh, P()))
    placed_batch = jax.device_put(batch, NamedSharding(mesh,
                                                       P("data", None)))
    compiled = stepm.lower(placed_state, placed_batch).compile()
    ok = _check_spmd(compiled, n, f"dp train step (data={n})", True)
    new_state, m = compiled(placed_state, placed_batch)

    lr = abs(float(m["loss"]) - float(ref_m["loss"])) / max(
        abs(float(ref_m["loss"])), 1.0)
    print(f"dp train: loss {float(m['loss'])!r} vs one chip "
          f"{float(ref_m['loss'])!r}, rel diff {lr:.3e}")
    ok &= lr <= LOSS_TOL
    # relative norm over each whole tree: Adam's first update is close
    # to lr * sign(g), so a gradient near zero may flip sign between two
    # summation orders; only the tree as a whole is compared
    flat = lambda t: np.concatenate([np.ravel(np.asarray(x, np.float64))
                                     for x in jax.tree.leaves(t)])
    for part in ("params", "opt"):
        err = rel_err(flat(new_state[part]), flat(ref_state[part]))[2]
        print(f"dp train: updated {part} rel norm diff {err:.3e}")
        ok &= err <= GRAD_TOL
    return ok


def phase_grid(n):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.retrieval import (SparseRep, build_inverted_index, retrieve,
                                 shard2d_index, shard2d_retrieve)

    vocab = 30522
    rng = np.random.default_rng(5)

    def rows(n_rows, nnz):
        idx = np.stack([np.sort(rng.choice(vocab, nnz, replace=False))
                        for _ in range(n_rows)]).astype(np.int32)
        val = rng.uniform(0.05, 3.0, (n_rows, nnz)).astype(np.float32)
        return SparseRep(jnp.asarray(val), jnp.asarray(idx),
                         jnp.full((n_rows,), nnz, jnp.int32))

    docs, queries = rows(GRID_DOCS, GRID_NNZ), rows(GRID_QUERIES,
                                                    GRID_Q_NNZ)
    _, ref = retrieve(queries, build_inverted_index(docs, vocab), 10,
                      method="impact")
    side = int(round(n ** 0.5))
    index = shard2d_index(docs, vocab, side, n // side)
    mesh = jax.make_mesh((side, n // side), ("doc", "term"),
                         axis_types=(AxisType.Auto,) * 2)
    grid = NamedSharding(mesh, P("doc", "term"))
    placed = dataclasses.replace(index, **{
        f: jax.device_put(getattr(index, f), s) for f, s in (
            ("term_starts", grid), ("term_lens", grid),
            ("postings_doc", grid), ("postings_val", grid),
            ("term_ubs", grid),
            ("term_lo", NamedSharding(mesh, P("term"))),
            ("term_hi", NamedSharding(mesh, P("term"))),
            ("chunk_starts", NamedSharding(mesh, P("doc"))),
            ("chunk_counts", NamedSharding(mesh, P("doc"))))})
    placed_q = jax.device_put(queries, NamedSharding(mesh, P()))
    fn = jax.jit(lambda q, ix: shard2d_retrieve(q, ix, 10, mesh=mesh))
    compiled = fn.lower(placed_q, placed).compile()
    ok = _check_spmd(compiled, n, f"shard2d retrieve ({side}x{n // side})",
                     False)
    _, idx = compiled(placed_q, placed)
    equal = bool(np.array_equal(np.asarray(idx), np.asarray(ref)))
    print(f"shard2d {side}x{n // side}: {GRID_DOCS} docs, "
          f"{GRID_QUERIES} queries, ids equal impact ids: {equal}")
    return ok and equal


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel train step and "
                         "the 2D sharded index across four chips")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no src/repro beside {__file__}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    os.makedirs(WORK, exist_ok=True)
    # blocks come from a fixed file in the checkout, never from $HOME
    os.environ["SPARTON_AUTOTUNE_CACHE"] = os.path.join(
        ROOT, ".autotune", "chip_smoke.json")

    import jax

    from repro.launch.compile_cache import setup_compile_cache

    print(f"compile cache: {setup_compile_cache()}")
    devices = jax.devices()
    dev = devices[0]
    print(f"devices: {len(devices)} x {dev.platform} {dev.device_kind}")
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; nothing measured",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "devices", file=sys.stderr)
        return 1

    if args.chips == 1:
        phases = [("parity", phase_parity), ("train", phase_train),
                  ("serve", phase_serve)]
    else:
        phases = [("dp_train", lambda: phase_dp_train(args.chips)),
                  ("grid", lambda: phase_grid(args.chips))]
    failed = []
    for name, fn in phases:
        t0 = time.monotonic()
        try:
            ok = fn()
        except Exception:
            traceback.print_exc()
            ok = False
        print(f"phase {name}: {'ok' if ok else 'FAILED'} "
              f"({time.monotonic() - t0:.1f} s wall, compile included)",
              flush=True)
        if not ok:
            failed.append(name)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
