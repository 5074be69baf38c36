"""Multi-device semantics: the vocab-sharded Sparton head, sharded
InfoNCE/FLOPS, expert-parallel MoE and compressed all-reduce must match
their single-device references bit-for-bit (up to fp tolerance).

Runs in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the main pytest process keeps its single CPU device (per the
assignment: never set the flag globally).
"""

import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    from repro.core.lm_head import lm_head_sparton
    from repro.core.sharded import (sharded_sparton_head, sharded_infonce,
                                    sharded_flops_reg)
    from repro.losses.contrastive import infonce_loss, flops_regularizer

    B, S, D, V = 4, 24, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    H = jax.random.normal(ks[0], (B, S, D))
    E = jax.random.normal(ks[1], (V, D)) * 0.3
    b = jax.random.normal(ks[2], (V,)) * 0.1
    mask = (jax.random.uniform(ks[3], (B, S)) > 0.2).astype(jnp.int32)
    mask = mask.at[:, 0].set(1)

    # ---- sharded sparton head == local head --------------------------
    head = sharded_sparton_head(mesh, batch_axes=("data",), vocab_tile=16)
    with jax.set_mesh(mesh):
        y_sharded = jax.jit(head)(H, E, b, mask)
    y_local = lm_head_sparton(H, E, b, mask, vocab_tile=16)
    np.testing.assert_allclose(np.asarray(y_sharded), np.asarray(y_local),
                               atol=1e-5, rtol=1e-5)
    print("OK sharded head forward")

    # ---- gradients through the sharded head --------------------------
    def loss_sharded(H, E, b):
        return jnp.sum(jnp.sin(head(H, E, b, mask)))
    def loss_local(H, E, b):
        return jnp.sum(jnp.sin(lm_head_sparton(H, E, b, mask,
                                               vocab_tile=16)))
    with jax.set_mesh(mesh):
        gs = jax.jit(jax.grad(loss_sharded, (0, 1, 2)))(H, E, b)
    gl = jax.grad(loss_local, (0, 1, 2))(H, E, b)
    for a, c in zip(gs, gl):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   atol=1e-4, rtol=1e-4)
    print("OK sharded head grads")

    # ---- sharded infonce == plain infonce ----------------------------
    yq = jax.random.normal(ks[4], (B, V))
    yd = jax.random.normal(jax.random.PRNGKey(9), (B, V))
    inf = sharded_infonce(mesh, batch_axes=("data",))
    with jax.set_mesh(mesh):
        l_sharded = jax.jit(inf)(yq, yd)
    l_plain = infonce_loss(yq, yd)
    np.testing.assert_allclose(float(l_sharded), float(l_plain), atol=1e-5)
    print("OK sharded infonce")

    # ---- sharded flops reg == plain -----------------------------------
    fl = sharded_flops_reg(mesh, batch_axes=("data",))
    with jax.set_mesh(mesh):
        f_sharded = jax.jit(fl)(jnp.abs(yq))
    f_plain = flops_regularizer(jnp.abs(yq))
    np.testing.assert_allclose(float(f_sharded), float(f_plain),
                               atol=1e-4, rtol=1e-5)
    print("OK sharded flops")

    # ---- sharded l1 reg == plain --------------------------------------
    from repro.core.sharded import sharded_l1_reg, sharded_row_dots
    from repro.losses.contrastive import l1_regularizer, gathered_infonce
    l1 = sharded_l1_reg(mesh, batch_axes=("data",))
    with jax.set_mesh(mesh):
        l1_sharded = jax.jit(l1)(jnp.abs(yq))
    np.testing.assert_allclose(float(l1_sharded),
                               float(l1_regularizer(jnp.abs(yq))),
                               atol=1e-4, rtol=1e-5)
    print("OK sharded l1")

    # ---- sharded row dots == per-row einsum ---------------------------
    rd = sharded_row_dots(mesh, batch_axes=("data",))
    with jax.set_mesh(mesh):
        dots = jax.jit(rd)(yq, yd)
    np.testing.assert_allclose(np.asarray(dots),
                               np.asarray(jnp.einsum("bv,bv->b", yq, yd)),
                               atol=1e-4, rtol=1e-5)
    print("OK sharded row dots")

    # ---- gathered infonce over the data axis == global infonce --------
    gi = jax.shard_map(
        lambda a, c: gathered_infonce(a, c, axis_names=("data",)),
        mesh=mesh, in_specs=(P("data", None), P("data", None)),
        out_specs=P(), check_vma=False)
    with jax.set_mesh(mesh):
        l_gathered = jax.jit(gi)(yq, yd)
    np.testing.assert_allclose(float(l_gathered),
                               float(infonce_loss(yq, yd)), atol=1e-5)
    print("OK gathered infonce")

    # ---- expert-parallel MoE == local MoE -----------------------------
    from repro.models.moe import moe_ffn, moe_ffn_local_experts
    T, Dm, F, Eexp = 16, 8, 12, 4
    x = jax.random.normal(jax.random.PRNGKey(11), (T, Dm))
    router = jax.random.normal(jax.random.PRNGKey(12), (Dm, Eexp))
    wg = jax.random.normal(jax.random.PRNGKey(13), (Eexp, Dm, F)) * 0.3
    wu = jax.random.normal(jax.random.PRNGKey(14), (Eexp, Dm, F)) * 0.3
    wd = jax.random.normal(jax.random.PRNGKey(15), (Eexp, F, Dm)) * 0.3
    out_local, aux_local = moe_ffn(x, router, wg, wu, wd, top_k=2,
                                   capacity_factor=8.0)
    import functools
    body = functools.partial(moe_ffn_local_experts, top_k=2,
                             capacity_factor=8.0, expert_axis="model",
                             token_axes=("data",))
    fn = jax.shard_map(body, mesh=mesh,
                   in_specs=(P("data", None), P(None, None),
                             P("model", None, None), P("model", None, None),
                             P("model", None, None)),
                   out_specs=(P("data", None), P()))
    with jax.set_mesh(mesh):
        out_ep, aux_ep = jax.jit(fn)(x, router, wg, wu, wd)
    # high capacity => no drops on either path => identical outputs
    np.testing.assert_allclose(np.asarray(out_ep), np.asarray(out_local),
                               atol=1e-4, rtol=1e-4)
    print("OK expert-parallel moe")

    # ---- compressed all-reduce ~= mean --------------------------------
    from repro.optim.compression import compressed_allreduce
    g_tree = {"w": jax.random.normal(jax.random.PRNGKey(20), (8, 64)),
              "b": jax.random.normal(jax.random.PRNGKey(21), (8, 16))}

    def car(gw, gb):
        mean, resid = compressed_allreduce({"w": gw, "b": gb}, None,
                                           "data")
        return mean["w"], mean["b"]
    fn2 = jax.shard_map(car, mesh=mesh,
                    in_specs=(P("data", None), P("data", None)),
                    out_specs=(P(None, None), P(None, None)),
                    check_vma=False)
    with jax.set_mesh(mesh):
        mw, mb = jax.jit(fn2)(g_tree["w"], g_tree["b"])
    # each data shard holds 4 rows; mean over the 2 shards
    ref_w = (np.asarray(g_tree["w"][:4]) + np.asarray(g_tree["w"][4:])) / 2
    rel = np.abs(np.asarray(mw) - ref_w).max() / np.abs(ref_w).max()
    assert rel < 0.03, f"int8 allreduce rel err {rel}"
    print("OK compressed allreduce")

    # ---- distributed gather/scatter (GNN §Perf machinery) -------------
    from repro.sparse.distributed import (distributed_take_local,
                                          distributed_segment_sum_local)
    axes2 = ("data", "model")
    rows, dd, R = 64, 16, 4096
    src2 = jax.random.normal(jax.random.PRNGKey(30), (rows, dd))
    idx2 = jax.random.randint(jax.random.PRNGKey(31), (R,), 0, rows)
    take2 = jax.shard_map(
        lambda s, i: distributed_take_local(s, i, axis_names=axes2),
        mesh=mesh, in_specs=(P(axes2, None), P(axes2)),
        out_specs=(P(axes2, None), P()), check_vma=False)
    with jax.set_mesh(mesh):
        got, ndrop = jax.jit(take2)(src2, idx2)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.take(src2, idx2, axis=0)),
                               atol=1e-6)
    assert int(ndrop) == 0
    print("OK distributed take")

    vals2 = jax.random.normal(jax.random.PRNGKey(32), (R, dd))
    dst2 = jax.random.randint(jax.random.PRNGKey(33), (R,), 0, rows)
    scat2 = jax.shard_map(
        lambda v, i: distributed_segment_sum_local(
            v, i, rows // 8, axis_names=axes2),
        mesh=mesh, in_specs=(P(axes2, None), P(axes2)),
        out_specs=(P(axes2, None), P()), check_vma=False)
    with jax.set_mesh(mesh):
        out3, ndrop3 = jax.jit(scat2)(vals2, dst2)
    np.testing.assert_allclose(
        np.asarray(out3),
        np.asarray(jax.ops.segment_sum(vals2, dst2, num_segments=rows)),
        atol=1e-4)
    assert int(ndrop3) == 0
    print("OK distributed scatter")

    # ---- row-sharded embedding lookup ---------------------------------
    from repro.sparse.sharded_embedding import make_sharded_lookup
    table = jax.random.normal(jax.random.PRNGKey(22), (32, 8))
    idx = jnp.array([0, 5, 17, 31, 8])
    lookup = make_sharded_lookup(mesh, axis_name="model")
    with jax.set_mesh(mesh):
        out = jax.jit(lookup)(table, idx)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.take(table, idx, axis=0)),
                               atol=1e-6)
    print("OK sharded embedding")

    print("ALL_SHARDED_TESTS_PASSED")
""")


def test_sharded_semantics_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=env,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (
        f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr[-4000:]}")
    assert "ALL_SHARDED_TESTS_PASSED" in proc.stdout
