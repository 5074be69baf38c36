"""Data pipeline: determinism, shard disjointness, shapes, loader."""

import glob

import jax
import numpy as np
import pytest

from repro.data.loader import HostShardedLoader, length_bucket
from repro.data.synthetic import (lm_token_batches, lsr_pair_batches,
                                  molecule_batches, recsys_batches)


def test_lsr_batches_deterministic_per_shard_step():
    g1 = lsr_pair_batches(batch=4, q_len=8, d_len=12, vocab=100, seed=1)
    g2 = lsr_pair_batches(batch=4, q_len=8, d_len=12, vocab=100, seed=1)
    b1, b2 = next(g1), next(g2)
    for k in b1:
        np.testing.assert_array_equal(b1[k], b2[k])


def test_lsr_shards_are_disjoint():
    b0 = next(lsr_pair_batches(batch=4, q_len=8, d_len=8, vocab=1000,
                               seed=1, shard=0))
    b1 = next(lsr_pair_batches(batch=4, q_len=8, d_len=8, vocab=1000,
                               seed=1, shard=1))
    assert not np.array_equal(b0["q_tokens"], b1["q_tokens"])


def test_lsr_masks_and_overlap():
    b = next(lsr_pair_batches(batch=8, q_len=16, d_len=16, vocab=500))
    assert b["q_mask"].shape == (8, 16)
    assert ((b["q_mask"] == 0) | (b["q_mask"] == 1)).all()
    # positives share a token prefix with their query (learnability)
    n_copy = 8
    np.testing.assert_array_equal(b["d_tokens"][:, :4] * b["d_mask"][:, :4],
                                  b["q_tokens"][:, :4] * b["d_mask"][:, :4])


def test_lm_batches_next_token_alignment():
    b = next(lm_token_batches(batch=2, seq_len=10, vocab=50))
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


def test_recsys_ids_in_range():
    sizes = (100, 5, 1000)
    b = next(recsys_batches(batch=32, n_dense=3, n_sparse=3,
                            table_sizes=sizes))
    for f, rows in enumerate(sizes):
        col = b["sparse_idx"][:, f]
        assert (col >= 0).all() and (col < rows).all()
    assert set(np.unique(b["label"])) <= {0.0, 1.0}


def test_molecule_batches_structure():
    b = next(molecule_batches(n_graphs=3, nodes_per_graph=6,
                              edges_per_graph=10))
    N = 18
    assert b["positions"].shape == (N, 3)
    assert b["node_graph_id"].max() == 2
    e_valid = b["edge_mask"].astype(bool)
    assert (b["edge_src"][e_valid] < N).all()
    # edges connect nodes within the same graph
    g_src = b["node_graph_id"][b["edge_src"][e_valid]]
    g_dst = b["node_graph_id"][b["edge_dst"][e_valid]]
    np.testing.assert_array_equal(g_src, g_dst)


def test_host_sharded_loader_prefetch():
    def make_iter(shard, n_shards):
        for i in range(5):
            yield {"x": np.full((2,), i)}

    loader = HostShardedLoader(make_iter, prefetch=2)
    got = [b["x"][0] for b in loader]
    assert got == [0, 1, 2, 3, 4]


@pytest.mark.parametrize("profiled", [False, True])
def test_host_sharded_loader_yields_the_source_batches(profiled, tmp_path):
    def make_iter(shard, n_shards):
        rng = np.random.default_rng(shard)
        for _ in range(4):
            yield {"x": rng.standard_normal(3)}

    want = list(make_iter(0, 1))
    if profiled:
        jax.profiler.start_trace(str(tmp_path))
    try:
        got = list(HostShardedLoader(make_iter))
    finally:
        if profiled:
            jax.profiler.stop_trace()
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g["x"], w["x"])
    if profiled:
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        pd = jax.profiler.ProfileData.from_file(path)
        waits = [ev for plane in pd.planes if plane.name.startswith("/host:")
                 for line in plane.lines for ev in line.events
                 if ev.name == "loader.next"]
        # one span a call: four batches and the call that ends the loop
        assert len(waits) == 5


def test_length_bucket():
    buckets = length_bucket([3, 10, 64, 7, 100], [8, 32])
    assert buckets[0] == [0, 3]     # <= 8
    assert buckets[1] == [1]        # <= 32
    assert buckets[2] == [2, 4]     # > 32
