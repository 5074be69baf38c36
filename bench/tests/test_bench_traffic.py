"""The traffic generator: lengths, determinism, the same work per seed."""

import numpy as np

from bench import traffic
from bench.tests import _small

SPEC = _small.load("traffic", "train_pairs_256")
PAIRS = {"pairs": 64, "query": SPEC["query"], "doc": SPEC["doc"]}
BIG_SEED = 2 ** 40 + 12345


def test_lengths_follow_the_ms_marco_spec():
    q = traffic.stratified_lengths(256, PAIRS["query"])
    d = traffic.stratified_lengths(256, PAIRS["doc"])
    assert 9 <= q.mean() <= 11 and q.min() >= 4 and q.max() <= 32
    assert 70 <= d.mean() <= 86 and d.min() >= 16 and d.max() <= 256
    # heavy tail: the longest documents are several times the median
    assert d.max() > 3 * np.median(d)


def test_same_seed_same_batches_and_wide_seeds():
    a = next(traffic.pair_batches(PAIRS, 30522, BIG_SEED))
    b = next(traffic.pair_batches(PAIRS, 30522, BIG_SEED))
    c = next(traffic.pair_batches(PAIRS, 30522, BIG_SEED + 2 ** 33))
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    assert not np.array_equal(a["d_tokens"], c["d_tokens"])


def test_every_seed_and_batch_does_the_same_work():
    counts = set()
    for seed in (1, 2, BIG_SEED):
        it = traffic.pair_batches(PAIRS, 250002, seed)
        for _ in range(3):
            b = next(it)
            counts.add((int(b["q_mask"].sum()), int(b["d_mask"].sum())))
            assert b["q_tokens"].shape == (64, 32)
            assert b["d_tokens"].shape == (64, 256)
            assert b["q_tokens"].max() < 250002
            # padded doc positions hold token 0; the splice is in place
            assert (b["d_tokens"][b["d_mask"] == 0] == 0).all()
            assert (b["d_tokens"][:, :16] == b["q_tokens"][:, :16]).all()
    assert len(counts) == 1


def test_doc_batches():
    spec = _small.load("traffic", "encode_docs_2560")
    b = next(traffic.doc_batches(spec, 30522, BIG_SEED))
    assert b["tokens"].shape == b["mask"].shape == (spec["docs"], 256)
    assert b["mask"][:, 0].all() and (b["tokens"][b["mask"] == 0] == 0).all()
