"""Sparse-native retrieval pipeline: SparseRep, inverted impact index,
and the unified retrieve() dispatcher (DESIGN.md §7).

The acceptance anchor is the three-way parity test: inverted-index
impact scoring, the streaming topk_score kernel, and the dense einsum
fallback must return identical top-k doc ids (scores within fp
tolerance) from the same SparseRep/dense inputs.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.retrieval import (InvertedIndex, SparseRep,
                             build_inverted_index, impact_scores,
                             retrieve, sparsify_threshold, sparsify_topk,
                             split_rows, stack_rows)

V = 128


def _sparse_mat(rng, n, nnz, vocab=V):
    m = np.zeros((n, vocab), np.float32)
    for r in range(n):
        cols = rng.choice(vocab, size=nnz, replace=False)
        m[r, cols] = rng.uniform(0.1, 2.0, size=nnz)
    return m


@pytest.fixture
def corpus():
    rng = np.random.default_rng(0)
    Q = _sparse_mat(rng, 5, 8)
    D = _sparse_mat(rng, 40, 10)
    return Q, D


# ---------------------------------------------------------------------------
# SparseRep + sparsifiers
# ---------------------------------------------------------------------------

def test_sparsify_roundtrip_exact_when_under_budget(corpus):
    Q, _ = corpus
    rep = sparsify_threshold(jnp.asarray(Q), 0.0, max_nnz=16)
    assert np.all(np.asarray(rep.nnz) == 8)
    np.testing.assert_allclose(np.asarray(rep.to_dense(V)), Q, atol=1e-6)
    # active slots are a prefix, sorted by value descending
    vals = np.asarray(rep.values)
    assert (vals[:, :8] > 0).all() and (vals[:, 8:] == 0).all()
    assert (np.diff(vals[:, :8], axis=1) <= 1e-6).all()


def test_sparsify_topk_keeps_largest():
    x = jnp.asarray([[0.5, 0.0, 2.0, 1.0, 0.0, 3.0]])
    rep = sparsify_topk(x, 2, tile=2)   # multiple tiles
    assert int(rep.nnz[0]) == 2
    np.testing.assert_array_equal(np.asarray(rep.indices)[0, :2], [5, 2])
    np.testing.assert_allclose(np.asarray(rep.values)[0, :2], [3.0, 2.0])


def test_sparsify_threshold_drops_small_entries():
    x = jnp.asarray([[0.5, 0.05, 2.0, 0.0, -1.0]])
    rep = sparsify_threshold(x, 0.1, max_nnz=4)
    assert int(rep.nnz[0]) == 2          # 2.0 and 0.5; never negatives
    dense = np.asarray(rep.to_dense(5))
    np.testing.assert_allclose(dense, [[0.5, 0.0, 2.0, 0.0, 0.0]])


def test_sparsify_tie_break_to_lowest_vocab_id():
    """Equal values across tile boundaries: lowest id wins the budget
    (the merge-stability invariant from kernels/topk_score)."""
    x = np.zeros((1, 64), np.float32)
    x[0, [3, 40, 50]] = 1.0              # three equal entries
    rep = sparsify_topk(jnp.asarray(x), 2, tile=16)
    np.testing.assert_array_equal(np.asarray(rep.indices)[0, :2], [3, 40])


def test_sparse_rep_is_a_pytree(corpus):
    Q, _ = corpus
    rep = sparsify_topk(jnp.asarray(Q), 8)
    doubled = jax.jit(lambda r: SparseRep(r.values * 2, r.indices,
                                          r.nnz))(rep)
    np.testing.assert_allclose(np.asarray(doubled.to_dense(V)), 2 * Q,
                               atol=1e-5)


def test_split_and_stack_rows_roundtrip(corpus):
    _, D = corpus
    rep = sparsify_topk(jnp.asarray(D), 12)
    back = stack_rows(split_rows(rep))
    np.testing.assert_allclose(np.asarray(back.to_dense(V)), D,
                               atol=1e-6)


def test_stack_rows_pads_mixed_widths():
    a = sparsify_topk(jnp.asarray([[1.0, 0.0, 2.0, 0.0]]), 2)
    b = sparsify_topk(jnp.asarray([[0.0, 3.0, 0.0, 0.0]]), 1)
    stacked = stack_rows([a, b])
    assert stacked.width == 2
    np.testing.assert_allclose(
        np.asarray(stacked.to_dense(4)),
        [[1.0, 0.0, 2.0, 0.0], [0.0, 3.0, 0.0, 0.0]], atol=1e-6)


# ---------------------------------------------------------------------------
# inverted index
# ---------------------------------------------------------------------------

def test_index_layout_and_stats(corpus):
    _, D = corpus
    rep = sparsify_topk(jnp.asarray(D), 16)
    idx = build_inverted_index(rep, V)
    st = idx.stats()
    assert st["n_docs"] == 40 and st["n_postings"] == 40 * 10
    lens = np.asarray(idx.term_lens)
    starts = np.asarray(idx.term_starts)
    assert lens.sum() == idx.n_postings
    np.testing.assert_array_equal(starts[1:],
                                  np.cumsum(lens)[:-1])
    assert idx.max_postings == lens.max()
    # postings within a term are ordered by doc id (stable build)
    for t in np.flatnonzero(lens > 1)[:10]:
        docs = np.asarray(idx.postings_doc)[starts[t]:starts[t] + lens[t]]
        assert (np.diff(docs) > 0).all()
    # the memory story: postings beat the dense (N, V) matrix
    assert idx.memory_bytes() < 40 * V * 4


def test_index_empty_corpus_is_valid():
    rep = sparsify_topk(jnp.zeros((3, V)), 8)
    idx = build_inverted_index(rep, V)
    assert idx.n_docs == 3 and idx.max_postings == 1
    q = sparsify_topk(jnp.asarray(_sparse_mat(
        np.random.default_rng(1), 2, 4)), 4)
    scores = np.asarray(impact_scores(q, idx))
    assert scores.shape == (2, 3) and (scores == 0).all()


def test_index_rejects_out_of_range_terms():
    rep = SparseRep(values=np.ones((1, 2), np.float32),
                    indices=np.array([[0, V + 5]], np.int32),
                    nnz=np.array([2], np.int32))
    with pytest.raises(ValueError, match="term ids"):
        build_inverted_index(rep, V)


def test_impact_scores_match_dense_einsum(corpus):
    Q, D = corpus
    q_rep = sparsify_threshold(jnp.asarray(Q), 0.0, max_nnz=16)
    d_rep = sparsify_threshold(jnp.asarray(D), 0.0, max_nnz=16)
    idx = build_inverted_index(d_rep, V)
    np.testing.assert_allclose(np.asarray(impact_scores(q_rep, idx)),
                               Q @ D.T, atol=1e-5)


# ---------------------------------------------------------------------------
# retrieve() dispatcher — the acceptance parity test
# ---------------------------------------------------------------------------

def test_parity_impact_streaming_dense(corpus):
    """Acceptance: the three scoring paths return identical top-k doc
    ids (and scores within fp tolerance) from the same SparseRep/dense
    inputs."""
    Q, D = corpus
    k = 7
    q_rep = sparsify_threshold(jnp.asarray(Q), 0.0, max_nnz=16)
    d_rep = sparsify_threshold(jnp.asarray(D), 0.0, max_nnz=16)
    index = build_inverted_index(d_rep, V)

    v_dense, i_dense = retrieve(jnp.asarray(Q), jnp.asarray(D), k,
                                method="dense")
    v_stream, i_stream = retrieve(q_rep, jnp.asarray(D), k,
                                  method="streaming", block_b=2,
                                  block_n=16, interpret=True)
    v_imp, i_imp = retrieve(q_rep, index, k, method="impact")
    v_fused, i_fused = retrieve(q_rep, index, k, method="fused",
                                block_n=16, block_w=128,
                                interpret=True)

    np.testing.assert_array_equal(np.asarray(i_dense),
                                  np.asarray(i_stream))
    np.testing.assert_array_equal(np.asarray(i_dense), np.asarray(i_imp))
    np.testing.assert_array_equal(np.asarray(i_dense),
                                  np.asarray(i_fused))
    np.testing.assert_allclose(np.asarray(v_dense), np.asarray(v_stream),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(v_dense), np.asarray(v_imp),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(v_dense), np.asarray(v_fused),
                               atol=1e-5)


def test_auto_routes_by_corpus_type(corpus):
    Q, D = corpus
    q_rep = sparsify_threshold(jnp.asarray(Q), 0.0, max_nnz=16)
    d_rep = sparsify_threshold(jnp.asarray(D), 0.0, max_nnz=16)
    index = build_inverted_index(d_rep, V)
    v_auto, i_auto = retrieve(q_rep, index, 5)           # -> impact
    v_imp, i_imp = retrieve(q_rep, index, 5, method="impact")
    np.testing.assert_array_equal(np.asarray(i_auto), np.asarray(i_imp))
    # dense corpus below the streaming cutoff -> dense
    v_d, i_d = retrieve(jnp.asarray(Q), jnp.asarray(D), 5)
    np.testing.assert_array_equal(np.asarray(i_d), np.asarray(i_imp))


def test_k_clamped_to_corpus_size(corpus):
    Q, D = corpus
    vals, idx = retrieve(jnp.asarray(Q), jnp.asarray(D), 100,
                         method="dense")
    assert vals.shape == (5, 40) and idx.shape == (5, 40)


def test_dispatcher_input_errors(corpus):
    Q, D = corpus
    d_rep = sparsify_threshold(jnp.asarray(D), 0.0, max_nnz=16)
    index = build_inverted_index(d_rep, V)
    with pytest.raises(ValueError, match="unknown retrieval method"):
        retrieve(jnp.asarray(Q), jnp.asarray(D), 5, method="bm25")
    with pytest.raises(ValueError, match="SparseRep queries"):
        retrieve(jnp.asarray(Q), index, 5, method="impact")
    with pytest.raises(ValueError, match="InvertedIndex corpus"):
        retrieve(sparsify_topk(jnp.asarray(Q), 8), jnp.asarray(D), 5,
                 method="impact")
    with pytest.raises(ValueError, match="dense .* corpus matrix"):
        retrieve(jnp.asarray(Q), index, 5, method="dense")


def test_dispatcher_rejects_stray_kwargs(corpus):
    """Kwargs the *resolved* method cannot honor raise instead of being
    silently ignored — a typo'd tuning knob must not become a no-op."""
    Q, D = corpus
    q_rep = sparsify_threshold(jnp.asarray(Q), 0.0, max_nnz=16)
    d_rep = sparsify_threshold(jnp.asarray(D), 0.0, max_nnz=16)
    index = build_inverted_index(d_rep, V)
    with pytest.raises(ValueError, match="does not accept mesh"):
        retrieve(q_rep, index, 5, method="impact", mesh=object())
    with pytest.raises(ValueError, match="does not accept prune_margin"):
        retrieve(q_rep, jnp.asarray(D), 5, method="streaming",
                 prune_margin=0.5, interpret=True)
    with pytest.raises(ValueError, match="does not accept block_w"):
        retrieve(q_rep, index, 5, method="impact", block_w=128)
    with pytest.raises(ValueError, match="does not accept candidates"):
        retrieve(q_rep, index, 5, method="fused", candidates=32,
                 interpret=True)
    # the check runs against the *resolved* method, so 'auto' on a
    # small bare index (-> impact) rejects fused-kernel knobs too
    with pytest.raises(ValueError, match="method='impact'"):
        retrieve(q_rep, index, 5, block_n=64)
    # None sentinels are "not passed", never an error
    vals, idx = retrieve(q_rep, index, 5, method="impact", mesh=None,
                         block_w=None)
    assert idx.shape == (5, 5)


# ---------------------------------------------------------------------------
# serving integration: SparseRep as the post-head currency
# ---------------------------------------------------------------------------

def _fake_sparse_encoder(k=4):
    """Token-count encoder emitting SparseReps over a 32-dim vocab."""
    def encode(tokens, mask):
        B, S = tokens.shape
        out = np.zeros((B, 32), np.float32)
        for i in range(B):
            for t, m in zip(np.asarray(tokens[i]), np.asarray(mask[i])):
                if m:
                    out[i, int(t) % 32] += 1
        return sparsify_topk(jnp.asarray(out), k)
    return encode


def test_serving_loop_round_trips_sparse_reps():
    from repro.runtime.serving import (BatchedEncoder, BatchPolicy,
                                       Request, ServingLoop)

    enc = BatchedEncoder(_fake_sparse_encoder(),
                         policy=BatchPolicy(max_batch=4, max_wait_s=0.0))
    loop = ServingLoop(enc, clock=lambda: 0.0)
    for uid in range(6):
        loop.submit(Request(
            uid=uid, tokens=np.array([uid, uid, 5], np.int32)))
        loop.tick(force=True)
    loop.drain()
    reps = [loop.take(u) for u in range(6)]
    assert not loop.completed
    assert all(isinstance(r, SparseRep) for r in reps)
    q = stack_rows(reps)
    dense = np.asarray(q.to_dense(32))
    for uid in range(6):
        expected = 3.0 if uid == 5 else 2.0
        assert dense[uid, uid % 32] == expected


def test_make_config_encoder_emits_sparse_reps():
    """The config's rep knobs flow through head_spec -> make_encoder ->
    serving: the encode fn returns SparseReps, and their densification
    matches the dense encoder's output top-k."""
    from repro.configs import get_config
    from repro.models import transformer as tfm
    from repro.runtime.serving import make_config_encoder

    cfg = get_config("splade_bert").SMOKE
    cfg = dataclasses.replace(cfg, n_layers=1, rep_topk=8)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    enc_sparse = make_config_encoder(params, cfg)
    enc_dense = make_config_encoder(
        params, dataclasses.replace(cfg, rep_topk=None))

    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 1,
                              cfg.vocab_size)
    mask = jnp.ones((2, 12), jnp.int32)
    rep = enc_sparse(toks, mask)
    assert isinstance(rep, SparseRep) and rep.width == 8
    dense = np.asarray(enc_dense(toks, mask), np.float32)
    # lax.top_k breaks ties toward the lower id, as a stable descending
    # sort does (bf16 outputs tie often)
    top8 = np.sort(np.argsort(-dense, axis=1, kind="stable")[:, :8],
                   axis=1)
    got = np.sort(np.asarray(rep.indices), axis=1)
    np.testing.assert_array_equal(got, top8)
