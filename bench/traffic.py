"""The benchmark's one traffic generator, driven by a traffic file.

A copy of ``repro.data.synthetic.lsr_pair_batches`` (Zipf token ids,
the query-doc overlap splice) with its lengths changed: each sequence's
real length comes from a log-normal distribution, clipped and padded as
the traffic file says. The lengths are stratified: every batch holds the
same ``n`` quantiles of the distribution, and the seed decides only
their order and the token ids. So every seed does the same work (the
same real tokens a batch), and a run's spread is the system's own.

A length spec in a traffic file::

    {"mean": 78, "sigma": 0.5, "min": 16, "max": 256, "pad": 256}

``mean`` is the mean of the unclipped log-normal, ``sigma`` the standard
deviation of its logarithm.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, Iterator

import numpy as np

# Zipf exponent of the token ids, as in repro.data.synthetic
ZIPF_A = 1.3


def _rng(seed: int, stream: int, step: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, stream, step]))


def _zipf_ids(rng, size, vocab: int) -> np.ndarray:
    raw = rng.zipf(ZIPF_A, size=size)
    return np.clip(raw - 1, 0, vocab - 1).astype(np.int32)


def stratified_lengths(n: int, spec: Dict) -> np.ndarray:
    """The ``n`` mid-quantiles of the clipped log-normal, ascending."""
    mu = math.log(spec["mean"]) - spec["sigma"] ** 2 / 2
    z = [statistics.NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    raw = np.exp(mu + spec["sigma"] * np.asarray(z))
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def _mask(lengths: np.ndarray, pad: int) -> np.ndarray:
    return (np.arange(pad)[None] < lengths[:, None]).astype(np.int32)


def pair_batches(traffic: Dict, vocab: int, seed: int
                 ) -> Iterator[Dict[str, np.ndarray]]:
    """(query, positive-doc) batches of ``traffic["pairs"]`` rows."""
    n, qs, ds = traffic["pairs"], traffic["query"], traffic["doc"]
    q_lens, d_lens = stratified_lengths(n, qs), stratified_lengths(n, ds)
    q_len, d_len = qs["pad"], ds["pad"]
    step = 0
    while True:
        rng = _rng(seed, 0, step)
        q_tok = _zipf_ids(rng, (n, q_len), vocab)
        d_tok = _zipf_ids(rng, (n, d_len), vocab)
        q_mask = _mask(rng.permutation(q_lens), q_len)
        d_mask = _mask(rng.permutation(d_lens), d_len)
        # overlap positives: splice some query tokens into the doc so
        # the contrastive task is learnable
        n_copy = max(1, q_len // 2)
        d_tok[:, :n_copy] = q_tok[:, :n_copy]
        yield {"q_tokens": q_tok, "q_mask": q_mask,
               "d_tokens": d_tok * d_mask, "d_mask": d_mask}
        step += 1


def doc_batches(traffic: Dict, vocab: int, seed: int
                ) -> Iterator[Dict[str, np.ndarray]]:
    """Document batches of ``traffic["docs"]`` rows, for encoding."""
    n, ds = traffic["docs"], traffic["doc"]
    d_lens, d_len = stratified_lengths(n, ds), ds["pad"]
    step = 0
    while True:
        rng = _rng(seed, 1, step)
        d_tok = _zipf_ids(rng, (n, d_len), vocab)
        d_mask = _mask(rng.permutation(d_lens), d_len)
        yield {"tokens": d_tok * d_mask, "mask": d_mask}
        step += 1
