"""Autotuner unit tests: candidate enumeration under the VMEM budget,
heuristic determinism, measured-winner JSON cache round trip, and the
config-level threading."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import autotune
from repro.kernels.autotune import (MIN_BLOCKS, autotune_blocks,
                                    candidate_blocks, get_blocks,
                                    heuristic_blocks, resolve_blocks,
                                    shape_key, vmem_bytes)


@pytest.fixture(autouse=True)
def isolated_cache(tmp_path, monkeypatch):
    """Every test gets a fresh cache file + empty in-memory cache."""
    path = tmp_path / "autotune.json"
    monkeypatch.setenv(autotune.CACHE_ENV, str(path))
    autotune.clear_cache()
    yield str(path)
    autotune.clear_cache()


def test_candidates_respect_vmem_budget():
    budget = 12 * 1024 * 1024
    cands = candidate_blocks(320, 512, 768, 30522, vmem_budget=budget)
    assert cands, "no candidates under a 12 MiB budget at bert-base size"
    for blocks in cands:
        assert vmem_bytes(blocks, 768) <= budget
        # Mosaic's (8, 128) tiling: block_b on sublanes, block_s and
        # block_v on lanes
        assert blocks[0] % 8 == 0
        assert blocks[1] % 128 == 0
        assert blocks[2] % 128 == 0
    # a short sequence may take its whole padded length as one block
    short = candidate_blocks(4, 40, 64, 1000)
    assert {bs for _, bs, _ in short} <= {48, 128}
    assert any(bs == 48 for _, bs, _ in short)


def test_candidates_sorted_by_traffic_model():
    cands = candidate_blocks(320, 512, 768, 30522)
    traffic = [autotune.hbm_traffic_elems(c, 320, 512, 768, 30522)
               for c in cands]
    assert traffic == sorted(traffic)


def test_heuristic_covers_paper_operating_points():
    """Acceptance: the tuner selects blocks for splade_bert (V≈30k) and
    splade_xlmr (V≈250k) shapes — and large-V gets a vocab tile at
    least as large (HBM traffic scales with V/block_v)."""
    bert = heuristic_blocks(320, 512, 768, 30522)
    xlmr = heuristic_blocks(16, 256, 768, 250002)
    for blocks in (bert, xlmr):
        assert all(x >= 1 for x in blocks)
        assert vmem_bytes(blocks, 768) <= autotune.VMEM_BUDGET_BYTES
    assert xlmr[2] >= bert[2]


def test_heuristic_fallback_when_budget_unreachable():
    # nothing fits => the overflow-minimizing smallest triple, never a
    # larger "default" that would amplify the VMEM overflow
    assert heuristic_blocks(8, 128, 65536, 1024,
                            vmem_budget=1) == MIN_BLOCKS


def test_default_cache_lives_in_the_checkout():
    """Without SPARTON_AUTOTUNE_CACHE the winners file sits inside the
    checkout, so no state outside the repository changes the blocks."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert os.path.commonpath([autotune.DEFAULT_CACHE, repo]) == repo


def test_get_blocks_without_cache_is_heuristic():
    assert get_blocks(4, 32, 16, 64) == heuristic_blocks(4, 32, 16, 64)


def test_autotune_cache_round_trip(isolated_cache):
    """Measured winner is persisted to JSON and read back — including
    by a cold in-memory cache (a fresh process)."""
    blocks = autotune_blocks(4, 32, 16, 64, max_candidates=2)
    assert os.path.exists(isolated_cache)
    raw = json.load(open(isolated_cache))
    key = shape_key(4, 32, 16, 64, jnp.float32, jax.default_backend())
    assert raw[key]["source"] == "measured"
    assert (raw[key]["block_b"], raw[key]["block_s"],
            raw[key]["block_v"]) == blocks

    # simulate a fresh process: drop the in-memory cache, hit the file
    autotune.clear_cache()
    assert get_blocks(4, 32, 16, 64) == blocks
    # re-tuning the same key is a cache hit (no re-measurement)
    assert autotune_blocks(4, 32, 16, 64) == blocks


def test_cache_keys_are_shape_and_dtype_specific(isolated_cache):
    autotune_blocks(4, 32, 16, 64, max_candidates=1)
    # different dtype => different key => heuristic (not the cached hit)
    raw = json.load(open(isolated_cache))
    backend = jax.default_backend()
    assert shape_key(4, 32, 16, 64, jnp.bfloat16, backend) not in raw
    assert shape_key(4, 32, 16, 64, jnp.float32, backend) in raw


def test_distinct_cache_paths_stay_isolated(tmp_path):
    """Entries written to one cache file must not bleed into saves of
    another (per-path in-memory caches)."""
    a, b = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    autotune_blocks(4, 32, 16, 64, max_candidates=1, path=a)
    autotune_blocks(2, 16, 8, 32, max_candidates=1, path=b)
    keys_a = set(json.load(open(a)))
    keys_b = set(json.load(open(b)))
    backend = jax.default_backend()
    assert keys_a == {shape_key(4, 32, 16, 64, jnp.float32, backend)}
    assert keys_b == {shape_key(2, 16, 8, 32, jnp.float32, backend)}


def test_partial_pin_respects_vmem_budget():
    """Pinning one component must re-derive the free ones under the
    budget, not graft a pin onto blocks tuned without it."""
    blocks = heuristic_blocks(320, 512, 768, 250002,
                              pinned=(None, None, 1024))
    assert blocks[2] == 1024
    assert vmem_bytes(blocks, 768) <= autotune.VMEM_BUDGET_BYTES
    # a pin no free choice can rescue (bv=2048 at D=768 overflows on
    # the dE scratch alone): minimal free components, not silent drop
    blocks = heuristic_blocks(320, 512, 768, 250002,
                              pinned=(None, None, 2048))
    assert blocks == MIN_BLOCKS[:2] + (2048,)
    # the kernel-wrapper path must re-enumerate jointly too, not graft
    # the pin onto the unpinned winner
    blocks = resolve_blocks(64, 512, 64, 250002, jnp.float32,
                            None, 512, None)
    assert blocks[1] == 512
    assert vmem_bytes(blocks, 64) <= autotune.VMEM_BUDGET_BYTES


def test_all_candidates_failing_does_not_poison_cache(
        isolated_cache, monkeypatch):
    """If every timing attempt raises, no 'measured' entry may be
    persisted — a later call must retry."""
    def boom(*a, **k):
        raise RuntimeError("lowering failed")
    monkeypatch.setattr(autotune, "_time_ms", boom)
    blocks = autotune_blocks(4, 32, 16, 64, max_candidates=2)
    assert blocks == heuristic_blocks(4, 32, 16, 64)
    assert not os.path.exists(isolated_cache)


# ---------------------------------------------------------------------------
# per-kernel tuning (fwd vs dH vs dE)
# ---------------------------------------------------------------------------

def test_per_kernel_round_trip(isolated_cache):
    """Per-kernel winners are persisted under kernel-suffixed keys and
    read back by kernel-scoped lookups (including a cold cache)."""
    from repro.kernels.autotune import autotune_kernel_blocks

    winners = autotune_kernel_blocks(4, 32, 16, 64, max_candidates=2)
    assert set(winners) == set(autotune.KERNELS)
    raw = json.load(open(isolated_cache))
    backend = jax.default_backend()
    for kn in autotune.KERNELS:
        key = shape_key(4, 32, 16, 64, jnp.float32, backend, kn)
        assert raw[key]["source"] == "measured"
        assert raw[key]["kernel"] == kn
    autotune.clear_cache()
    for kn in autotune.KERNELS:
        assert get_blocks(4, 32, 16, 64, kernel=kn) == winners[kn]
    # re-tuning is a pure cache hit
    assert autotune_kernel_blocks(4, 32, 16, 64) == winners


def test_per_kernel_falls_back_to_legacy_joint_entry(isolated_cache):
    """Old cache files (joint keys only) must keep working: a
    per-kernel lookup with no suffixed entry reads the joint one."""
    blocks = autotune_blocks(4, 32, 16, 64, max_candidates=1)
    autotune.clear_cache()
    for kn in autotune.KERNELS:
        assert get_blocks(4, 32, 16, 64, kernel=kn) == blocks


def test_per_kernel_vmem_is_component_of_joint():
    """Kernel-scoped VMEM residency never exceeds the joint worst case,
    and the joint is exactly the max over the three kernels."""
    for blocks in [(2, 64, 128), (8, 128, 512), (1, 256, 2048)]:
        per = [vmem_bytes(blocks, 768, kernel=kn)
               for kn in autotune.KERNELS]
        assert vmem_bytes(blocks, 768) == max(per)


def test_per_kernel_candidates_admit_more_than_joint():
    """A tight budget excludes a triple jointly (worst-case kernel
    overflows) while still admitting it for a cheaper kernel — the
    reason per-kernel enumeration exists."""
    B, S, D, V = 16, 256, 2048, 30522
    per_kernel = {kn: candidate_blocks(B, S, D, V, kernel=kn)
                  for kn in autotune.KERNELS}
    joint = candidate_blocks(B, S, D, V)
    for kn, cands in per_kernel.items():
        assert set(joint) <= set(cands), kn
    assert any(len(cands) > len(joint)
               for cands in per_kernel.values())


def test_all_kernel_candidates_failing_does_not_poison_cache(
        isolated_cache, monkeypatch):
    from repro.kernels.autotune import autotune_kernel_blocks

    def boom(*a, **k):
        raise RuntimeError("lowering failed")
    monkeypatch.setattr(autotune, "_time_ms", boom)
    winners = autotune_kernel_blocks(4, 32, 16, 64, max_candidates=2)
    for kn in autotune.KERNELS:
        assert winners[kn] == heuristic_blocks(4, 32, 16, 64, kernel=kn)
    assert not os.path.exists(isolated_cache)


# ---------------------------------------------------------------------------
# fused impact-scoring kernel (``_impact`` key family)
# ---------------------------------------------------------------------------

def test_impact_candidates_respect_vmem_budget():
    budget = 2 * 1024 * 1024
    cands = autotune.impact_candidate_blocks(16, 32, 512, 1 << 20,
                                             vmem_budget=budget)
    assert cands, "no impact candidates under a 2 MiB budget"
    for blocks in cands:
        assert autotune.impact_vmem_bytes(blocks, 32, 512) <= budget
    proxies = [autotune.impact_traffic_proxy(c, 16, 32, 512, 1 << 20)
               for c in cands]
    assert proxies == sorted(proxies)


def test_impact_shape_key_rejects_unknown_variant():
    with pytest.raises(ValueError, match="variant"):
        autotune.impact_shape_key(4, 8, 16, 64, "f16", "cpu")


def test_impact_cache_round_trip(isolated_cache):
    """Measured impact winner persists under the ``_impact`` key and is
    read back by a cold cache; the head-kernel key family is
    untouched."""
    blocks = autotune.autotune_impact_blocks(2, 4, 8, 64,
                                             max_candidates=2)
    raw = json.load(open(isolated_cache))
    backend = jax.default_backend()
    key = autotune.impact_shape_key(2, 4, 8, 64, "f32", backend)
    assert raw[key]["source"] == "measured"
    assert raw[key]["kernel"] == "impact"
    assert (raw[key]["block_n"], raw[key]["block_w"]) == blocks
    assert all(k.endswith("_impact") for k in raw)

    autotune.clear_cache()
    assert autotune.get_impact_blocks(2, 4, 8, 64) == blocks
    # re-tuning the same key is a cache hit (no re-measurement)
    assert autotune.autotune_impact_blocks(2, 4, 8, 64) == blocks


def test_impact_variants_get_distinct_keys(isolated_cache):
    autotune.autotune_impact_blocks(2, 4, 8, 64, max_candidates=1)
    raw = json.load(open(isolated_cache))
    backend = jax.default_backend()
    assert autotune.impact_shape_key(2, 4, 8, 64, "u4",
                                     backend) not in raw
    u4 = autotune.autotune_impact_blocks(2, 4, 8, 64, variant="u4",
                                         max_candidates=1)
    raw = json.load(open(isolated_cache))
    key = autotune.impact_shape_key(2, 4, 8, 64, "u4", backend)
    assert (raw[key]["block_n"], raw[key]["block_w"]) == u4
    assert raw[key]["variant"] == "u4"


def test_impact_cold_cache_is_heuristic():
    assert (autotune.get_impact_blocks(4, 16, 64, 4096)
            == autotune.heuristic_impact_blocks(4, 16, 64, 4096))


def test_impact_resolve_partial_pins():
    """Explicit pair passes through; a single pin filters the
    candidate enumeration instead of grafting onto the cached
    winner."""
    assert autotune.resolve_impact_blocks(4, 16, 64, 4096, 256,
                                          128) == (256, 128)
    bn, bw = autotune.resolve_impact_blocks(4, 16, 64, 4096, 256, None)
    assert bn == 256 and bw in autotune._IMPACT_BW_CHOICES
    bn, bw = autotune.resolve_impact_blocks(4, 16, 64, 4096, None, None)
    assert (bn, bw) == autotune.heuristic_impact_blocks(4, 16, 64, 4096)


def test_impact_all_candidates_failing_does_not_poison_cache(
        isolated_cache, monkeypatch):
    def boom(*a, **k):
        raise RuntimeError("lowering failed")
    monkeypatch.setattr(autotune, "_time_ms", boom)
    blocks = autotune.autotune_impact_blocks(2, 4, 8, 64,
                                             max_candidates=2)
    assert blocks == autotune.heuristic_impact_blocks(2, 4, 8, 64)
    assert not os.path.exists(isolated_cache)


def test_config_head_blocks_threading():
    """TransformerConfig.head_blocks: pinned fields win, None = auto."""
    from repro.configs import get_config

    cfg = get_config("splade_bert").CONFIG
    assert cfg.head_block_b is None  # configs stopped hard-coding
    auto = cfg.head_blocks(8, 128)
    assert auto == get_blocks(8, 128, cfg.d_model, cfg.vocab_size,
                              dtype=jnp.dtype(cfg.compute_dtype))

    import dataclasses
    pinned = dataclasses.replace(cfg, head_block_b=2, head_block_s=64,
                                 head_block_v=256)
    assert pinned.head_blocks(8, 128) == (2, 64, 256)
