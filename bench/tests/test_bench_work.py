"""Work counts against hand-computed values at small shapes."""

import pytest

from bench import work
from bench.backbones import dense_encoder as dense

PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_v5e_peaks_and_unknown_kind():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_head_forward_counts_real_tokens_only():
    # 5 real of 2x4 positions, V=3, D=2: 2*5*3*2 FLOPs
    w = work.head_fwd(5, 2, 4, 3, 2)
    assert w.flops == 60
    # H 2*4*2 bf16 + E 3*2 bf16 + bias 3 f32 + mask 2*4 i32 + y, i 2*3*(4+4)
    assert w.bytes == 32 + 12 + 12 + 32 + 48


def test_head_backward_counts_one_row_per_output():
    dh, de = work.head_dh(2, 4, 3, 2), work.head_de(2, 4, 3, 2)
    assert dh.flops == de.flops == 2 * 2 * 3 * 2
    # dy, y, i of (2, 3); E bf16; dH (2, 4, 2) f32
    assert dh.bytes == 6 * 12 + 12 + 64
    # dy, y, i; H bf16; dE (3, 2) f32; db (3,) f32
    assert de.bytes == 6 * 12 + 32 + 24 + 12


def test_min_seconds_is_the_larger_bound():
    assert work.Work(1000.0, 10.0).min_seconds(PEAK) == 10.0
    assert work.Work(100.0, 100.0).min_seconds(PEAK) == 10.0
    assert (work.Work(1, 2) + work.Work(3, 4)) * 2 == work.Work(8, 12)


SIZES = {"L": 2, "D": 4, "H": 2, "dh": 2, "F": 8, "V": 10}


def test_transformer_params():
    # per layer 4 * 4*2*2 attention + 3 * 4*8 FFN
    assert dense.transformer_params(SIZES) == 2 * (64 + 96)


def test_encode_and_train_flops():
    P = 320
    # lengths 1 and 3: T = 4, sum n^2 = 10
    attn = 4 * 10 * 2 * 2 * 2
    enc = 2 * P * 4 + attn + 2 * 4 * 10 * 4
    assert dense.encode_flops([1, 3], SIZES) == enc
    q = 6 * P * 4 + 3 * attn + 2 * 4 * 10 * 4 + 4 * 2 * 10 * 4
    d = 6 * P * 2 + 3 * (4 * 2 * 2 * 2 * 2) + 2 * 2 * 10 * 4 + 4 * 2 * 10 * 4
    assert dense.train_step_flops([1, 3], [1, 1], SIZES) == q + d + 6 * 4 * 10
