"""The work the Sparton head needs, from shapes and masks, and the
chip's peaks. (Each backbone counts its own model FLOPs, in its module
under ``bench/backbones/``.)

Counts are of the work the operation needs, whatever implements it, so
a share of a peak computed from them is a lower bound on the time and
cannot pass 100%:

* Sparton head forward: ``2 * real_tokens * V * D`` FLOPs (padded
  positions need none); bytes: each operand and result at the kernel's
  boundary, read or written once, in the dtypes the program passes.
* Head backward, per encoder call of ``B`` sequences: the arg-max
  gradient routes one row per (b, v), so ``2 * B * V * D`` for dH and as
  many for dE.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, Sequence, Tuple

import numpy as np

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")
F32, BF16, I32 = 4, 2, 4


def peaks(device_kind: str, path: str = PEAKS_FILE) -> Dict[str, float]:
    """The table's peaks for a ``device_kind``; an unknown kind raises."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"the table has {sorted(table)}")
    return table[device_kind]


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def __mul__(self, n: float) -> "Work":
        return Work(self.flops * n, self.bytes * n)

    def min_seconds(self, peak: Dict[str, float]) -> float:
        """The least time the chip could take: compute or HBM bound."""
        return max(self.flops / peak["bf16_flops_per_s"],
                   self.bytes / peak["hbm_bytes_per_s"])


def head_fwd(real_tokens: int, B: int, S: int, V: int, D: int,
             act_bytes: int = BF16) -> Work:
    """Forward kernel: H (B,S,D), E (V,D), bias (V,) f32, mask (B,S) i32
    in; y (B,V) f32 and arg-max (B,V) i32 out."""
    return Work(2.0 * real_tokens * V * D,
                float(B * S * D * act_bytes + V * D * act_bytes + V * F32
                      + B * S * I32 + B * V * (F32 + I32)))


def head_dh(B: int, S: int, V: int, D: int, w_bytes: int = BF16) -> Work:
    """dH kernel: dy, y (f32) and arg-max (i32) of (B,V), E in; dH
    (B,S,D) f32 out."""
    return Work(2.0 * B * V * D,
                float(B * V * (F32 + F32 + I32) + V * D * w_bytes
                      + B * S * D * F32))


def head_de(B: int, S: int, V: int, D: int, act_bytes: int = BF16) -> Work:
    """dE kernel: dy, y, arg-max of (B,V) and H in; dE (V,D) and db (V,)
    in f32 out."""
    return Work(2.0 * B * V * D,
                float(B * V * (F32 + F32 + I32) + B * S * D * act_bytes
                      + V * D * F32 + V * F32))


def head_train(batch: Dict[str, np.ndarray], V: int, D: int
               ) -> Tuple[Work, Work, Work]:
    """The head's forward, dH and dE work in one training step: one
    encoder call for the queries and one for the documents."""
    fwd = dh = de = Work()
    for tok, mask in (("q_tokens", "q_mask"), ("d_tokens", "d_mask")):
        B, S = batch[tok].shape
        fwd = fwd + head_fwd(int(batch[mask].sum()), B, S, V, D)
        dh = dh + head_dh(B, S, V, D)
        de = de + head_de(B, S, V, D)
    return fwd, dh, de


def head_encode(batches: Sequence[Dict[str, np.ndarray]], V: int, D: int
                ) -> Work:
    """The head's forward work over encoded document batches."""
    total = Work()
    for b in batches:
        B, S = b["mask"].shape
        total = total + head_fwd(int(b["mask"].sum()), B, S, V, D)
    return total
