"""The numbers that decide ``correct``, each held to its own limit.

Training (one step is the program's jitted step; the reference follows
the same first steps on the same rows, from the same seeded weights):

``loss_gap``         largest relative gap of a step's loss.
``grad_norm_gap``    the first gradient as the optimizer got it (its
                     first moment after one step, over 1 - beta1),
                     against the reference's clipped gradient: by the
                     worst leaf, the gap between the two leaf norms over
                     the larger of the reference's norm of that leaf and
                     of the median leaf.
``update_norm_gap``  the same measure on each leaf's change from the
                     initial weights after the checked steps. Leaves
                     whose reference gradient is under a thousandth of
                     the median leaf's move by round-off alone and are
                     left out.

Encoding (every sampled row of the window, against the reference's
dense representation of the same document):

``rep_value_gap``    the widest gap between a kept term's weight and the
                     reference's weight of that term (a wrong weight or
                     a wrong term id).
``rep_topk_gap``     the widest gap between the j-th largest kept weight
                     (0 for an empty slot) and the reference's j-th
                     largest weight of the row (a wrong selection: terms
                     missing or not the largest).
"""

from __future__ import annotations

import json
import math
import os
from typing import Dict, List, Tuple

import numpy as np

# reference gradient under this share of the median leaf's: round-off
DEAD_LEAF = 1e-3

Check = Tuple[str, float, float]


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              names) -> Dict[str, float]:
    """Each leaf's gap of norms over the larger of its reference norm
    and the median leaf's."""
    med = float(np.median([ref[n] for n in ref]))
    return {n: abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
            for n in names}


def _leaf_gap(prog, ref, names) -> float:
    return max(leaf_gaps(prog, ref, names).values(), default=0.0)


def train_numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """``prog``/``ref``: {"loss": [..], "grad": {leaf: norm},
    "change": {leaf: norm}}."""
    loss_gap = max(abs(p - r) / max(abs(r), 1e-30)
                   for p, r in zip(prog["loss"], ref["loss"]))
    if not all(math.isfinite(p) for p in prog["loss"]):
        loss_gap = math.inf
    med = float(np.median(list(ref["grad"].values())))
    live = [n for n in ref["grad"] if ref["grad"][n] >= DEAD_LEAF * med]
    return {"loss_gap": loss_gap,
            "grad_norm_gap": _leaf_gap(prog["grad"], ref["grad"], ref["grad"]),
            "update_norm_gap": _leaf_gap(prog["change"], ref["change"], live)}


def encode_numbers(values: np.ndarray, ref_at: np.ndarray,
                   ref_top: np.ndarray) -> Dict[str, float]:
    """``values`` (N, K): the program's kept weights (0 = empty slot);
    ``ref_at`` (N, K): the reference's weights at the kept term ids;
    ``ref_top`` (N, K): the reference's K largest weights per row."""
    values = np.asarray(values, np.float64)
    if not np.all(np.isfinite(values)):
        return {"rep_value_gap": math.inf, "rep_topk_gap": math.inf}
    value_gap = np.where(values > 0, np.abs(values - ref_at), 0.0).max()
    top_gap = np.abs(-np.sort(-values, axis=1)
                     - -np.sort(-np.asarray(ref_top, np.float64), axis=1))
    return {"rep_value_gap": float(value_gap),
            "rep_topk_gap": float(top_gap.max())}


def load_limits(root: str, workload: str) -> Dict[str, float]:
    path = os.path.join(root, "bench", "limits", f"{workload}.json")
    with open(path) as f:
        return {k: float(v) for k, v in json.load(f)["limits"].items()}


def checks(numbers: Dict[str, float], limits: Dict[str, float]
           ) -> List[Check]:
    """(name, number, limit) for every limited number; a number with no
    limit is an error, not a pass."""
    missing = set(numbers) - set(limits)
    if missing:
        raise KeyError(f"no limit for {sorted(missing)}")
    return [(n, numbers[n], limits[n]) for n in sorted(numbers)]


def passed(checked: List[Check]) -> bool:
    return all(math.isfinite(v) and v <= lim for _, v, lim in checked)
