"""A cell at a size a CPU test run holds: the published configuration
files with their sizes cut as their backbone's ``SMALL`` says, and short
traffic."""

import copy
import json
import os
import time

from bench import backbones
from bench.drivers._common import Cell

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
QUERY = {"mean": 6, "sigma": 0.5, "min": 2, "max": 16, "pad": 16}
DOC = {"mean": 20, "sigma": 0.5, "min": 4, "max": 32, "pad": 32}


def load(kind, name):
    with open(os.path.join(BENCH, kind, f"{name}.json")) as f:
        return json.load(f)


def config(name="splade_bert"):
    cfg = copy.deepcopy(load("configs", name))
    cfg.update(backbones.load(cfg).SMALL)
    cfg["run"]["rep_topk"] = 16
    cfg["init"]["head_bias"] = -1.0
    cfg["reference"] = {"vocab_tile": 128, "rows": 8}
    return cfg


# Limits at this size, from CPU readings over three seeds: sound runs
# read at most loss 0.021, gradient 0.012, update 0.006, rep value
# 0.019 and rank 0.012; the float8 control at least 0.13, 0.6, 0.98,
# 0.12 and 0.09. (The cells' own limits, set on the chip at the cells'
# sizes, are in bench/limits/.)
LIMITS = {"train": {"loss_gap": 0.06, "grad_norm_gap": 0.1,
                    "update_norm_gap": 0.1},
          "encode": {"rep_value_gap": 0.06, "rep_topk_gap": 0.06}}


def cell(driver, seed=2 ** 40 + 7, seconds=0.5, name="splade_bert"):
    if driver == "train":
        traffic = {"driver": "train", "pairs": 8, "query": QUERY, "doc": DOC}
    else:
        traffic = {"driver": "encode", "docs": 8, "doc": DOC,
                   "check_rows": 16}
    return Cell(name="small", config=config(name), traffic=traffic, chips=1,
                seed=seed, seconds=seconds, trace=False, t0=time.monotonic(),
                limits=LIMITS[driver])
