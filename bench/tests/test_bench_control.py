"""The control: the reference computed in float8, the precision below
the configuration's bfloat16, put in the program's place, fails the
limits. (On the chip, at the cells' sizes, ``bench/calibrate.py`` reads
it; here at a size a test run holds.)"""

import numpy as np

from bench import backbones, compare, traffic
from bench.tests import _small


def test_float8_training_is_not_correct():
    cell = _small.cell("train")
    it = traffic.pair_batches(cell.traffic, cell.config["vocab_size"],
                              cell.seed)
    batches = [next(it) for _ in range(3)]
    bb = backbones.load(cell.config)
    ref = bb.train_readings(cell.config, cell.seed, batches)
    ctrl = bb.train_readings(cell.config, cell.seed, batches, quant=True)
    numbers = compare.train_numbers(ctrl, ref)
    assert not compare.passed(compare.checks(numbers, cell.limits))


def test_float8_encoding_is_not_correct():
    cell = _small.cell("encode")
    b = next(traffic.doc_batches(dict(cell.traffic, docs=16),
                                 cell.config["vocab_size"], cell.seed))
    k = cell.config["run"]["rep_topk"]
    dummy = np.zeros((16, k), np.int32)
    bb = backbones.load(cell.config)
    ctrl = bb.encode_readings(cell.config, cell.seed, b["tokens"], b["mask"],
                              dummy, block=8, quant=True)
    ref = bb.encode_readings(cell.config, cell.seed, b["tokens"], b["mask"],
                             ctrl["indices"], block=8)
    numbers = compare.encode_numbers(ctrl["values"], ref["at"], ref["values"])
    assert not compare.passed(compare.checks(numbers, cell.limits))
