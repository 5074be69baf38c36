"""A whole run at a small size on the CPU, the look for a chip skipped:
sound, it is correct; with the timed path broken underneath, it is not."""

import jax
import pytest

from bench import faults, run
from bench.tests import _small

E2E = {"train": ("train_tokens_per_s", "train_peak_hbm_gib", "setup_s"),
       "encode": ("encode_tokens_per_s", "setup_s")}


def _correct(driver):
    cell = _small.cell(driver)
    spec = {"traffic": cell.traffic, "per_layer": [],
            "end_to_end": [{"name": n, "unit": "-"} for n in E2E[driver]]}
    result = run.run_cell(spec, cell, {}, jax.devices()[0])
    assert result["attempted"] > 0 and list(result)[-1] == "checks"
    return result["correct"]


def test_sound_train_run_is_correct():
    assert _correct("train")


@pytest.mark.parametrize("fault", [faults.unchanged_state, faults.half_batch])
def test_broken_train_step_is_not_correct(fault, monkeypatch):
    from repro.launch import steps

    monkeypatch.setattr(steps, "build_lsr_train_step",
                        fault(steps.build_lsr_train_step))
    assert not _correct("train")


def test_sound_encode_run_is_correct():
    assert _correct("encode")


def test_altered_answer_is_not_correct(monkeypatch):
    from repro.runtime import serving

    monkeypatch.setattr(serving, "make_config_encoder",
                        faults.altered_answer(serving.make_config_encoder))
    assert not _correct("encode")
