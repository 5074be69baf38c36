"""The command's refusals and the data that drive it: every name in
BENCHMARK.json finds its files."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import run
from bench.tests import _small

ROOT = os.path.dirname(_small.BENCH)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
CELLS = [w["name"] for w in SPEC["workloads"]]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"), "--workload",
         CELLS[0], "--seed", str(2 ** 33 + 1), "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=root, timeout=300)


def test_no_tpu_means_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_alone_without_the_program_gives_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(_small.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_finds_its_files(cell):
    spec = run.load_spec(ROOT, cell)
    assert spec["traffic"]["driver"] in ("train", "encode")
    assert os.path.exists(os.path.join(
        _small.BENCH, "drivers", spec["traffic"]["driver"] + ".py"))
    assert _small.load("limits", cell)["limits"]
    reported = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert spec["per_layer"]
    for m in spec["per_layer"]:
        assert m["moves"] in reported
        reader = run._load_file(os.path.join(
            _small.BENCH, "metrics", m["name"] + ".py"))
        assert callable(reader.read)


def test_configs_are_published_widths():
    for c in SPEC["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert (cfg["hidden_size"], cfg["num_hidden_layers"],
                cfg["num_attention_heads"], cfg["intermediate_size"]) == (
                    768, 12, 12, 3072)



class _Device:
    def __init__(self, **stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


def test_peak_counts_memory_reserved_for_temporaries():
    from bench.drivers._common import peak_bytes

    devices = [_Device(peak_bytes_in_use=3, peak_bytes_reserved=10),
               _Device(peak_bytes_in_use=8, peak_bytes_reserved=4),
               _Device()]
    assert peak_bytes(devices) == 13
