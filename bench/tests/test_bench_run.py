"""The command's refusals and the data that drive it: every name in
BENCHMARK.json finds its files."""

import importlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import backbones, run
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
    driver = spec["traffic"]["driver"]
    assert os.path.exists(os.path.join(_small.BENCH, "drivers",
                                       driver + ".py"))
    assert callable(importlib.import_module(f"bench.drivers.{driver}").run)
    backbone = spec["config"]["backbone"]
    assert os.path.exists(os.path.join(_small.BENCH, "backbones",
                                       backbone + ".py"))
    module = backbones.load(spec["config"])
    for name in backbones.INTERFACE:
        assert hasattr(module, name), (backbone, name)
    assert _small.load("limits", cell)["limits"]
    reported = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert spec["per_layer"]
    for m in spec["per_layer"]:
        assert m["moves"] in reported
        reader = run._load_file(os.path.join(
            _small.BENCH, "metrics", m["name"] + ".py"))
        assert callable(reader.read)


# the two configurations at the widths and vocabularies of their sources
PINNED = {"splade_bert": (768, 12, 12, 3072, 30522),
          "splade_xlmr": (768, 12, 12, 3072, 250002)}
# a key that names a width (a hidden, intermediate, latent, state, head or
# window size, an expansion factor, the experts per token), which
# ``reduced`` may never list; depth, head and expert counts and the
# vocabulary may be cut to one chip's share
WIDTH = re.compile(r"(?<!vocab)_size$|_dim$|_rank$|window|expansion"
                   r"|^num_experts_per_tok$")


def test_configs_are_published_widths():
    for c in SPEC["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        published = cfg["published"]
        for key, value in published.items():
            if key not in c["reduced"]:
                assert cfg[key] == value, (c["name"], key)
        for key in c["reduced"]:
            assert key in published and cfg[key] != published[key], key
            assert not WIDTH.search(key), (c["name"], key)
        if c["name"] in PINNED:
            assert (cfg["hidden_size"], cfg["num_hidden_layers"],
                    cfg["num_attention_heads"], cfg["intermediate_size"],
                    cfg["vocab_size"]) == PINNED[c["name"]]
    assert set(PINNED) <= {c["name"] for c in SPEC["configs"]}


# a second backbone, as a later change would add it: a module of its own
# (here the dense encoder under another name, noting the calls the
# driver makes of it), a configuration naming it, a traffic file, a
# limits file and one cell
TWIN = '''"""The dense encoder under another name."""

from bench.backbones import dense_encoder as _dense
from bench.backbones.dense_encoder import (  # noqa: F401
    SMALL, encode_readings, encode_work, init_params, sizes, step_work,
    train_readings)

CALLS = []


def program_config(config):
    CALLS.append("program_config")
    return _dense.program_config(config)


def init_state(config, seed):
    CALLS.append("init_state")
    return _dense.init_state(config, seed)


def change_norms(params, config, seed):
    CALLS.append("change_norms")
    return _dense.change_norms(params, config, seed)
'''

ADDED_RUN = """
import json
from bench import backbones, compare, run
from bench.drivers import train
from bench.tests import _small

spec = run.load_spec(".", "twin_train")
bb = backbones.load(spec["config"])
cell = _small.cell("train", name="twin")
st = train.start(cell, train.build(cell))
st.loader.close()
ref = bb.train_readings(cell.config, cell.seed, st.batches)
numbers = compare.train_numbers(st.readings, ref)
print(json.dumps({"module": bb.__file__, "calls": bb.CALLS,
                  "traffic": spec["traffic"],
                  "per_layer": [m["name"] for m in spec["per_layer"]],
                  "correct": compare.passed(
                      compare.checks(numbers, cell.limits))}))
"""


def _write_json(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=2)


def test_a_backbone_is_added_by_new_files_alone(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(_small.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    bench = root / "bench"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    (bench / "backbones" / "dense_twin.py").write_text(TWIN)
    config = dict(_small.load("configs", "splade_bert"), backbone="dense_twin")
    _write_json(bench / "configs" / "twin.json", config)
    _write_json(bench / "traffic" / "twin_pairs.json",
                _small.load("traffic", "train_pairs_448"))
    _write_json(bench / "limits" / "twin_train.json",
                {"limits": _small.LIMITS["train"]})
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "twin", "source": config["source"],
        "file": "bench/configs/twin.json", "reduced": [],
        "why": "the dense encoder under a second backbone name"})
    spec["workloads"].append({
        "name": "twin_train", "config": "twin", "traffic": "twin_pairs",
        "chips": 1, "why": "a training cell of the second backbone"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "bert_train" in m.get("workloads", ()):
            m["workloads"].append("twin_train")
    _write_json(root / "BENCHMARK.json", spec)
    # every file of the benchmark that was there is as it was
    assert {p: p.read_bytes() for p in before} == before

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", ADDED_RUN], cwd=root,
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["module"] == str(bench / "backbones" / "dense_twin.py")
    # the driver took the step, the state and the change from it
    assert got["calls"] == ["program_config", "init_state", "change_norms"]
    assert got["traffic"]["driver"] == "train"
    assert "backbone_ms.train" in got["per_layer"]
    assert got["correct"]


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
