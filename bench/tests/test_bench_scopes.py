"""Layer time from the program's named scopes and host spans: the
parser of a compiled module's ``op_name``s, the union per scope on a
synthetic trace, the scopes in the small step's and encoder's compiled
modules, the readers of ``bench/metrics/`` that take them, and a small
trace recorded on a TPU v5e
(``bench/testdata/scopes.xplane.pb`` with ``scopes.op_names.json``,
made by ``record_scopes.py``: three training steps and three encoded
batches at ``_small.py``'s sizes)."""

import json
import os
import types

import jax
import pytest

from bench import run, scopes, trace
from bench.tests import _small

TESTDATA = os.path.join(_small.BENCH, "testdata")
# the scopes the program names (PERF.md, section 3)
SCOPES = ("backbone", "optimizer", "sparsify", "head_order")

HLO = """HloModule jit_step, is_scheduled=true, entry_computation_layout={()}

%fused_computation.3 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step)/optimizer/mul" stack_frame_id=3}
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="state"}
  %while.130 = (f32[8]{0}) while(%tuple.2), condition=%cond, body=%body, metadata={op_name="jit(step)/transpose(jvp(backbone))/while" source_file="t.py" source_line=3}
  %copy-start.1 = (f32[8]{0}, u32[]) copy-start(%Arg_0.1)
  %fusion.12 = f32[8]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(step)/optimizer/sub"}
  ROOT %jvp_jit__forward_call__.2 = f32[8]{0} custom-call(%fusion.12), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(jit(_forward_call))/pallas_call" scheduling_name="a \\"quoted\\" name"}
}
"""


def test_op_names_parses_each_instruction():
    module, names = scopes.op_names(HLO)
    assert module == "jit_step"
    assert names == {
        "multiply.1": "jit(step)/optimizer/mul",
        "Arg_0.1": "state",
        "while.130": "jit(step)/transpose(jvp(backbone))/while",
        "fusion.12": "jit(step)/optimizer/sub",
        "jvp_jit__forward_call__.2": "jit(step)/jvp(jit(_forward_call))/"
                                     "pallas_call"}
    with pytest.raises(ValueError):
        scopes.op_names("ENTRY %main {}")


@pytest.mark.parametrize("op_name,scope,inside", [
    ("jit(step)/transpose(jvp(backbone))/while", "backbone", True),
    ("jit(step)/jvp(backbone)/while/body/closed_call/add", "backbone", True),
    ("jit(step)/optimizer/sub", "optimizer", True),
    ("jit(encode)/sparsify/reduce_sum", "sparsify", True),
    ("jit(step)/jvp(jit(_forward_call))/pallas_call", "backbone", False),
    ("jit(step)/backbone_extra/add", "backbone", False),
    ("jit(optimizer_step)/add", "optimizer", False),
])
def test_scope_is_a_whole_component(op_name, scope, inside):
    assert (scope in scopes.scopes_of(op_name)) == inside


def _ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _line(name, events):
    return types.SimpleNamespace(name=name, events=events)


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=lines)


MODULES = {"jit_step": {"while.1": "jit(step)/jvp(backbone)/while",
                        "fusion.2": "jit(step)/jvp(backbone)/while/body/dot",
                        "fusion.3": "jit(step)/optimizer/sub",
                        "k_fwd.1": "jit(step)/jvp(jit(_forward_call))/x"},
           "jit_other": {"fusion.2": "jit(other)/add"}}


def _synthetic(devices=(0, 1)):
    host = _plane("/host:CPU", [_line("python", [
        _ev("window", 200, 1000), _ev("fetch_batch", 150, 100),
        _ev("loader.next", 160, 80), _ev("fetch_batch", 1000, 50),
        _ev("loader.next", 1010, 20)])])
    dev = [_plane(f"/device:TPU:{i}", [
        _line("XLA Modules", [_ev("jit_step(8412)", 100, 1000),
                              _ev("jit_other(77)", 1100, 50)]),
        _line("XLA Ops", [
            # a while and an op of its body: counted once
            _ev("%while.1 = (f32[8]) while(...)", 100, 400),
            _ev("%fusion.2 = f32[8] fusion(...)", 150, 150),
            # past the window's end: clipped to it
            _ev("%fusion.3 = f32[8] fusion(...)", 1000, 300),
            _ev("%k_fwd.1 = f32[8] custom-call(...)", 600, 100),
            # the same name in another module: no scope
            _ev("%fusion.2 = f32[8] fusion(...)", 1110, 30)])])
        for i in devices]
    return types.SimpleNamespace(planes=[host] + dev)


def test_scope_union_on_a_synthetic_trace():
    got = scopes.scope_seconds(_synthetic(), MODULES)
    ns = 1e-9
    # backbone: [100, 500] from 200 on; optimizer: [1000, 1200]
    assert got["backbone"] == pytest.approx(300 * ns)
    assert got["optimizer"] == pytest.approx(200 * ns)
    assert "sparsify" not in got
    # every component but the operation is a scope: the jitted function
    # and the ones it calls too
    assert got["step"] == pytest.approx((300 + 100 + 200) * ns)
    assert got["_forward_call"] == pytest.approx(100 * ns)
    assert got["other"] == pytest.approx(30 * ns)
    assert not {"sub", "dot", "x", "add"} & set(got)
    # averaged over the devices, as busy time is
    assert scopes.scope_seconds(_synthetic((0,)), MODULES) == got
    # a module not in the map contributes nothing
    mods = {"jit_other": MODULES["jit_other"]}
    assert "backbone" not in scopes.scope_seconds(_synthetic(), mods)


def test_span_seconds_clipped_to_the_window():
    got = scopes.span_seconds(_synthetic())
    assert got["loader.next"] == pytest.approx((40 + 20) * 1e-9)
    assert got["fetch_batch"] == pytest.approx((50 + 50) * 1e-9)
    assert "absent" not in got


def test_reduce_adds_scopes_and_spans_given_the_modules():
    pd = _synthetic()
    red = trace.reduce(pd, ("fetch_batch",), MODULES)
    assert red["scope_seconds"] == scopes.scope_seconds(pd, MODULES)
    assert red["span_seconds"] == scopes.span_seconds(pd)
    plain = trace.reduce(pd, ("fetch_batch",))
    assert "scope_seconds" not in plain and "span_seconds" not in plain
    assert plain == {k: v for k, v in red.items()
                     if k not in ("scope_seconds", "span_seconds")}


READERS = {"backbone_ms.train": "backbone", "backbone_ms.encode": "backbone",
           "optimizer_ms.train": "optimizer",
           "sparsify_ms.encode": "sparsify",
           "head_order_ms.train": "head_order",
           "head_order_ms.encode": "head_order"}
SPAN_READERS = {"input_wait_ms.train": "loader.next",
                "input_wait_ms.encode": "loader.next"}


def _read(metric, reduced, steps):
    reader = run._load_file(os.path.join(_small.BENCH, "metrics",
                                         metric + ".py"))
    return reader.read({"reduced": reduced, "work": {"steps": steps},
                        "peaks": {}, "chips": 1})


@pytest.mark.parametrize("metric", sorted(READERS) + sorted(SPAN_READERS))
def test_scope_reader_on_a_synthetic_trace(metric):
    red = trace.reduce(_synthetic(), ("fetch_batch",), MODULES)
    if metric in SPAN_READERS:
        expected = 1000 * red["span_seconds"][SPAN_READERS[metric]] / 4
    else:
        expected = red["scope_seconds"].get(READERS[metric], 0) * 1000 / 4
    got = _read(metric, red, 4)
    # a scope that reads 0 gives nothing, never a 0
    assert got == (pytest.approx(expected) if expected else None)
    # nor does a trace reduced without the modules
    assert _read(metric, trace.reduce(_synthetic(), ()), 4) is None


@pytest.mark.parametrize("driver,expected", [
    ("train", ("backbone", "optimizer", "head_order")),
    ("encode", ("backbone", "sparsify", "head_order"))])
def test_compiled_modules_carry_the_scopes(driver, expected):
    from bench import backbones, traffic
    from bench.drivers import train
    from repro.runtime import serving

    cell = _small.cell(driver)
    bb = backbones.load(cell.config)
    V = cell.config["vocab_size"]
    if driver == "train":
        state = jax.eval_shape(
            lambda: bb.init_state(cell.config, cell.seed))
        batch = next(traffic.pair_batches(cell.traffic, V, cell.seed))
        lowered = train.build(cell).lower(state, batch)
    else:
        params = jax.eval_shape(
            lambda: bb.init_params(cell.config, cell.seed))
        docs = next(traffic.doc_batches(cell.traffic, V, cell.seed))
        encode = serving.make_config_encoder(params,
                                             bb.program_config(cell.config))
        lowered = encode.func.lower(params, docs["tokens"], docs["mask"])
    module, names = scopes.op_names(lowered.compile().as_text())
    assert module == f"jit_{'step' if driver == 'train' else 'encode'}"
    found = {s for n in names.values() for s in scopes.scopes_of(n)}
    assert set(expected) <= found
    assert not set(SCOPES) - set(expected) & found


def _recorded():
    pd = trace.load(os.path.join(TESTDATA, "scopes.xplane.pb"))
    with open(os.path.join(TESTDATA, "scopes.op_names.json")) as f:
        modules = json.load(f)
    return pd, modules, trace.reduce(pd, ("fetch_batch", "dispatch",
                                          "sync_loss", "copy_reps"), modules)


def test_recorded_chip_trace():
    pd, modules, red = _recorded()
    assert set(modules) == {"jit_step", "jit_encode"}
    got = red["scope_seconds"]
    assert got == scopes.scope_seconds(pd, modules)
    for s in SCOPES:
        assert 0 < got[s] <= red["busy_s"]
    # the kernels keep the names the roofline readers match, and lie
    # outside every scope
    from bench.metrics import _shared

    for kernel in (_shared.FWD_KERNEL, _shared.DH_KERNEL, _shared.DE_KERNEL):
        ops = [(m, k) for m, names in modules.items() for k in names
               if kernel in k]
        assert ops
        for m, k in ops:
            assert not set(SCOPES) & set(scopes.scopes_of(modules[m][k]))
    # the program's span shares the device trace's clock: each wait for
    # input lies inside the harness's fetch_batch around it
    spans = trace.host_spans(pd)
    assert len(spans["loader.next"]) == 6
    for a, b in spans["loader.next"]:
        assert any(fa <= a and b <= fb for fa, fb in spans["fetch_batch"])
    assert 0 < red["span_seconds"]["loader.next"] < red["window_s"]


@pytest.mark.parametrize("metric", sorted(READERS) + sorted(SPAN_READERS))
def test_scope_reader_on_the_recorded_chip_trace(metric):
    # three training steps and three encoded batches in one window
    _, _, red = _recorded()
    got = _read(metric, red, 6)
    if metric in SPAN_READERS:
        seconds = red["span_seconds"][SPAN_READERS[metric]]
    else:
        seconds = red["scope_seconds"][READERS[metric]]
    assert got == pytest.approx(1000 * seconds / 6)
    assert 0 < got < 1000 * red["window_s"] / 6


def test_recorder_drops_only_the_named_plane():
    from bench.tests import record_scopes

    path = os.path.join(TESTDATA, "small.xplane.pb")
    with open(path, "rb") as f:
        data = f.read()
    kept = record_scopes.without_plane(data, record_scopes.METADATA_PLANE)
    assert len(kept) < len(data)
    before, after = (jax.profiler.ProfileData.from_serialized_xspace(d)
                     for d in (data, kept))
    names = [p.name for p in before.planes]
    assert record_scopes.METADATA_PLANE in names
    assert [p.name for p in after.planes] == [
        n for n in names if n != record_scopes.METADATA_PLANE]
    assert trace.reduce(after, ()) == trace.reduce(before, ())
