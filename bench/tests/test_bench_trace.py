"""The reduction from a trace to busy, idle and kernel time: interval
arithmetic by hand, a synthetic trace, and a small trace recorded on a
TPU v5e (``bench/testdata/small.xplane.pb``: three calls of the Sparton
head's forward and backward, B=8, S=128, V=4096, D=768, under the same
host spans a run uses)."""

import os
import types

import pytest

from bench import trace

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "small.xplane.pb")


def test_op_name():
    assert trace.op_name("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %a)") == \
        "fusion.12"
    assert trace.op_name("jit_step") == "jit_step"


def test_interval_arithmetic():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    busy = [(1, 2), (4, 6)]
    assert trace.complement(busy, 0, 10) == [(0, 1), (2, 4), (6, 10)]
    assert trace.complement(busy, 1.5, 5) == [(2, 4)]
    assert trace.intersect([(0, 3), (5, 8)], [(2, 6)]) == [(2, 3), (5, 6)]
    assert trace.length([(0, 3), (5, 8)]) == 6


def _ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _line(name, events):
    return types.SimpleNamespace(name=name, events=events)


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=lines)


def test_reduce_synthetic_trace():
    host = _plane("/host:CPU", [_line("python", [
        _ev("window", 100, 1000), _ev("dispatch", 100, 300),
        _ev("sync_loss", 400, 700)])])
    dev = [_plane(f"/device:TPU:{i}", [_line("XLA Ops", [
        _ev("k_fwd", 50, 250), _ev("fusion.1", 500, 200),
        _ev("k_fwd", 600, 200)]), _line("XLA Modules", [
            _ev("jit_step", 0, 2000)])]) for i in (0, 1)]
    red = trace.reduce(types.SimpleNamespace(planes=[host] + dev),
                       ("dispatch", "sync_loss", "fetch_batch"))
    ns = 1e-9
    assert red["n_devices"] == 2
    assert red["window_s"] == pytest.approx(1000 * ns)
    # busy in [100, 1100]: [100, 300] and [500, 800]
    assert red["busy_s"] == pytest.approx(500 * ns)
    assert red["op_seconds"]["k_fwd"] == pytest.approx(400 * ns)
    assert trace.kernel_seconds(red, "fwd") == pytest.approx(400 * ns)
    # idle [300, 500] under sync_loss (400..) and dispatch (..400);
    # [800, 1100] under sync_loss
    assert red["idle_seconds"]["dispatch"] == pytest.approx(100 * ns)
    assert red["idle_seconds"]["sync_loss"] == pytest.approx(400 * ns)
    assert red["idle_seconds"]["other"] == pytest.approx(0.0)
    b = trace.breakdown(red)
    assert b["device_ops"][0][0] == "k_fwd"
    assert b["idle_gaps"][0][0] == "sync_loss"


def test_reduce_recorded_chip_trace():
    from bench.metrics import _shared

    red = trace.reduce(trace.load(TESTDATA),
                       ("fetch_batch", "dispatch", "sync_loss"))
    assert red["n_devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    for kernel in (_shared.FWD_KERNEL, _shared.DH_KERNEL, _shared.DE_KERNEL):
        assert sum(n for name, n in red["op_counts"].items()
                   if kernel in name) == 3
        assert trace.kernel_seconds(red, kernel) > 0
    idle = sum(red["idle_seconds"].values())
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    assert red["idle_seconds"]["fetch_batch"] > 0
