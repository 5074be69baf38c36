"""Fault-tolerant runner + serving loop behaviour (injected faults,
fake clocks — no real devices needed)."""

import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime.fault_tolerance import (ElasticMeshManager,
                                           FaultTolerantRunner,
                                           RunnerConfig, StragglerPolicy)
from repro.runtime.serving import (BatchedEncoder, BatchPolicy, Request,
                                   ServingLoop)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _counting_step(durations, clock):
    """A step whose (fake) duration comes from `durations`."""
    it = iter(durations)

    def step(state, batch):
        clock.advance(next(it, 0.1))
        return {"n": state["n"] + 1}, {"loss": 1.0 / (state["n"] + 1)}
    return step


def _batches():
    return itertools.repeat({"x": np.zeros((2,), np.float32)})


def test_runner_runs_and_checkpoints(tmp_path):
    clock = FakeClock()
    runner = FaultTolerantRunner(
        _counting_step([0.1] * 100, clock), {"n": jnp.array(0)},
        _batches(),
        config=RunnerConfig(ckpt_dir=str(tmp_path), ckpt_every=4,
                            max_steps=10, log_every=1),
        clock=clock)
    state = runner.run()
    assert int(state["n"]) == 10
    assert len(runner.metrics_log) == 10
    assert runner.skipped_steps == []
    from repro.checkpoint.store import latest_step
    assert latest_step(str(tmp_path)) == 10


def test_runner_on_step_hook(tmp_path):
    """on_step fires after every successful step with the fresh state;
    a non-empty returned dict lands in metrics_log as its own entry."""
    clock = FakeClock()
    seen = []

    def hook(step, state):
        seen.append((step, int(state["n"])))
        return {"eval_x": step * 10} if step % 3 == 0 else None

    runner = FaultTolerantRunner(
        _counting_step([0.1] * 100, clock), {"n": jnp.array(0)},
        _batches(),
        config=RunnerConfig(ckpt_dir=str(tmp_path), ckpt_every=0,
                            max_steps=6, log_every=0),
        on_step=hook, clock=clock)
    runner.run()
    # hook saw post-step state: after step i the counter is i+1
    assert seen == [(i, i + 1) for i in range(6)]
    assert runner.metrics_log == [{"step": 0, "eval_x": 0},
                                  {"step": 3, "eval_x": 30}]


def test_runner_on_step_skipped_on_straggler(tmp_path):
    """Straggled (skipped) steps must not fire the hook."""
    clock = FakeClock()
    fired = []
    # steps 0/1 fast (build EWMA), step 2 slow twice (retry + skip)
    durations = [0.1, 0.1, 9.0, 9.0] + [0.1] * 10
    runner = FaultTolerantRunner(
        _counting_step(durations, clock), {"n": jnp.array(0)},
        _batches(),
        config=RunnerConfig(
            ckpt_dir=str(tmp_path), ckpt_every=0, max_steps=5,
            log_every=0,
            straggler=StragglerPolicy(slack=2.0, min_deadline_s=0.05)),
        on_step=lambda s, st: fired.append(s),
        clock=clock)
    runner.run()
    assert runner.skipped_steps == [2]
    assert fired == [0, 1, 3, 4]


def test_runner_resume(tmp_path):
    clock = FakeClock()
    cfg = RunnerConfig(ckpt_dir=str(tmp_path), ckpt_every=5, max_steps=5)
    r1 = FaultTolerantRunner(_counting_step([0.1] * 50, clock),
                             {"n": jnp.array(0)}, _batches(),
                             config=cfg, clock=clock)
    r1.run()
    # second run resumes at 5 and continues to 8
    cfg2 = RunnerConfig(ckpt_dir=str(tmp_path), ckpt_every=100,
                        max_steps=8)
    r2 = FaultTolerantRunner(_counting_step([0.1] * 50, clock),
                             {"n": jnp.array(0)}, _batches(),
                             config=cfg2, clock=clock)
    assert r2.try_resume()
    assert r2.start_step == 5
    state = r2.run()
    assert int(state["n"]) == 8


def test_straggler_detection_and_skip(tmp_path):
    clock = FakeClock()
    # establish ~0.1s EWMA, then two huge stalls (initial + retry) => skip
    durations = [0.1] * 5 + [99.0, 99.0] + [0.1] * 20
    policy = StragglerPolicy(slack=3.0, max_retries=1,
                             suspect_threshold=100)
    runner = FaultTolerantRunner(
        _counting_step(durations, clock), {"n": jnp.array(0)},
        _batches(),
        config=RunnerConfig(ckpt_dir=str(tmp_path), ckpt_every=0,
                            max_steps=10, straggler=policy),
        clock=clock)
    state = runner.run()
    assert runner.skipped_steps == [5]
    # the skipped step consumed a batch but not an update
    assert int(state["n"]) == 9


def test_remesh_triggered_after_repeated_suspects(tmp_path):
    clock = FakeClock()
    durations = [0.1] * 3 + [50.0, 50.0] * 3 + [0.1] * 30
    policy = StragglerPolicy(slack=3.0, max_retries=1, suspect_threshold=3)
    remesh_calls = []

    def on_remesh(state):
        remesh_calls.append(True)
        return _counting_step([0.1] * 50, clock), state

    runner = FaultTolerantRunner(
        _counting_step(durations, clock), {"n": jnp.array(0)},
        _batches(),
        config=RunnerConfig(ckpt_dir=str(tmp_path), ckpt_every=0,
                            max_steps=12, straggler=policy),
        on_remesh=on_remesh, clock=clock)
    runner.run()
    assert len(remesh_calls) == 1
    assert len(runner.remesh_events) == 1


def test_step_exception_counts_as_failure(tmp_path):
    clock = FakeClock()
    calls = {"n": 0}

    def flaky(state, batch):
        calls["n"] += 1
        clock.advance(0.1)
        if calls["n"] == 3:
            raise RuntimeError("device lost")
        return {"n": state["n"] + 1}, {"loss": 0.0}

    runner = FaultTolerantRunner(
        flaky, {"n": jnp.array(0)}, _batches(),
        config=RunnerConfig(ckpt_dir=str(tmp_path), ckpt_every=0,
                            max_steps=6),
        clock=clock)
    state = runner.run()
    assert len(runner.skipped_steps) == 1
    assert int(state["n"]) == 5


def test_first_step_exception_is_raised(tmp_path):
    """The first step compiles: its failure (a kernel that does not
    compile) must stop the run, not be skipped like a lost device."""
    clock = FakeClock()

    def broken(state, batch):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    runner = FaultTolerantRunner(
        broken, {"n": jnp.array(0)}, _batches(),
        config=RunnerConfig(ckpt_dir=str(tmp_path), ckpt_every=0,
                            max_steps=3),
        clock=clock)
    with pytest.raises(RuntimeError, match="compile"):
        runner.run()
    assert runner.skipped_steps == []


def test_compile_cache_placed_from_outside(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and is left to JAX; otherwise the
    cache goes to one fixed directory inside the checkout."""
    import os

    import jax

    from repro.launch import compile_cache as cc

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(cc.CACHE_ENV, "/elsewhere/cache")
        assert cc.setup_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(cc.CACHE_ENV)
        path = cc.setup_compile_cache()
        assert path == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_elastic_mesh_factorization():
    mgr = ElasticMeshManager(lambda shape: shape, model_axis=16)
    assert mgr.factorize(512) == (1, 32, 16)
    assert mgr.factorize(256) == (1, 16, 16)
    assert mgr.factorize(255) == (1, 8, 16)   # lost a device
    assert mgr.factorize(24) == (1, 1, 16)
    assert mgr.factorize(8) == (1, 1, 8)
    assert mgr.factorize(1) == (1, 1, 1)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def _fake_encoder():
    def encode(tokens, mask):
        # "sparse rep" = bag of token counts over a fake 32-dim vocab
        B, S = tokens.shape
        out = np.zeros((B, 32), np.float32)
        for i in range(B):
            for t, m in zip(np.asarray(tokens[i]), np.asarray(mask[i])):
                if m:
                    out[i, int(t) % 32] += 1
        return out
    return encode


def test_serving_loop_batches_by_size():
    clock = FakeClock()
    enc = BatchedEncoder(_fake_encoder(),
                         policy=BatchPolicy(max_batch=4, max_wait_s=10.0))
    loop = ServingLoop(enc, clock=clock)
    for uid in range(10):
        loop.submit(Request(uid=uid, tokens=np.array([uid], np.int32)))
        loop.tick()
    loop.drain()
    assert len(loop.completed) == 10
    assert loop.batch_sizes[0] == 4  # size-triggered batches first
    assert sum(loop.batch_sizes) == 10


def test_serving_loop_deadline_trigger():
    clock = FakeClock()
    enc = BatchedEncoder(_fake_encoder(),
                         policy=BatchPolicy(max_batch=64, max_wait_s=0.005))
    loop = ServingLoop(enc, clock=clock)
    loop.submit(Request(uid=0, tokens=np.array([3], np.int32)))
    assert loop.tick() == 0        # too fresh
    clock.advance(0.01)
    assert loop.tick() == 1        # deadline hit, dispatched alone
    assert 0 in loop.completed


def test_take_pops_results_memory_bounded():
    """Results must be popped on read: a long-lived loop whose caller
    collects every result holds nothing afterwards — memory is bounded
    by in-flight work, not total traffic."""
    clock = FakeClock()
    enc = BatchedEncoder(_fake_encoder(),
                         policy=BatchPolicy(max_batch=4, max_wait_s=0.0))
    loop = ServingLoop(enc, clock=clock)
    high_water = 0
    for uid in range(64):
        loop.submit(Request(uid=uid, tokens=np.array([uid], np.int32)))
        clock.advance(0.01)
        loop.tick()
        high_water = max(high_water, len(loop.completed))
        if uid % 4 == 3:           # collect the finished micro-batch
            for u in range(uid - 3, uid + 1):
                rep = loop.take(u)
                assert rep.shape == (32,)
            assert len(loop.completed) == 0
    loop.drain()
    # never accumulated more than one dispatched batch
    assert high_water <= 4
    assert len(loop.completed) == 0


def test_take_raises_on_missing_and_double_take():
    clock = FakeClock()
    enc = BatchedEncoder(_fake_encoder(),
                         policy=BatchPolicy(max_batch=1, max_wait_s=0.0))
    loop = ServingLoop(enc, clock=clock)
    loop.submit(Request(uid=7, tokens=np.array([1], np.int32)))
    clock.advance(1.0)
    loop.tick()
    loop.take(7)
    with pytest.raises(KeyError):
        loop.take(7)               # a result is never handed out twice
    with pytest.raises(KeyError):
        loop.take(8)               # never completed


def test_serving_pads_and_masks_correctly():
    enc = BatchedEncoder(_fake_encoder(),
                         policy=BatchPolicy(pad_to_multiple=8))
    reqs = [Request(uid=0, tokens=np.array([1, 1, 1], np.int32)),
            Request(uid=1, tokens=np.array([2], np.int32))]
    out = enc.encode_batch(reqs)
    assert out[0][1] == 3.0   # three 1-tokens counted, padding ignored
    assert out[1][2] == 1.0
    assert out[1][0] == 0.0   # pad token 0 masked out
