"""Readings that the limits in ``bench/limits/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,...
        [--control-seeds ...] [--fault-seeds ...]

On the chip, in one process, at the cell's own sizes, it reads for each
seed the numbers ``correct`` compares (``bench.compare``), without a
measured window: in a training cell after the checked first steps, in
an encoding cell over the driver's window closed after one batch. For the
control seeds it reads the same numbers with the reference computed in
float8 put in the program's place; for the fault seeds, with the
program's step leaving out half of each batch. One JSON line per
reading, then the lower reading (the program's largest) and the upper
readings (the smallest of the control and of the fault) of each number.
The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def train_readings(cell_of, seeds, control, faults):
    from bench import backbones, compare, faults as flt
    from bench.drivers import train
    from bench.drivers._common import delete

    def program(cell, jitted):
        st = train.start(cell, jitted)
        st.loader.close()
        delete(st.state)
        return st

    jitted = train.build(cell_of(seeds[0]))
    bb = backbones.load(cell_of(seeds[0]).config)
    half = None
    for seed in seeds:
        cell = cell_of(seed)
        st = program(cell, jitted)
        ref = bb.train_readings(cell.config, seed, st.batches)
        gaps = compare.leaf_gaps(st.readings["grad"], ref["grad"], ref["grad"])
        print(json.dumps({"seed": seed, "grad_leaf_gaps": gaps,
                          "ref_grad": ref["grad"]}), file=sys.stderr)
        yield seed, "program", compare.train_numbers(st.readings, ref)
        if seed in control:
            ctrl = bb.train_readings(cell.config, seed, st.batches,
                                     quant=True)
            yield seed, "control", compare.train_numbers(ctrl, ref)
        if seed in faults:
            if half is None:
                import jax

                from repro.launch import steps
                hp = cell.config["train"]
                step = flt.half_batch(steps.build_lsr_train_step)(
                    bb.program_config(cell.config), None, n_micro=1,
                    n_pairs=cell.traffic["pairs"], lr=hp["lr"],
                    total_steps=hp["total_steps"])
                half = jax.jit(step, donate_argnums=(0,))
            bad = program(cell, half)
            yield seed, "half_batch", compare.train_numbers(bad.readings, ref)


def encode_readings(cell_of, seeds, control, _faults):
    from bench import backbones, compare
    from bench.drivers import encode
    from bench.drivers._common import CompileCounter, delete, measure

    counter = CompileCounter()
    for seed in seeds:
        cell = cell_of(seed)
        st = encode.start(cell)
        enc = encode.Encoded()
        # the driver's own window, closed after its first batch
        measure(0.0, *enc.steps(st), sync_label="copy_reps", traced=False,
                counter=counter)
        st.loader.close()
        delete(st.params)
        tokens, mask, values, indices = enc.sampled(cell)
        yield seed, "program", encode.check(cell, tokens, mask, values,
                                            indices)
        if seed in control:
            bb = backbones.load(cell.config)
            block = cell.config["reference"]["rows"]
            ctrl = bb.encode_readings(cell.config, seed, tokens, mask,
                                      indices, block=block, quant=True)
            ref = bb.encode_readings(cell.config, seed, tokens, mask,
                                     ctrl["indices"], block=block)
            yield seed, "control", compare.encode_numbers(
                ctrl["values"], ref["at"], ref["values"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ["SPARTON_AUTOTUNE_CACHE"] = os.path.join(
        ROOT, ".autotune", "bench_untuned.json")

    import jax

    from bench.drivers._common import Cell
    from bench.run import load_spec
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 1
    spec = load_spec(ROOT, args.workload)

    def cell_of(seed):
        return Cell(name=args.workload, config=spec["config"],
                    traffic=spec["traffic"], chips=1, seed=seed, seconds=0.0,
                    trace=False, t0=time.monotonic(), limits={})

    readings = {"train": train_readings,
                "encode": encode_readings}[spec["traffic"]["driver"]]
    seeds = list(dict.fromkeys(args.seeds + args.control_seeds
                               + args.fault_seeds))
    by_kind = {}
    for seed, kind, numbers in readings(cell_of, seeds, set(
            args.control_seeds), set(args.fault_seeds)):
        print(json.dumps({"seed": seed, "kind": kind, **numbers}), flush=True)
        by_kind.setdefault(kind, []).append(numbers)
    summary = {}
    for name in by_kind["program"][0]:
        summary[name] = {"lower": max(n[name] for n in by_kind["program"])}
        for kind, rows in by_kind.items():
            if kind != "program":
                summary[name][kind] = min(n[name] for n in rows)
    print(json.dumps({"summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
