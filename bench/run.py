"""One run of one benchmark cell, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
        --trace <0|1>

Everything about a cell is found by name: its entry in ``BENCHMARK.json``
names a configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``, which names its driver,
``bench/drivers/<driver>.py``); its limits are in
``bench/limits/<cell>.json``; the configuration names its backbone
(``bench/backbones/<backbone>.py``); each per-layer metric is read by
``bench/metrics/<metric>.py``. The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and with ``--trace 1`` a ``breakdown``), then ``checks``,
each compared number beside its limit, as also printed last on standard
error. With no TPU, fewer chips than the cell asks for, or a device kind
missing from ``bench/peaks.json``, it exits non-zero and prints no
result.
"""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_spec(root: str, workload: str) -> dict:
    """The cell's entry with its configuration, traffic, limits and the
    metrics it reports, from ``BENCHMARK.json`` and the files it names."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; one of {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[cell["config"]]["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           f"{cell['traffic']}.json")) as f:
        traffic = json.load(f)
    applies = lambda m: workload in m.get("workloads", [workload])
    e2e = [m for m in bench["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}

    def reported(m):
        if "workloads" in m:
            return workload in m["workloads"]
        return m["moves"] in names

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": e2e,
            "per_layer": [m for m in bench["per_layer"] if reported(m)]}


def _load_file(path: str):
    name = "bench_metric_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def per_layer(root: str, metrics, ctx: dict) -> dict:
    """Each per-layer metric from its reader; a reader that finds nothing
    returns None and the metric is left out."""
    out = {}
    for m in metrics:
        reader = _load_file(os.path.join(root, "bench", "metrics",
                                         f"{m['name']}.py"))
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(spec: dict, cell, peaks: dict, device) -> dict:
    """Drive the cell and build the result object (without printing)."""
    from bench import compare, trace

    driver = importlib.import_module(
        f"bench.drivers.{spec['traffic']['driver']}")
    outcome = driver.run(cell)
    checked = compare.checks(outcome.numbers, cell.limits)
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": cell.chips,
           "memory_peak_bytes": outcome.memory_peak_bytes}
    result = {"correct": compare.passed(checked) and outcome.failed == 0,
              "attempted": outcome.attempted, "failed": outcome.failed}
    if cell.trace:
        red = outcome.reduced
        ctx = {"reduced": red, "work": outcome.work, "peaks": peaks,
               "chips": cell.chips}
        result["metrics"] = per_layer(ROOT, spec["per_layer"], ctx)
        dev.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["device"] = dev
        result["breakdown"] = trace.breakdown(red)
    else:
        e2e = outcome.end_to_end
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in spec["end_to_end"]}
        result["device"] = dev
    result["window_compiles"] = outcome.window_compiles
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checked}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: no program under {src}", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, src]
    # blocks are what the program resolves untuned: no winner file
    os.environ["SPARTON_AUTOTUNE_CACHE"] = os.path.join(
        ROOT, ".autotune", "bench_untuned.json")
    spec = load_spec(ROOT, args.workload)

    import jax

    from bench import compare, work
    from bench.drivers._common import Cell
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    # every program, however quick to compile, goes to the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if args.trace:
        # the scopes are read from the executables' op_name metadata,
        # which the cache's key otherwise leaves out
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
    devices = jax.devices()
    chips = spec["cell"]["chips"]
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} TPU chip(s); JAX has "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 1
    try:
        peaks = work.peaks(devices[0].device_kind)
    except KeyError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    cell = Cell(name=args.workload, config=spec["config"],
                traffic=spec["traffic"], chips=chips, seed=args.seed,
                seconds=args.seconds, trace=bool(args.trace), t0=T0,
                limits=compare.load_limits(ROOT, args.workload))
    result = run_cell(spec, cell, peaks, devices[0])
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
