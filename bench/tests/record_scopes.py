"""Records the small chip trace of the program's scopes that
``test_bench_scopes.py`` reads: three training steps and three encoded
batches at the CPU tests' sizes (``_small.py``), each batch taken from
the program's loader, under the host spans a run uses (``window``,
``fetch_batch``, ``dispatch``, ``sync_loss`` and ``copy_reps``). It
writes ``bench/testdata/scopes.xplane.pb`` and, beside it,
``scopes.op_names.json``: each module's map from instruction to
``op_name`` (``bench.scopes.op_names``), in place of the modules' text,
kept to the instructions that ran on the device.

Run on a TPU, from the root of the repository:

    python3 bench/tests/record_scopes.py
"""

import glob
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TESTDATA = os.path.join(ROOT, "bench", "testdata")
STEPS = 3


# the modules' HLO protos, which the reduction does not read: two thirds
# of the file (the profiler writes them whatever its options say)
METADATA_PLANE = "/host:metadata"


def _varint(data: bytes, i: int):
    value, shift = 0, 0
    while True:
        byte = data[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(data: bytes):
    """(field number, raw bytes of the whole field, payload of a
    length-delimited field) of a serialized protobuf message."""
    i = 0
    while i < len(data):
        start = i
        key, i = _varint(data, i)
        payload, wire = None, key & 7
        if wire == 0:
            _, i = _varint(data, i)
        elif wire == 2:
            n, i = _varint(data, i)
            payload, i = data[i:i + n], i + n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, data[start:i], payload


def without_plane(xspace: bytes, name: str) -> bytes:
    """A serialized ``XSpace`` without the plane named ``name``: planes
    are its field 1, a plane's name is the plane's field 2."""
    def named(plane):
        return any(f == 2 and p == name.encode()
                   for f, _, p in _fields(plane))

    return b"".join(raw for f, raw, p in _fields(xspace)
                    if not (f == 1 and named(p)))


def main() -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("record_scopes: needs a TPU", file=sys.stderr)
        return 1
    from bench import scopes, trace
    from bench.drivers import encode, train
    from bench.drivers._common import span
    from bench.tests import _small

    tr = _small.cell("train")
    st = train.start(tr, train.build(tr))
    es = encode.start(_small.cell("encode"))
    fetch, dispatch, finish = encode.Encoded().steps(es)
    tmp = tempfile.mkdtemp(prefix="record_scopes_")
    try:
        # without Python's calls, which the reduction does not read
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with span("window", True):
            for _ in range(STEPS):
                with span("fetch_batch", True):
                    batch = train._place(next(st.loader))
                with span("dispatch", True):
                    st.state, metrics = st.step(st.state, batch)
                with span("sync_loss", True):
                    float(metrics["loss"])
            for _ in range(STEPS):
                with span("fetch_batch", True):
                    docs = fetch()
                with span("dispatch", True):
                    rep = dispatch(docs)
                with span("copy_reps", True):
                    finish(rep)
        jax.profiler.stop_trace()
        # the executables the calls ran, from jit's own cache
        texts = [st.step.lower(st.state, batch).compile().as_text(),
                 es.encode.func.lower(es.params, jnp.asarray(docs["tokens"]),
                                      jnp.asarray(docs["mask"]))
                 .compile().as_text()]
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True)
        with open(path, "rb") as f:
            data = without_plane(f.read(), METADATA_PLANE)
        with open(os.path.join(TESTDATA, "scopes.xplane.pb"), "wb") as f:
            f.write(data)
        # keep the instructions that ran on the device
        ran = {name for ops in trace.device_ops(trace.load(path)).values()
               for name, _, _ in ops}
        names = {module: {k: v for k, v in m.items() if k in ran}
                 for module, m in map(scopes.op_names, texts)}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
        st.loader.close()
        es.loader.close()
    with open(os.path.join(TESTDATA, "scopes.op_names.json"), "w") as f:
        json.dump(names, f, indent=0, sort_keys=True)
    print(json.dumps({m: len(n) for m, n in names.items()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
