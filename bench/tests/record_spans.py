#!/usr/bin/env python3
"""Record ``data/spans.xplane.pb.gz``: a profiler trace of a short window
of the front end with the program's tracing on, for tests/test_spans.py.

    python3 bench/tests/record_spans.py OUT.xplane.pb.gz [--host-level 1]

The window (``bench.window``) holds two 20-iteration solves of two small
subjects and one virtual-lesion query on the first, each sent when the
last answer is in: so each job's engine is built and its runner traced and
compiled inside the window.  The trace is taken with the harness's
profiler options, host tracer level 2 unless ``--host-level`` says
otherwise (level 1 keeps the program's spans and JAX's lowering and
compile events, without XLA's passes).  Only what the reductions read is
kept (:func:`shrink`; ``/host:metadata`` alone, the compiled programs, is
megabytes).  It prints the file's size and the reduction of
``bench/spans.py``.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
SMALL = {"n_theta": 96, "n_atoms": 96, "n_fibers": 2000, "grid": [16, 16, 16],
         "tractography": "PROB", "active_frac": 0.35, "noise": 0.01}


KEEP = ("/host:CPU", "/device:TPU:0")


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _kept(buf, keep) -> bytes:
    """The fields of message ``buf`` for which ``keep(field, body)``, each
    re-encoded around its (possibly filtered) body; every field of an
    XSpace, XPlane and XLine that this filters is length-delimited, the
    rest (varints) pass unchanged."""
    from bench import spans
    out, i = [], 0
    while i < len(buf):
        start = i
        key, i = spans._varint(buf, i)
        if key & 7 != 2:
            _, i = spans._varint(buf, i)
            out.append(bytes(buf[start:i]))
            continue
        size, i = spans._varint(buf, i)
        body, i = buf[i:i + size], i + size
        body = keep(key >> 3, body)
        if body is not None:
            out.append(_varint(key) + _varint(len(body)) + bytes(body))
    return b"".join(out)


def shrink(raw: bytes) -> bytes:
    """Only what the reductions read: the host plane's threads that wrote
    a program span or a JAX call, and the first TPU's ``XLA Ops`` line
    with the metadata of its ops."""
    from bench import spans, trace
    wanted = spans.PROGRAM_SPANS + spans.COMPILE

    def line(names, f, body):
        if f != 3:
            return body
        name = spans._text(dict(spans._fields(body)).get(2, b""))
        if name == trace.OPS_LINE:
            return body
        ids = {dict(spans._fields(e)).get(1) for g, e in spans._fields(body)
               if g == 4}
        return body if any(names.get(m, "").startswith(
            wanted + (trace.BENCH_PREFIX, spans.PJIT_PREFIX))
            for m in ids) else None

    def plane(f, body):
        if f != 1:
            return body
        name, _, names = spans._plane(body, False)
        if name not in KEEP:
            return None
        return _kept(body, lambda g, b: line(names, g, b))

    return _kept(memoryview(raw), plane)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("out")
    ap.add_argument("--host-level", type=int, default=2)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax
    from bench import gen, harness, spans, traffic
    from repro import obs
    from repro.science.lesion import lesion_problem, warm_start_weights
    from repro.serve.frontend import LifeFrontend

    d = gen.dictionary(SMALL["n_atoms"], SMALL["n_theta"])
    subjects = gen.subjects(SMALL, 2, seed=11)
    problems = [harness.to_problem(s, d) for s in subjects]
    bundle = traffic.bundles(subjects[0], size=50, count=1, seed=11)[0]
    fe = LifeFrontend(harness.life_config(False, {}))
    trace_dir = tempfile.mkdtemp(prefix="spans-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = args.host_level
    obs.enable()
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            answers = [fe.submit_async(p, n_iters=20).result(timeout=300)
                       for p in problems]
            w0 = warm_start_weights(answers[0][0], bundle)
            fe.submit_async(lesion_problem(problems[0], bundle), n_iters=20,
                            w0=w0).result(timeout=300)
    finally:
        jax.profiler.stop_trace()
        obs.disable()
        fe.shutdown(drain=False)
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    data = gzip.compress(shrink(Path(path).read_bytes()), 9)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_bytes(data)
    print(json.dumps({"out": args.out, "bytes": len(data),
                      "device": jax.devices()[0].device_kind,
                      "analysis": spans.analyse(args.out)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
