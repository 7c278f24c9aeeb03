#!/usr/bin/env python3
"""Run one benchmark cell once and print its result.

    python3 bench/run.py --workload stn96-prob50k.solve --seed 7 \
        --seconds 51 --trace 0

The cell, its configuration, traffic mix, limits and metric readers are
found by name under ``bench/`` (see ``bench/README.md``).  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
(traced) ``breakdown``, and last ``checks``: each compared number with its
limit.  The same numbers end standard error.  The line before the result
holds counts that are not metrics: jobs, compiles in the window,
generator lateness, peak device bytes.

It exits 2 with no result when JAX finds no TPU, or fewer chips than the
cell asks for.  ``--control 1`` runs the program's bf16-storage path in
place of fp32: it exists to show that the check fails it.  The program's
plan cache is a new empty directory under ``$TMPDIR`` for the run,
removed at its end (``harness.fresh_plan_cache``); its compiles are cold
too, while the benchmark's own keep the persistent cache in
``.bench_cache/jax``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    cell = harness.load_cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      T_START, control=bool(args.control))
    print(json.dumps({"info": out["info"]}), flush=True)
    print("\n".join(out["check_lines"]), file=sys.stderr, flush=True)
    print(json.dumps(out["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
