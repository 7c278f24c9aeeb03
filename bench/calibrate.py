#!/usr/bin/env python3
"""Readings for a cell's limits.

    python3 bench/calibrate.py --workload stn96-prob50k.solve --seconds 30 \
        --seeds 11 12 13 --control-seeds 21 22 23

Runs the cell in one process, once per seed: the program as configured on
``--seeds``, the program's bf16 control on ``--control-seeds``.  Prints one
JSON line per run: the compared numbers, the end-to-end metrics and the
run's counts.  The lower reading of a number is the largest the program
gives, the upper the smallest the control gives; ``PERF.md`` records both
and the limit set between them.  Needs a TPU, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    cell = harness.load_cell(args.workload)
    runs = [(s, False) for s in args.seeds] + \
        [(s, True) for s in args.control_seeds]
    for seed, control in runs:
        harness.enable_compile_cache()
        out = harness.run(cell, seed, args.seconds, bool(args.trace),
                          time.perf_counter(), control=control)
        res = out["result"]
        print(json.dumps({
            "seed": seed, "control": control, "correct": res["correct"],
            "readings": {k: v["value"] for k, v in res["checks"].items()},
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "device": res["device"], "info": out["info"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
