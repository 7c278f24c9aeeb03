#!/usr/bin/env python3
"""Find the knee of an open mix: the highest rate the program sustains.

    python3 bench/sweep.py --workload roi-prob5k.served --seed 7 \
        --seconds 51 --rates 0.25 0.5 0.75 1 1.25

Runs the cell once per rate through ``harness.run``, in one process, with
the mix's ``rate_per_s`` replaced by the rate (a cell ``BENCHMARK.json``
does not list yet is made from its files, ``configs/<config>.json``,
``traffic/<traffic>.json`` and ``limits/<cell>.json``).  Each run is a
run as ``run.py`` makes it: set-up with the mix's warm-up at that rate,
the window with its drain, and the check; JAX's in-memory compile caches
are cleared first, so each rate starts as cold as a run of its own.
Prints one JSON line per rate: whether it was sustained and correct, the
compiles in the window, and the window's summary (jobs sent and
answered, how late the sender ran, latency by thirds).

The rates run in ascending order, and the lowest is the unloaded
reference: its median latency over the window is an unloaded job's, so
take it well below the knee (jobs should rarely overlap).  A rate is
sustained where ``harness.sustained`` holds: every job answered within
the drain, a first third whose median latency is at most
``harness.STEADY_FACTOR`` unloaded jobs, and a last third within 1.5
times the first.  The sweep stops after ``--stop-after`` rates in a row
that were not.  ``PERF.md`` records each sweep.  Needs a TPU, as
``run.py`` does.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--stop-after", type=int, default=2)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    try:
        cell = harness.load_cell(args.workload)
    except KeyError:                    # a cell not added yet
        cell = harness.cell_from_files(args.workload)
    if cell.mix["loop"] != "open":
        print("sweep: the cell's mix is not an open loop", file=sys.stderr)
        return 2
    unloaded_s, failed = None, 0
    for rate in sorted(args.rates):
        jax.clear_caches()
        out = harness.run(
            dataclasses.replace(cell, mix=dict(cell.mix, rate_per_s=rate)),
            args.seed, args.seconds, False, time.perf_counter())
        info, summary = out["info"], out["info"]["open"]
        if unloaded_s is None:
            lat = [lat for _, lat in info["sent_and_latency_s"]
                   if lat is not None]
            unloaded_s = float(np.median(lat)) if lat else float("inf")
        ok = harness.sustained(summary, unloaded_s)
        print(json.dumps({
            "rate_per_s": rate, "sustained": ok,
            "correct": out["result"]["correct"], "unloaded_s": unloaded_s,
            "compiles_in_window": info["compiles_in_window"],
            **summary}), flush=True)
        failed = 0 if ok else failed + 1
        if failed >= args.stop_after:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
