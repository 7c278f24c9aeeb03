"""Benchmark harness: one module per paper table.

Prints ``name,us_per_call,derived`` CSV; ``--json PATH`` additionally
writes the machine-readable payload CI's bench-smoke lane gates on
(see benchmarks/check_regression.py):

    PYTHONPATH=src python -m benchmarks.run [--json out.json]
        [--metrics metrics.json] [table ...]

JSON schema (version 1): environment fields (jax version, backend, device
count), a ``config_digest`` identifying the run configuration, a
``calibration_us`` machine-speed yardstick, the ``results`` rows — exactly
the CSV rows as objects (plus optional per-row annotation fields, e.g. the
roofline fields on kernel rows) — and an ``obs_snapshot`` of the
observability registry at end of run.

The harness runs with observability enabled (``repro.obs``), so the
instrumented production stack (plan cache, tuner, engines, serving)
populates the registry as tables execute.  ``--metrics PATH`` writes that
snapshot (plus the recorded span trace in Chrome-trace form) standalone —
the METRICS_CI.json artifact CI uploads and gates with
``check_regression.py --metrics``.  Note tables that reset the registry
for their own bookkeeping (table13 resets per arrival rate) bound what the
end-of-run snapshot accumulates; the gated warm-cache gauges are set after
every table has run.
"""
import argparse
import hashlib
import json
import os
import sys
import time

import jax

from benchmarks import common
from repro import obs
from repro.compile_cache import enable_compile_cache
from benchmarks import (table2_restructuring, table3_partitioning,
                        table4_opt_combos, table5_scaling,
                        table8_kernel_ladder, table9_param_sweep,
                        table10_end2end, table11_batched, table12_formats,
                        table13_service, table14_shard_scaling,
                        table15_tuning, table16_coldstart, table17_science)

TABLES = {
    "table2": table2_restructuring,
    "table3": table3_partitioning,
    "table4": table4_opt_combos,
    "table5": table5_scaling,
    "table8": table8_kernel_ladder,   # covers paper tables 6-8
    "table9": table9_param_sweep,
    "table10": table10_end2end,
    "table11": table11_batched,       # beyond-paper: multi-subject batching
    "table12": table12_formats,       # beyond-paper: Phi format comparison
    "table13": table13_service,       # beyond-paper: serving under open-loop load
    "table14": table14_shard_scaling, # beyond-paper: sharded subjects/sec scaling
    "table15": table15_tuning,        # beyond-paper: tuned vs frozen kernel params
    "table16": table16_coldstart,     # beyond-paper: learned zero-measurement cold start
    "table17": table17_science,       # beyond-paper: warm-started science workloads
}

SCHEMA_VERSION = 1


def config_digest(wanted) -> str:
    """Informational identity of the run configuration (not its
    measurements): the table set, the software/platform, and the
    timing-protocol env overrides.  Shown by check_regression.py so a
    surprising gate result can be traced to a configuration difference at
    a glance; the gate's own comparability check is the ``tables`` field
    (baseline tables must all be present in the new run)."""
    h = hashlib.sha256()
    h.update(("|".join(sorted(wanted))).encode())
    h.update(jax.__version__.encode())
    h.update(jax.default_backend().encode())
    h.update(str(len(jax.devices())).encode())
    for var in ("REPRO_BENCH_WARMUP", "REPRO_BENCH_REPEATS"):
        h.update(f"{var}={os.environ.get(var, '')}".encode())
    return h.hexdigest()[:16]


def warm_cache_probe() -> None:
    """Exercise the plan cache's warm path and pin the result in gauges.

    Builds the Pallas-kernel engine twice against one fresh on-disk cache:
    the first build misses and persists its tile plans, the second — read
    through a brand-new PlanCache handle so no in-memory state helps —
    must replay every plan from disk.  Gauges ``plan_cache.warm.hit_rate``
    (CI gates this == 1.0 via ``check_regression.py --metrics``) and
    ``plan_cache.warm.lookups`` record the outcome."""
    import tempfile

    from repro.core.life import LifeConfig, LifeEngine
    from repro.core.plan_cache import PlanCache

    p = common.problem(scale="small")
    with tempfile.TemporaryDirectory() as d:
        cfg = LifeConfig(executor="kernel", plan_cache_dir=d)
        LifeEngine(p, cfg)                       # cold build: miss + persist
        warm = PlanCache(d)
        LifeEngine(p, cfg, warm)                 # warm build: hits only
        obs.gauge("plan_cache.warm.hit_rate").set(warm.stats.hit_rate)
        obs.gauge("plan_cache.warm.lookups").set(float(warm.stats.lookups))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="Run benchmark tables; CSV to stdout, optional JSON.")
    ap.add_argument("tables", nargs="*", metavar="table",
                    help=f"subset to run (default: all of {sorted(TABLES)})")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write machine-readable results to PATH")
    ap.add_argument("--metrics", metavar="PATH", default=None,
                    help="write the observability snapshot + span trace "
                         "to PATH (the METRICS_CI.json artifact)")
    args = ap.parse_args(argv)
    unknown = [t for t in args.tables if t not in TABLES]
    if unknown:
        ap.error(f"unknown tables {unknown}; choose from {sorted(TABLES)}")
    wanted = args.tables or list(TABLES)

    print(f"# compile cache: {enable_compile_cache()}", file=sys.stderr)
    common.reset_results()
    obs.enable()
    obs.reset()
    print("name,us_per_call,derived")
    for name in wanted:
        t0 = time.time()
        TABLES[name].run()
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)

    warm_cache_probe()
    # re-pin table16's zero-measurement invariant gauge after every table:
    # table13 resets the registry per arrival-rate scenario, which would
    # otherwise wipe a gauge set mid-run (no-op when table16 did not run)
    table16_coldstart.set_gauges()
    snap = obs.snapshot()

    if args.json:
        payload = dict(
            schema=SCHEMA_VERSION,
            jax_version=jax.__version__,
            backend=jax.default_backend(),
            device_count=len(jax.devices()),
            tables=sorted(wanted),
            config_digest=config_digest(wanted),
            calibration_us=common.calibration_us(),
            results=common.RESULTS,
            obs_snapshot=snap,
        )
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"# wrote {len(common.RESULTS)} rows to {args.json}",
              file=sys.stderr)

    if args.metrics:
        metrics = dict(snap, trace_events=obs.TRACER.export_chrome())
        with open(args.metrics, "w") as f:
            json.dump(metrics, f, indent=2)
            f.write("\n")
        print(f"# wrote observability snapshot to {args.metrics}",
              file=sys.stderr)


if __name__ == "__main__":
    main()
