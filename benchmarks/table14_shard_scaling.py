"""Beyond-paper: sharded-serving throughput vs device count (DESIGN.md §9).

Subjects/sec through the format-aware sharded executors (`shard` over inner
COO cells, `shard-sell` over per-cell SELL tiles) on 1/2/4/8-device meshes
in this process, over the devices that exist (one process owns the chip).
A topology that needs more devices than exist is skipped with a printed
line; one that fails fails the run.  The derived column also reports the
per-cell padding overhead — the quantity the equal-nnz partition and the
per-cell layout trade against each other.
"""
import time

from benchmarks.common import emit, fitting_meshes
from repro.core.life import LifeConfig, LifeEngine
from repro.data.dmri import synth_cohort

REPEATS = 3


def run():
    cohort = synth_cohort(1, base_seed=7, n_fibers=256, n_theta=32,
                          n_atoms=32, grid=(12, 12, 12))
    for n, (R, C), _ in fitting_meshes("table14"):
        for name, fmt in (("shard", "coo"), ("shard-sell", "sell")):
            cfg = LifeConfig(executor=name, format=fmt, shard_rows=R,
                             shard_cols=C, n_iters=10, slot_tile=16,
                             plan_cache_dir="")
            # one engine per topology: time the sharded *solve*, not
            # per-engine trace/compile + host encoding (those are amortized
            # by the plan cache and jit cache in a serving deployment)
            eng = LifeEngine(cohort[0], cfg)
            eng.run(2)                          # compile both SpMV closures
            t0 = time.perf_counter()
            for _ in range(REPEATS):
                eng.run()                       # one full subject solve
            sps = REPEATS / (time.perf_counter() - t0)
            sp = eng.executor.plans["shard_dsc"]
            emit(f"table14.{name}.devices{n}", 1e6 / sps,
                 f"subjects_per_sec={sps:.3f};"
                 f"padding_overhead={sp.padding_overhead:.2f}")


if __name__ == "__main__":
    run()
