#!/usr/bin/env python3
"""Bring-up smoke run of the LiFE solve on TPU chips.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the 2x2 mesh path on four chips

The subject sits at the lower end of the paper's STN96 range: 50,000
candidate fibers from PROB tractography over a 52^3 voxel grid, with
Ntheta = Na = 96 (about 1.0e6 coefficients).  Weights and data are random,
made from ``--seed``.

One chip: every one-chip executor x format pair the registry declares, plus
``format="auto"``, is checked against the reference SpMV
(``spmv.dsc_naive`` / ``spmv.wc_naive``) on the chip — fp32, and bf16 for
the Pallas pairs, whose compiled programs must contain ``tpu_custom_call``.
Then a few jobs go through ``LifeFrontend`` under the default ``opt``
executor and under ``format="sell"``, ``"fcoo"`` and ``"auto"``; each must
complete with falling losses and weights that match a direct
``LifeEngine.run``.

Four chips: the mesh executors ``shard`` / ``shard-sell`` at ``mesh=(2, 2)``
against the reference SpMV, the per-device bytes of their cells, and mesh
jobs through ``LifeFrontend``.

Each phase prints one line: compile and run seconds, the largest error
relative to the reference's largest magnitude, and the device's peak bytes
in use.  The last line of stdout is ``{"ok": true, "device": {...}}``.  The
script exits non-zero without that line when JAX finds no TPU, when it runs
without the repository's ``src/``, or when any phase fails.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
import traceback
from pathlib import Path

N_FIBERS = 50_000
GRID = (52, 52, 52)
N_THETA = N_ATOMS = 96
PALLAS = ("kernel", "kernel-sell", "kernel-fcoo")
#: fp32 tolerance of the conformance matrix (tests/test_conformance.py)
FP32_RTOL = 2e-4
#: weights of two solves through different programs: the conformance
#: matrix's end-to-end tolerance
SOLVE_RTOL = 2e-2
JOBS, JOB_ITERS, SLICE_ITERS = 3, 50, 25


def say(tag: str, **fields) -> None:
    parts = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{tag}] {parts}", flush=True)


def rel_err(got, ref) -> float:
    """Largest deviation relative to the reference's largest magnitude."""
    import numpy as np
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


class Smoke:
    """Runs the phases, recording failures instead of stopping at the first
    (one chip call then reports every broken pair)."""

    def __init__(self, devices):
        self.devices = devices
        self.failures = []

    @staticmethod
    def mem(device, key: str) -> int:
        return device.memory_stats()[key]

    def peak(self) -> int:
        return self.mem(self.devices[0], "peak_bytes_in_use")

    def check(self, ok: bool, what: str) -> None:
        if not ok:
            self.failures.append(what)
            say("FAIL", what=what)

    def phase(self, name: str, fn, *args) -> None:
        try:
            fn(*args)
        except Exception:                    # report it, run the next phase
            traceback.print_exc()
            self.failures.append(f"{name}: {traceback.format_exc(limit=1)}")
            say("FAIL", phase=name)
        gc.collect()

    # -- phases --------------------------------------------------------------
    def build(self, seed: int) -> None:
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.core import spmv
        from repro.data.dmri import synth_connectome

        t0 = time.perf_counter()
        self.problem = p = synth_connectome(
            n_fibers=N_FIBERS, n_theta=N_THETA, n_atoms=N_ATOMS, grid=GRID,
            algorithm="PROB", seed=seed)
        say("subject", host_s=f"{time.perf_counter() - t0:.1f}",
            n_voxels=p.phi.n_voxels, n_fibers=p.phi.n_fibers,
            n_coeffs=p.phi.n_coeffs, n_theta=N_THETA, n_atoms=N_ATOMS)
        rng = np.random.default_rng(seed + 1)
        self.w = jnp.asarray(rng.uniform(size=p.phi.n_fibers), jnp.float32)
        self.y = jnp.asarray(rng.normal(size=(p.phi.n_voxels, N_THETA)),
                             jnp.float32)
        t0 = time.perf_counter()
        self.y_ref = spmv.dsc_naive(p.phi, p.dictionary, self.w)
        self.w_ref = spmv.wc_naive(p.phi, p.dictionary, self.y)
        jax.block_until_ready((self.y_ref, self.w_ref))
        say("reference", seconds=f"{time.perf_counter() - t0:.2f}",
            peak_bytes=self.peak())

    def pair(self, label: str, executor, rtol: float, kernel: bool) -> None:
        """Compile and run one executor's SpMV pair against the reference."""
        import jax
        apply = jax.jit(lambda fn, x: fn(x))
        fields = {}
        for op, fn, x, ref in (("mv", executor.matvec, self.w, self.y_ref),
                               ("rmv", executor.rmatvec, self.y, self.w_ref)):
            t0 = time.perf_counter()
            compiled = apply.lower(fn, x).compile()
            fields[f"{op}_compile_s"] = f"{time.perf_counter() - t0:.2f}"
            times = []
            for _ in range(3):
                t0 = time.perf_counter()
                out = jax.block_until_ready(compiled(fn, x))
                times.append(time.perf_counter() - t0)
            fields[f"{op}_run_s"] = f"{sorted(times)[1]:.5f}"
            err = rel_err(out, ref)
            fields[f"{op}_err"] = f"{err:.3e}"
            self.check(err <= rtol, f"{label} {op} err {err:.3e} > {rtol}")
            if kernel:
                has = "tpu_custom_call" in compiled.as_text()
                fields[f"{op}_custom_call"] = has
                self.check(has, f"{label} {op}: no tpu_custom_call")
        say("pair", pair=label, ran=executor.name, **fields,
            peak_bytes=self.peak())

    def pairs(self) -> None:
        """Every one-chip executor x format pair, plus format="auto"."""
        from repro.core.registry import REGISTRY
        from repro.formats.base import format_names

        todo = [(ex, fmt) for fmt in format_names()
                for ex in REGISTRY.executors_for_format(fmt)
                if ex != REGISTRY.mesh_executor_for(fmt)]
        missing = {("kernel", "coo"), ("kernel-sell", "sell"),
                   ("kernel-fcoo", "fcoo")} - set(todo)
        self.check(not missing, f"Pallas pairs not registered: {missing}")
        for ex, fmt in todo + [("opt", "auto")]:
            for cd in ("fp32", "bf16") if ex in PALLAS else ("fp32",):
                label = f"{ex if fmt != 'auto' else 'selected'}/{fmt}/{cd}"
                self.phase(label, self.one_pair, label, ex, fmt, cd)

    def one_pair(self, label: str, ex: str, fmt: str, cd: str) -> None:
        from repro.core.life import LifeConfig
        from repro.core.registry import create_for_format
        from repro.tune.plan import BF16_RTOL

        cfg = LifeConfig(executor=ex, format=fmt, compute_dtype=cd,
                         plan_cache_dir="")
        t0 = time.perf_counter()
        executor = create_for_format(self.problem.phi, self.problem, cfg)
        say("build", pair=label, host_s=f"{time.perf_counter() - t0:.2f}")
        self.pair(label, executor, BF16_RTOL if cd == "bf16" else FP32_RTOL,
                  kernel=executor.name in PALLAS)

    def serve(self, fmt: str, mesh=None) -> None:
        """JOBS jobs through LifeFrontend vs a direct LifeEngine.run."""
        import numpy as np
        from repro.core.life import LifeConfig, LifeEngine
        from repro.serve.frontend import LifeFrontend

        label = f"{fmt}" + ("" if mesh is None else f"@{mesh[0]}x{mesh[1]}")
        fe = LifeFrontend(LifeConfig(plan_cache_dir=""), start=False,
                          slice_iters=SLICE_ITERS)
        handles = [fe.submit_async(self.problem, n_iters=JOB_ITERS,
                                   format=fmt, mesh=mesh)
                   for _ in range(JOBS)]
        t0 = time.perf_counter()
        fe.start()
        try:
            results = [h.result(timeout=900) for h in handles]
        finally:
            fe.shutdown(drain=False)
        serve_s = time.perf_counter() - t0

        rows, cols = (1, 1) if mesh is None else mesh
        cfg = LifeConfig(format=fmt, n_iters=JOB_ITERS, plan_cache_dir="",
                         shard_rows=rows, shard_cols=cols)
        t0 = time.perf_counter()
        engine = LifeEngine(self.problem, cfg)
        w_direct, l_direct = engine.run()
        direct_s = time.perf_counter() - t0
        errs = [rel_err(w, w_direct) for w, _ in results]
        falls = [bool(ls[-1] < ls[0]) for _, ls in results]
        statuses = [h.status() for h in handles]
        self.check(statuses == ["done"] * JOBS,
                   f"serve {label}: statuses {statuses}")
        self.check(all(falls), f"serve {label}: losses did not fall")
        self.check(max(errs) <= SOLVE_RTOL,
                   f"serve {label}: weights off direct run by {max(errs):.3e}")
        say("serve", format=label, jobs=JOBS, iters=JOB_ITERS,
            completed=sum(s == "done" for s in statuses),
            engine=engine.executor.name, serve_s=f"{serve_s:.1f}",
            direct_s=f"{direct_s:.1f}",
            loss_first=f"{float(np.asarray(l_direct)[0]):.6e}",
            loss_last=f"{float(np.asarray(l_direct)[-1]):.6e}",
            max_w_err=f"{max(errs):.3e}", peak_bytes=self.peak())

    def mesh_pairs(self) -> None:
        """shard / shard-sell at mesh=(2, 2) vs the reference SpMV."""
        from repro.core.life import LifeConfig
        from repro.core.registry import create_for_format

        for ex, fmt in (("shard", "coo"), ("shard-sell", "sell")):
            label = f"{ex}/{fmt}/2x2"
            cfg = LifeConfig(executor=ex, format=fmt, shard_rows=2,
                             shard_cols=2, plan_cache_dir="")
            t0 = time.perf_counter()
            executor = create_for_format(self.problem.phi, self.problem, cfg)
            say("build", pair=label, host_s=f"{time.perf_counter() - t0:.2f}")
            cells = executor.matvec.args[0]["cell"] + \
                executor.rmatvec.args[0]["cell"]
            per_dev = {d.id: 0 for d in self.devices}
            for leaf in cells:
                for shard in leaf.addressable_shards:
                    per_dev[shard.device.id] += shard.data.nbytes
            total = sum(leaf.nbytes for leaf in cells)
            say("cells", pair=label, total_bytes=total,
                per_device_cell_bytes=per_dev,
                bytes_in_use={d.id: self.mem(d, "bytes_in_use")
                              for d in self.devices})
            self.check(max(per_dev.values()) < total,
                       f"{label}: some device holds every cell")
            self.pair(label, executor, FP32_RTOL,
                      kernel=ex == "shard-sell")
            del executor, cells
            gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} chips; JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import enable_compile_cache

    say("device", platform=devices[0].platform,
        kind=repr(devices[0].device_kind), count=len(devices),
        compile_cache=enable_compile_cache())
    smoke = Smoke(devices)
    smoke.phase("subject", smoke.build, args.seed)
    if smoke.failures:
        return 1
    if args.chips == 4:
        smoke.phase("mesh pairs", smoke.mesh_pairs)
        for fmt in ("coo", "sell"):
            smoke.phase(f"serve {fmt}@2x2", smoke.serve, fmt, (2, 2))
    else:
        smoke.pairs()
        for fmt in ("coo", "sell", "fcoo", "auto"):
            smoke.phase(f"serve {fmt}", smoke.serve, fmt)
    if smoke.failures:
        print(f"chip_smoke: {len(smoke.failures)} failure(s):",
              file=sys.stderr)
        for f in smoke.failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
