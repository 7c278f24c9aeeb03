import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST stay the first two lines — jax locks the device count on first init,
#   and the production meshes need 512 placeholder devices.
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Do not import this module from tests/benchmarks (they want 1 device); it is
a CLI:

  PYTHONPATH=src python -m repro.launch.dryrun --mesh pod --arch deepseek-7b \
      --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all          # full sweep

Per cell it records into results/dryrun/<mesh>/<arch>__<shape>.json:
  memory_analysis (bytes per device), cost_analysis (flops/bytes),
  per-collective bytes from the post-SPMD HLO, the three roofline terms and
  the dominant bottleneck.  Failures (sharding mismatch, OOM-at-compile,
  unsupported collective) are bugs — the sweep fails loudly.
"""
import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (ARCH_IDS, SHAPES, ArchConfig, get_config,
                                input_specs)
from repro.distributed import hints
from repro.distributed import sharding as SH
from repro.launch.mesh import make_production_mesh
from repro.launch import steps as ST
from repro.optim.adamw import OptConfig
from repro.roofline import analysis as RL
from repro.roofline import hlo_cost as HC

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def opt_for(cfg: ArchConfig) -> OptConfig:
    # the 1T MoE trains with factored moments (DESIGN.md §5)
    kind = "adafactor" if cfg.param_count() > SH.FSDP_PARAM_THRESHOLD else "adamw"
    return OptConfig(kind=kind)


def lower_cell(arch: str, shape: str, mesh, *,
               variant: str = "base") -> Dict[str, Any]:
    """Lower+compile one cell; returns the record dict."""
    if arch.startswith("life-stn96"):
        return _lower_life(mesh, shape,
                           variant="1d" if arch.endswith("-1d") else "2d")
    cfg = get_config(arch)
    if not cfg.supports(shape):
        return {"status": "skipped",
                "reason": "full-attention arch at 500k context "
                          "(DESIGN.md §4)"}
    seq, batch, kind = SHAPES[shape]
    opt = opt_for(cfg)
    n_chips = mesh.devices.size
    hints.activate(mesh)

    t0 = time.time()
    state_sds = ST.abstract_state(cfg, opt)
    params_sds, opt_sds = state_sds
    pspecs = SH.param_specs(cfg, mesh, params_sds)
    ospecs = SH.opt_state_specs(cfg, mesh, opt_sds)
    bspecs = SH.batch_specs(cfg, mesh, shape)
    psh = SH.logical_to_shardings(mesh, pspecs)
    osh = SH.logical_to_shardings(mesh, ospecs)
    bsh = SH.logical_to_shardings(mesh, bspecs)
    batch_sds = input_specs(cfg, shape)

    with_sh = lambda sds, sh: jax.tree.map(
        lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
        sds, sh)

    if kind == "train":
        fn = ST.make_train_step(cfg, opt, grad_specs=psh)
        args = (with_sh(params_sds, psh), with_sh(opt_sds, osh),
                with_sh(batch_sds, bsh))
        jitted = jax.jit(fn, out_shardings=(psh, osh, None))
    elif kind == "prefill":
        fn = ST.make_prefill(cfg)
        args = (with_sh(params_sds, psh), with_sh(batch_sds, bsh))
        jitted = jax.jit(fn)
    else:  # decode
        fn = ST.make_serve_step(cfg)
        cache_sh = bsh["cache"]
        out_cache_sh = dict(cache_sh)
        out_cache_sh["index"] = SH.logical_to_shardings(
            mesh, jax.sharding.PartitionSpec())
        args = (with_sh(params_sds, psh), with_sh(batch_sds, bsh))
        jitted = jax.jit(fn, out_shardings=(None, out_cache_sh))

    with mesh:
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    # trip-count-corrected cost model (cost_analysis counts while bodies once)
    hc = HC.analyze(hlo, n_chips)
    mf = RL.model_flops(cfg, shape, seq, batch, kind)
    # the placeholder devices stand in for a v5e pod
    r = RL.roofline(hc.flops, hc.bytes_accessed, hc.collective_total,
                    n_chips, mf, device_kind=RL.V5E)

    return {
        "status": "ok",
        "arch": arch, "shape": shape, "variant": variant,
        "mesh": dict(shape=dict(mesh.shape), n_chips=int(n_chips)),
        "kind": kind,
        "compile_seconds": round(t_compile, 1),
        "memory": _mem_dict(mem),
        "xla_cost_raw": {k: cost[k] for k in ("flops", "bytes accessed")
                         if k in cost},
        "collectives": dict(hc.collective, total=hc.collective_total),
        "loop_multipliers": {k: v for k, v in sorted(
            hc.loops.items(), key=lambda kv: -kv[1])[:8]},
        "roofline": r.as_dict(),
        "mfu_upper_bound": RL.mfu_fraction(r, n_chips, kind),
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }


def _lower_life(mesh, shape: str, variant: str = "2d") -> Dict[str, Any]:
    """The paper's own workload: distributed SBBNNLS iteration at Table-9
    scale.  `shape` selects the connectome size; `variant` selects the 2-D
    (voxel x fiber) partition vs the paper-faithful 1-D coefficient
    partition (MPI-LiFE analogue) used as the §Perf baseline."""
    from repro.distributed import life_shard as LS
    scales = {
        "train_4k": dict(n_fibers=500_000, nnz=400_000_000),   # iFOD1 500k
        "prefill_32k": dict(n_fibers=250_000, nnz=190_000_000),
        "decode_32k": dict(n_fibers=100_000, nnz=100_000_000),
        "long_500k": dict(n_fibers=50_000, nnz=50_000_000),
    }
    sc = scales[shape]
    n_chips = mesh.devices.size
    t0 = time.time()
    if variant == "1d":
        specs = LS.life_input_specs_1d(mesh, **sc)
        meta = specs.pop("meta")
        step = LS.make_sharded_step_1d(mesh, meta)
        jitted = jax.jit(step)
        with mesh:
            lowered = jitted.lower(
                specs["a"], specs["v"], specs["fi"], specs["vals"],
                specs["d"], specs["b"], specs["w"], specs["it"])
            compiled = lowered.compile()
    else:
        specs = LS.life_input_specs(mesh, **sc)
        meta = specs.pop("meta")
        step = LS.make_sharded_step(mesh, meta)
        jitted = jax.jit(step)
        with mesh:
            lowered = jitted.lower(
                specs["da"], specs["dv"], specs["df"], specs["dw"],
                specs["wa"], specs["wv"], specs["wf"], specs["ww"],
                specs["d"], specs["b"], specs["w"], specs["it"])
            compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hc = HC.analyze(compiled.as_text(), n_chips)
    # useful flops: 2 ops/nnz/theta x (2 DSC + 1.5 WC avg -> here 3 spmv + dots)
    n_theta = meta["n_theta"]
    mf = 3.5 * 2.0 * sc["nnz"] * n_theta
    # the placeholder devices stand in for a v5e pod
    r = RL.roofline(hc.flops, hc.bytes_accessed, hc.collective_total,
                    n_chips, mf, device_kind=RL.V5E)
    return {
        "status": "ok", "arch": "life-stn96" + ("-1d" if variant == "1d" else ""),
        "shape": shape, "variant": variant,
        "mesh": dict(shape=dict(mesh.shape), n_chips=int(n_chips)),
        "kind": "sbbnnls", "compile_seconds": round(t_compile, 1),
        "memory": _mem_dict(mem),
        "xla_cost_raw": {k: cost[k] for k in ("flops", "bytes accessed")
                         if k in cost},
        "collectives": dict(hc.collective, total=hc.collective_total),
        "roofline": r.as_dict(),
        "scale": sc,
    }


def _mem_dict(mem) -> Dict[str, float]:
    keys = ("temp_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    out = {}
    for k in keys:
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = float(v)
    if out:
        out["total_bytes_per_device"] = (
            out.get("temp_size_in_bytes", 0)
            + out.get("argument_size_in_bytes", 0))
    else:
        out["repr"] = str(mem)
    return out


def run_cell(arch: str, shape: str, mesh_kind: str,
             out_dir: Optional[str] = None) -> Dict[str, Any]:
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    try:
        rec = lower_cell(arch, shape, mesh)
    except Exception as e:  # noqa: BLE001 — recorded, sweep continues
        rec = {"status": "error", "arch": arch, "shape": shape,
               "error": repr(e), "traceback": traceback.format_exc()}
    rec["mesh_kind"] = mesh_kind
    out_dir = out_dir or RESULTS_DIR
    d = os.path.join(out_dir, mesh_kind)
    os.makedirs(d, exist_ok=True)
    fname = os.path.join(d, f"{arch}__{shape}.json")
    with open(fname, "w") as f:
        json.dump(rec, f, indent=2, default=float)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cells = []
    archs = ARCH_IDS if (args.all or args.arch is None) else (args.arch,)
    shapes = tuple(SHAPES) if (args.all or args.shape is None) else (args.shape,)
    meshes = ("pod", "multipod") if args.all else (args.mesh,)
    for mk in meshes:
        for a in archs:
            for s in shapes:
                cells.append((a, s, mk))

    failures = 0
    for a, s, mk in cells:
        t0 = time.time()
        rec = run_cell(a, s, mk, args.out)
        dt = time.time() - t0
        status = rec["status"]
        extra = ""
        if status == "ok":
            r = rec.get("roofline", {})
            extra = (f" dominant={r.get('dominant')}"
                     f" bound={r.get('bound_s', 0):.4f}s"
                     f" mem={rec['memory'].get('total_bytes_per_device', 0)/1e9:.2f}GB")
        elif status == "error":
            failures += 1
            extra = " " + rec["error"][:120]
        print(f"[{mk}] {a:24s} {s:12s} {status:7s} {dt:6.1f}s{extra}",
              flush=True)
        if status == "ok":
            ma = rec["memory"]
            r = rec["roofline"]
            print(f"    memory_analysis: {json.dumps(ma)}", flush=True)
            print(f"    corrected cost: flops/chip={r['flops_per_chip']:.3e}"
                  f" bytes/chip={r['bytes_per_chip']:.3e}"
                  f" coll_bytes/chip={r['coll_bytes_per_chip']:.3e}"
                  f" useful={r['useful_ratio']:.2f}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
