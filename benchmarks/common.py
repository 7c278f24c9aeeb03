"""Shared benchmark utilities: timing, CSV emission, JSON collection.

Every table prints ``name,us_per_call,derived`` rows (derived column holds
the table-specific metric: speedup, bytes, iterations/s, ...).  Rows are
also collected in :data:`RESULTS` so ``benchmarks/run.py --json`` can emit
the machine-readable trajectory CI gates on.

Timing protocol: ``warmup`` blocking calls (compile + cache warm), then the
**median** of ``repeats`` blocking calls — the median (not the mean) so one
scheduler hiccup can't poison a row the regression gate compares against.
``$REPRO_BENCH_WARMUP`` / ``$REPRO_BENCH_REPEATS`` override every call
site's own values, letting CI harden the gate lane (more repeats) without
touching per-table code.
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.obs import quantile

#: rows collected for --json: dicts of name / us_per_call / derived
RESULTS = []


def reset_results():
    RESULTS.clear()


def time_fn(fn, *args, warmup=2, repeats=5):
    """Median wall time of a blocking call, in microseconds."""
    warmup = int(os.environ.get("REPRO_BENCH_WARMUP", warmup))
    repeats = max(1, int(os.environ.get("REPRO_BENCH_REPEATS", repeats)))
    for _ in range(warmup):
        _block(fn(*args))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        _block(fn(*args))
        times.append((time.perf_counter() - t0) * 1e6)
    return quantile(times, 50.0)


def _block(out):
    for leaf in jax.tree.leaves(out):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()
    return out


def emit(name, us, derived="", **fields):
    """Record one row.  ``fields`` ride only in the JSON payload (e.g. the
    roofline annotations below); the printed CSV stays three columns."""
    row = dict(name=str(name), us_per_call=float(us), derived=str(derived))
    row.update(fields)
    RESULTS.append(row)
    print(f"{name},{us:.1f},{derived}")


def roofline_fields(fn, us, *args):
    """Roofline annotation fields for a timed jittable call.

    Lowers ``fn(*args)`` and runs the trip-count-aware HLO cost model over
    the compiled text, turning the measured microseconds into an
    achieved-HBM-bandwidth fraction against the running chip's roofline
    (``analysis.PEAKS``), plus the full Roofline term breakdown.  Returns
    ``{}`` on a device with no published peaks (a CPU run has no roofline
    share)."""
    from repro.roofline import hlo_cost
    from repro.roofline.analysis import PEAKS, roofline
    kind = jax.devices()[0].device_kind
    if kind not in PEAKS:
        return {}
    txt = jax.jit(fn).lower(*args).compile().as_text()
    cost = hlo_cost.analyze(txt, n_chips=1)
    secs = us / 1e6
    achieved = cost.bytes_accessed / secs if secs > 0 else 0.0
    r = roofline(cost.flops, cost.bytes_accessed, 0.0, 1, cost.flops,
                 device_kind=kind)
    return dict(bytes_accessed=cost.bytes_accessed,
                achieved_gbps=achieved / 1e9,
                roofline_frac=achieved / PEAKS[kind]["hbm_bw"],
                roofline=r.as_dict())


def calibration_us():
    """Median time of a fixed Pallas SELL kernel call (compiled on a TPU,
    interpreted elsewhere) — the machine-speed yardstick recorded in the
    JSON payload.

    ``check_regression.py`` rescales a baseline captured on different
    hardware by the calibration ratio before applying its slowdown factor
    (an absolute 2x gate across unknown CI machine generations would
    otherwise be pure noise).  The yardstick is deliberately the same cost
    family as the gated rows — interpret-mode kernel dispatch — because a
    plain XLA matmul does not track it: machines with identical matmul
    throughput can differ 2x in dispatch overhead."""
    import jax.numpy as jnp
    from repro.kernels.dsc import dsc_sell_pallas
    from repro.kernels.ops import interpret_mode
    atoms = jnp.zeros((32, 128), jnp.int32)      # slot-major (width, rows)
    scaled = jnp.ones((32, 128), jnp.float32)
    d = jnp.ones((32, 128), jnp.float32)
    f = jax.jit(lambda a, s: dsc_sell_pallas(a, s, d, slot_tile=16,
                                             interpret=interpret_mode()))
    return time_fn(f, atoms, scaled, warmup=2, repeats=5)


def fitting_meshes(table, topologies=((1, 1), (2, 1), (2, 2), (4, 2))):
    """``(n_devices, (R, C), mesh)`` for each (data, model) topology that
    fits the devices of this process; prints a line for each one that does
    not."""
    import numpy as np
    from jax.sharding import AxisType, Mesh
    have = jax.devices()
    for R, C in topologies:
        n = R * C
        if n > len(have):
            print(f"# {table}: skip {R}x{C} mesh: needs {n} devices, "
                  f"have {len(have)}", file=sys.stderr)
            continue
        mesh = Mesh(np.asarray(have[:n]).reshape(R, C), ("data", "model"),
                    axis_types=(AxisType.Auto,) * 2)
        yield n, (R, C), mesh


def problem(scale="bench"):
    from repro.data.dmri import synth_connectome
    if scale == "bench":
        return synth_connectome(n_fibers=1024, n_theta=96, n_atoms=96,
                                grid=(20, 20, 20), algorithm="PROB", seed=5)
    return synth_connectome(n_fibers=128, n_theta=32, n_atoms=32,
                            grid=(10, 10, 10), seed=5)
