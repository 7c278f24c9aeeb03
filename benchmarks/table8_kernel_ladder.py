"""Paper Tables 6-8: the accelerator-kernel optimization ladder.

GPU version ladder (Ref-opt -> +restructure -> +partition -> GPU-opt) mapped
to this framework's executors:

  naive          scatter/gather translation (Ref-opt analogue)
  restructured   per-op output-side sorts (target-independent opts)
  segment        sorted segment reduction (sync-free partitioning)
  kernel         Pallas executor (compiled on a TPU; interpreted elsewhere,
                 where wall time is NOT meaningful — the derived column also
                 reports the roofline-modeled v5e time from the tile plan)

Derived: speedup vs naive (JAX rows) / modeled v5e microseconds (kernel row).
"""
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, problem, roofline_fields, time_fn
from repro.core import spmv
from repro.core.inspector import auto_tile, plan_tiles
from repro.core.restructure import sort_by_host
from repro.kernels import ops as kops
from repro.roofline.analysis import V5E, peaks


def _kernel_model_us(plan, n_theta_padded, d_bytes=4):
    """Roofline model of the DSC kernel on one v5e core: bytes streamed
    (coefficient tiles + output blocks) / HBM bw vs MXU time."""
    hw = peaks(V5E)
    tiles = plan.n_tiles
    c, r = plan.c_tile, plan.row_tile
    bytes_in = tiles * c * (3 * 4 + d_bytes)              # idx + scaled
    bytes_out = tiles * r * n_theta_padded * d_bytes * 2  # rmw of blocks
    t_mem = (bytes_in + bytes_out) / hw["hbm_bw"]
    flops = tiles * (r * c * n_theta_padded * 2           # one-hot matmul
                     + c * n_theta_padded * 2)            # scale
    t_compute = flops / hw["peak_flops"]
    return max(t_mem, t_compute) * 1e6


def run():
    p = problem()
    w = jnp.ones((p.phi.n_fibers,), jnp.float32)
    y = p.b
    phi_v, _ = sort_by_host(p.phi, "voxel")
    phi_f, _ = sort_by_host(p.phi, "fiber")

    t0_dsc = time_fn(spmv.dsc_naive, p.phi, p.dictionary, w)
    t1_dsc = time_fn(spmv.dsc_atom_sorted, phi_v, p.dictionary, w)
    t2_dsc = time_fn(spmv.dsc, phi_v, p.dictionary, w)
    emit("table8.dsc.naive", t0_dsc, "1.00x")
    emit("table8.dsc.restructured", t1_dsc, f"{t0_dsc / t1_dsc:.2f}x")
    emit("table8.dsc.segment", t2_dsc, f"{t0_dsc / t2_dsc:.2f}x",
         **roofline_fields(lambda w_: spmv.dsc(phi_v, p.dictionary, w_),
                           t2_dsc, w))

    ct, rt = auto_tile(np.asarray(phi_v.voxels), p.phi.n_voxels)
    plan = plan_tiles(np.asarray(phi_v.voxels), p.phi.n_voxels,
                      c_tile=ct, row_tile=rt)
    mv = kops.make_dsc(phi_v, p.dictionary, plan)
    t3 = time_fn(mv, w, warmup=1, repeats=2)
    emit("table8.dsc.kernel", t3,
         f"modeled_v5e_us={_kernel_model_us(plan, 128):.1f}"
         f";occupancy={plan.occupancy():.2f}",
         **roofline_fields(mv, t3, w))

    t0_wc = time_fn(spmv.wc_naive, p.phi, p.dictionary, y)
    t1_wc = time_fn(spmv.wc_atom_sorted, phi_f, p.dictionary, y)
    t2_wc = time_fn(spmv.wc, phi_f, p.dictionary, y)
    emit("table8.wc.naive", t0_wc, "1.00x")
    emit("table8.wc.restructured", t1_wc, f"{t0_wc / t1_wc:.2f}x")
    emit("table8.wc.segment", t2_wc, f"{t0_wc / t2_wc:.2f}x",
         **roofline_fields(lambda y_: spmv.wc(phi_f, p.dictionary, y_),
                           t2_wc, y))
    ct, rt = auto_tile(np.asarray(phi_f.fibers), p.phi.n_fibers)
    wc_plan = plan_tiles(np.asarray(phi_f.fibers), p.phi.n_fibers,
                         c_tile=ct, row_tile=rt)
    rv = kops.make_wc(phi_f, p.dictionary, wc_plan)
    t4 = time_fn(rv, y, warmup=1, repeats=2)
    emit("table8.wc.kernel", t4,
         f"occupancy={wc_plan.occupancy():.2f}",
         **roofline_fields(rv, t4, y))


if __name__ == "__main__":
    run()
