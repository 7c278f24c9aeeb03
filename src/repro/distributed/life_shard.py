"""Distributed LiFE: 2-D (voxel x fiber) mesh partition of SBBNNLS.

The paper's computation partitioning (§4.1.3) lifted from threads to the
device mesh (its MPI-LiFE comparison point, §7.1.3, rebuilt jax-native):

  * voxel ranges shard over the batch axes (`pod`,`data`) — R row groups,
  * fiber ranges shard over `model`                        — C col groups,
  * each device owns the Phi coefficients in its (voxel-range x fiber-range)
    cell, TWICE (voxel-sorted for DSC, fiber-sorted for WC — the per-op
    restructuring), with *localized* indices,
  * DSC: local sorted-segment-sum -> psum over `model`  (fiber reduction),
  * WC : local sorted-segment-sum -> psum over rows     (voxel reduction),
  * SBBNNLS dot products: local vdot + psum over the axis the operand is
    sharded on (w-like: `model`; y-like: rows).

Boundaries are equal-nnz and snapped to sub-vector boundaries
(inspector.shard_boundaries via formats/shard.py:partition_cuts) — the
synchronization-free mapping of §4.2.1.2 at mesh granularity; padding
coefficients carry value 0 and are inert through both ops and the solver.

Cell materialization goes through the PhiFormat subsystem (DESIGN.md §9):
:func:`build_life_shards` and the registry's ``shard``/``shard-sell``
executors encode each (voxel-range x fiber-range) cell with
``formats/shard.py:ShardPhi`` — inner sorted-COO cells for the segment-sum
path here, inner SELL tiles for :func:`make_sharded_sell_ops`.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.sbbnnls import projected_gradient
from repro.core.std import PhiTensor
from repro.data.dmri import LifeProblem

Array = jax.Array
# fp32 dot products at full precision: the TPU default rounds operands to bf16
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass
class LifeShards:
    """Host-side 2-D partition (R x C cells, padded to common sizes)."""
    # each (R, C, nnz_max) int32/float32; *_local indices are cell-relative
    dsc_atoms: np.ndarray
    dsc_voxels_local: np.ndarray
    dsc_fibers_local: np.ndarray
    dsc_values: np.ndarray
    wc_atoms: np.ndarray
    wc_voxels_local: np.ndarray
    wc_fibers_local: np.ndarray
    wc_values: np.ndarray
    nv_local: int
    nf_local: int
    n_theta: int
    R: int
    C: int
    voxel_cuts: np.ndarray      # (R+1,) global voxel boundaries
    fiber_cuts: np.ndarray      # (C+1,)


def build_life_shards(phi: PhiTensor, n_theta: int, R: int, C: int,
                      cache=None) -> LifeShards:
    """Materialize the 2-D partition through the format subsystem.

    Both per-op layouts (voxel-sorted for DSC, fiber-sorted for WC) are
    :class:`~repro.formats.shard.ShardPhi` encodes over inner COO cells —
    the partition boundaries come from one shared
    :func:`~repro.formats.shard.partition_cuts` plan (persistent-cache-backed
    when ``cache`` is given), so this function is now a thin adapter from
    the PhiFormat world to the historical LifeShards operand names.
    """
    from repro.formats.shard import encode_pair, partition_cuts

    plan = partition_cuts(phi, R, C, cell_format="coo", cache=cache)
    dsc, wc = encode_pair(phi, cell_format="coo", plan=plan)
    return LifeShards(
        dsc_atoms=dsc.arrays["atoms"], dsc_voxels_local=dsc.arrays["voxels"],
        dsc_fibers_local=dsc.arrays["fibers"], dsc_values=dsc.arrays["values"],
        wc_atoms=wc.arrays["atoms"], wc_voxels_local=wc.arrays["voxels"],
        wc_fibers_local=wc.arrays["fibers"], wc_values=wc.arrays["values"],
        nv_local=plan.nv_local, nf_local=plan.nf_local, n_theta=n_theta,
        R=R, C=C, voxel_cuts=plan.voxel_cuts, fiber_cuts=plan.fiber_cuts)


def shard_b(shards: LifeShards, b: np.ndarray) -> np.ndarray:
    """(Nv, Ntheta) -> (R * nv_local, Ntheta) row-padded layout."""
    out = np.zeros((shards.R * shards.nv_local, b.shape[1]), b.dtype)
    for r in range(shards.R):
        lo, hi = shards.voxel_cuts[r], shards.voxel_cuts[r + 1]
        out[r * shards.nv_local: r * shards.nv_local + (hi - lo)] = b[lo:hi]
    return out


def shard_w(shards: LifeShards, w: np.ndarray) -> np.ndarray:
    out = np.zeros((shards.C * shards.nf_local,), w.dtype)
    for c in range(shards.C):
        lo, hi = shards.fiber_cuts[c], shards.fiber_cuts[c + 1]
        out[c * shards.nf_local: c * shards.nf_local + (hi - lo)] = w[lo:hi]
    return out


def unshard_w(shards: LifeShards, w_padded: np.ndarray) -> np.ndarray:
    segs = []
    for c in range(shards.C):
        lo, hi = shards.fiber_cuts[c], shards.fiber_cuts[c + 1]
        segs.append(w_padded[c * shards.nf_local:
                             c * shards.nf_local + (hi - lo)])
    return np.concatenate(segs)


# ----------------------------------------------------------------------------
# shard_map SBBNNLS
# ----------------------------------------------------------------------------

def _row_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def make_sharded_step(mesh: Mesh, shards_meta: Dict[str, int],
                      use_reduce_scatter: bool = False):
    """Builds the jit-able distributed SBBNNLS iteration.

    shards_meta: dict(nv_local=, nf_local=, n_theta=).
    Inputs (global layouts):
      phi cell arrays: (R, C, nnz) sharded (rows, model, None)
      d:        (Na, Ntheta) replicated
      b:        (R*nv_local, Ntheta) sharded (rows, None)
      w:        (C*nf_local,) sharded (model,)
      it:       scalar int32
    Returns (w_new, loss) with the same shardings.
    """
    rows = _row_axes(mesh)
    nv_l = shards_meta["nv_local"]
    nf_l = shards_meta["nf_local"]

    cell = P(rows, "model", None)
    yspec = P(rows, None)
    wspec = P("model")

    def dsc_local(a, v, f, w_vals, d, w_loc):
        scaled = jnp.take(w_loc, f) * w_vals
        contrib = jnp.take(d, a, axis=0) * scaled[:, None]
        y = jax.ops.segment_sum(contrib, v, num_segments=nv_l,
                                indices_are_sorted=True)
        return jax.lax.psum(y, "model")

    def wc_local(a, v, f, w_vals, d, y_loc):
        dots = jnp.einsum("ct,ct->c", jnp.take(d, a, axis=0),
                          jnp.take(y_loc, v, axis=0), precision=HIGHEST)
        w = jax.ops.segment_sum(dots * w_vals, f, num_segments=nf_l,
                                indices_are_sorted=True)
        return jax.lax.psum(w, rows)

    def dot_y(x, y):
        return jax.lax.psum(jnp.vdot(x, y, precision=HIGHEST), rows)

    def dot_w(x, y):
        return jax.lax.psum(jnp.vdot(x, y, precision=HIGHEST), "model")

    def step(da, dv, df, dw, wa, wv, wf, ww, d, b_loc, w_loc, it):
        # squeeze the per-device cell dims
        sq = lambda x: x.reshape(x.shape[-1])
        da, dv, df, dw = map(sq, (da, dv, df, dw))
        wa, wv, wf, ww = map(sq, (wa, wv, wf, ww))
        w_loc = w_loc.reshape(-1)
        b2 = b_loc.reshape(b_loc.shape[-2], b_loc.shape[-1])

        y = dsc_local(da, dv, df, dw, d, w_loc) - b2          # DSC
        g = wc_local(wa, wv, wf, ww, d, y)                    # WC
        gt = projected_gradient(w_loc, g)
        v = dsc_local(da, dv, df, dw, d, gt)                  # DSC

        def odd(_):
            return _safe(dot_w(gt, gt), dot_y(v, v))

        def even(_):
            vv = wc_local(wa, wv, wf, ww, d, v)               # WC
            vv = projected_gradient(w_loc, vv)
            return _safe(dot_y(v, v), dot_w(vv, vv))

        alpha = jax.lax.cond(it % 2 == 1, odd, even, operand=None)
        w_new = jnp.maximum(w_loc - alpha * gt, 0.0)
        loss = 0.5 * dot_y(y, y)
        return w_new, loss

    specs_in = (cell, cell, cell, cell, cell, cell, cell, cell,
                P(None, None), yspec, wspec, P())
    specs_out = (P("model"), P())
    return jax.shard_map(step, mesh=mesh, in_specs=specs_in,
                         out_specs=specs_out, check_vma=False)


def make_sharded_ops(mesh: Mesh, shards_meta: Dict[str, int]):
    """Per-op shard_map'd SpMVs for the executor-registry `shard` path.

    Same cell layout and collectives as :func:`make_sharded_step`, but
    exposed as standalone DSC / WC closures so the registry can bind them to
    the single-process matvec/rmatvec protocol (the solver then runs
    undistributed while each SpMV fans out over the mesh).

    Returns (dsc_fn, wc_fn):
      dsc_fn(a, v, f, vals, d, w_padded)  -> (R*nv_local, Ntheta)
      wc_fn(a, v, f, vals, d, y_padded)   -> (C*nf_local,)
    """
    rows = _row_axes(mesh)
    nv_l = shards_meta["nv_local"]
    nf_l = shards_meta["nf_local"]
    cell = P(rows, "model", None)

    def dsc_op(a, v, f, vals, d, w_loc):
        sq = lambda x: x.reshape(x.shape[-1])
        a, v, f, vals = map(sq, (a, v, f, vals))
        scaled = jnp.take(w_loc.reshape(-1), f) * vals
        contrib = jnp.take(d, a, axis=0) * scaled[:, None]
        y = jax.ops.segment_sum(contrib, v, num_segments=nv_l,
                                indices_are_sorted=True)
        return jax.lax.psum(y, "model")

    def wc_op(a, v, f, vals, d, y_loc):
        sq = lambda x: x.reshape(x.shape[-1])
        a, v, f, vals = map(sq, (a, v, f, vals))
        y2 = y_loc.reshape(y_loc.shape[-2], y_loc.shape[-1])
        dots = jnp.einsum("ct,ct->c", jnp.take(d, a, axis=0),
                          jnp.take(y2, v, axis=0), precision=HIGHEST)
        w = jax.ops.segment_sum(dots * vals, f, num_segments=nf_l,
                                indices_are_sorted=True)
        return jax.lax.psum(w, rows)

    dsc_fn = jax.shard_map(
        dsc_op, mesh=mesh,
        in_specs=(cell, cell, cell, cell, P(None, None), P("model")),
        out_specs=P(rows, None), check_vma=False)
    wc_fn = jax.shard_map(
        wc_op, mesh=mesh,
        in_specs=(cell, cell, cell, cell, P(None, None), P(rows, None)),
        out_specs=P("model"), check_vma=False)
    return dsc_fn, wc_fn


def make_sharded_sell_ops(mesh: Mesh, shards_meta: Dict[str, int], *,
                          slot_tile: int, out_dtype=None):
    """shard_map'd SpMVs over per-cell SELL tiles (the `shard-sell` path).

    Same mesh layout and collectives as :func:`make_sharded_ops`, but each
    device's cell is a blocked-ELL slot array feeding the existing Pallas
    SELL kernels (``kernels/dsc.py:dsc_sell_pallas`` /
    ``kernels/wc.py:wc_sell_pallas``) instead of a sorted-COO segment sum —
    the DESIGN.md §7 fast path lifted to mesh granularity (§9).

    The kernels compile when the mesh is made of TPU devices and run in the
    Pallas interpreter otherwise.

    Inputs (global layouts; ``T_p`` = lane-padded Ntheta):
      cell slot arrays: (R, C, width, rows_padded) sharded (rows, model, ., .)
                        — slot-major, from ``kernels/ops.py:sell_lanes``
      d_padded:         (Na, T_p) replicated
      w:                (C*nf_local,) sharded (model,)     [dsc]
      y_padded:         (R*nv_local, T_p) sharded (rows,)  [wc]
    Returns (dsc_fn, wc_fn):
      dsc_fn(atoms, fibers, values, d_padded, w)  -> (R*nv_local, T_p)
      wc_fn(atoms, voxels, values, d_padded, y)   -> (C*nf_local,)
    """
    from repro.kernels import dsc as dsc_kernel
    from repro.kernels import wc as wc_kernel
    from repro.kernels.ops import interpret_mode

    interpret = interpret_mode(mesh.devices.flat[0].platform)
    rows = _row_axes(mesh)
    nv_l = shards_meta["nv_local"]
    nf_l = shards_meta["nf_local"]
    cell = P(rows, "model", None, None)
    sq = lambda x: x.reshape(x.shape[-2], x.shape[-1])

    def dsc_op(a, f, vals, d, w_loc):
        a, f, vals = map(sq, (a, f, vals))
        scaled = jnp.take(w_loc.reshape(-1), f) * vals   # padding slots stay 0
        y = dsc_kernel.dsc_sell_pallas(
            a, scaled, d, slot_tile=slot_tile, out_dtype=out_dtype,
            interpret=interpret)
        return jax.lax.psum(y[:nv_l], "model")

    def wc_op(a, v, vals, d, y_loc):
        a, v, vals = map(sq, (a, v, vals))
        y2 = y_loc.reshape(y_loc.shape[-2], y_loc.shape[-1])
        # pre-gather of local Y rows; padding slots gather row 0, value 0
        yg = jnp.take(y2, v, axis=0)
        w = wc_kernel.wc_sell_pallas(
            a, yg, vals, d, slot_tile=slot_tile, out_dtype=out_dtype,
            interpret=interpret)
        return jax.lax.psum(w[:nf_l], rows)

    dsc_fn = jax.shard_map(
        dsc_op, mesh=mesh,
        in_specs=(cell, cell, cell, P(None, None), P("model")),
        out_specs=P(rows, None), check_vma=False)
    wc_fn = jax.shard_map(
        wc_op, mesh=mesh,
        in_specs=(cell, cell, cell, P(None, None), P(rows, None)),
        out_specs=P("model"), check_vma=False)
    return dsc_fn, wc_fn


def _safe(num, den):
    return jnp.where(den > 0, num / jnp.where(den > 0, den, 1.0), 0.0)


def make_sharded_step_1d(mesh: Mesh, shards_meta: Dict[str, int]):
    """Paper-faithful 1-D coefficient partitioning (the MPI-LiFE analogue,
    §7.1.3): every device owns a coefficient block; Y and w are REPLICATED
    and every SpMV ends in a psum over the whole mesh.  This is the
    §Perf baseline the 2-D (voxel x fiber) partition improves on: its
    collective volume scales with the full Y and w vectors instead of the
    per-shard outputs.

    Inputs: coefficient block arrays shaped (n_dev, nnz_cell) sharded over
    all axes on dim 0; d, b (Nv, Ntheta), w (Nf,) replicated.
    """
    all_axes = _row_axes(mesh) + ("model",)
    nv = shards_meta["n_voxels"]
    nf = shards_meta["n_fibers"]

    def dsc_local(a, v, f, vals, d, w):
        scaled = jnp.take(w, f) * vals
        contrib = jnp.take(d, a, axis=0) * scaled[:, None]
        y = jax.ops.segment_sum(contrib, v, num_segments=nv,
                                indices_are_sorted=True)
        return jax.lax.psum(y, all_axes)              # full-Y reduction

    def wc_local(a, v, f, vals, d, y):
        dots = jnp.einsum("ct,ct->c", jnp.take(d, a, axis=0),
                          jnp.take(y, v, axis=0), precision=HIGHEST)
        w = jax.ops.segment_sum(dots * vals, f, num_segments=nf,
                                indices_are_sorted=False)
        return jax.lax.psum(w, all_axes)              # full-w reduction

    def step(a, v, f, vals, d, b, w, it):
        sq = lambda x: x.reshape(x.shape[-1])
        a, v, f, vals = map(sq, (a, v, f, vals))
        y = dsc_local(a, v, f, vals, d, w) - b
        g = wc_local(a, v, f, vals, d, y)
        gt = projected_gradient(w, g)
        vv1 = dsc_local(a, v, f, vals, d, gt)

        def odd(_):
            return _safe(jnp.vdot(gt, gt, precision=HIGHEST),
                         jnp.vdot(vv1, vv1, precision=HIGHEST))

        def even(_):
            vv2 = projected_gradient(w, wc_local(a, v, f, vals, d, vv1))
            return _safe(jnp.vdot(vv1, vv1, precision=HIGHEST),
                         jnp.vdot(vv2, vv2, precision=HIGHEST))

        alpha = jax.lax.cond(it % 2 == 1, odd, even, operand=None)
        w_new = jnp.maximum(w - alpha * gt, 0.0)
        return w_new, 0.5 * jnp.vdot(y, y, precision=HIGHEST)

    cell = P(all_axes, None)
    return jax.shard_map(
        step, mesh=mesh,
        in_specs=(cell, cell, cell, cell, P(None, None), P(None, None),
                  P(None), P()),
        out_specs=(P(None), P()), check_vma=False)


def life_input_specs_1d(mesh: Mesh, *, n_voxels: int = 247_356,
                        n_fibers: int = 500_000, n_theta: int = 96,
                        n_atoms: int = 1_024, nnz: int = 400_000_000):
    n_dev = int(mesh.devices.size)
    nnz_cell = -(-nnz // n_dev)
    f = jax.ShapeDtypeStruct
    return dict(
        a=f((n_dev, nnz_cell), jnp.int32), v=f((n_dev, nnz_cell), jnp.int32),
        fi=f((n_dev, nnz_cell), jnp.int32),
        vals=f((n_dev, nnz_cell), jnp.float32),
        d=f((n_atoms, n_theta), jnp.float32),
        b=f((n_voxels, n_theta), jnp.float32),
        w=f((n_fibers,), jnp.float32), it=f((), jnp.int32),
        meta=dict(n_voxels=n_voxels, n_fibers=n_fibers, n_theta=n_theta),
    )


def sharded_state(mesh: Mesh, shards: LifeShards, problem: LifeProblem,
                  w0: Optional[np.ndarray] = None):
    """device_put the shard tensors under the mesh shardings."""
    rows = _row_axes(mesh)
    cell = NamedSharding(mesh, P(rows, "model", None))
    ysh = NamedSharding(mesh, P(rows, None))
    wsh = NamedSharding(mesh, P("model"))
    rep = NamedSharding(mesh, P(None, None))
    put = jax.device_put
    args = dict(
        da=put(shards.dsc_atoms, cell), dv=put(shards.dsc_voxels_local, cell),
        df=put(shards.dsc_fibers_local, cell), dw=put(shards.dsc_values, cell),
        wa=put(shards.wc_atoms, cell), wv=put(shards.wc_voxels_local, cell),
        wf=put(shards.wc_fibers_local, cell), ww=put(shards.wc_values, cell),
        d=put(np.asarray(problem.dictionary), rep),
        b=put(shard_b(shards, np.asarray(problem.b)), ysh),
        w=put(shard_w(shards, w0 if w0 is not None else
                      np.ones(problem.phi.n_fibers, np.float32)), wsh),
    )
    return args


def life_input_specs(mesh: Mesh, *, n_voxels: int = 247_356,
                     n_fibers: int = 500_000, n_theta: int = 96,
                     n_atoms: int = 1_024, nnz: int = 400_000_000
                     ) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins at paper scale (Table 9, iFOD1/500k) for the
    dry-run: 2.5e5 voxels, 5e5 fibers, 4e8 coefficients."""
    rows = _row_axes(mesh)
    R = int(np.prod([mesh.shape[a] for a in rows]))
    C = int(mesh.shape["model"])
    nv_l = -(-n_voxels // R)
    nf_l = -(-n_fibers // C)
    nnz_cell = -(-nnz // (R * C))
    f = jax.ShapeDtypeStruct
    cell_i = lambda: f((R, C, nnz_cell), jnp.int32)
    cell_f = lambda: f((R, C, nnz_cell), jnp.float32)
    return dict(
        da=cell_i(), dv=cell_i(), df=cell_i(), dw=cell_f(),
        wa=cell_i(), wv=cell_i(), wf=cell_i(), ww=cell_f(),
        d=f((n_atoms, n_theta), jnp.float32),
        b=f((R * nv_l, n_theta), jnp.float32),
        w=f((C * nf_l,), jnp.float32),
        it=f((), jnp.int32),
        meta=dict(nv_local=nv_l, nf_local=nf_l, n_theta=n_theta),
    )
