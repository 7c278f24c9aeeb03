"""jit'd wrappers binding inspector TilePlans to the Pallas executors.

`make_dsc` / `make_wc` close over the *static* plan operands (padded index
tiles, host-computed once, amortized across SBBNNLS iterations and runs) and
return matvec/rmatvec callables whose only dynamic inputs are ``w`` / ``Y``.

Lane padding: Ntheta is padded to a 128-lane multiple (the paper pads Ntheta
to warp multiples; zero columns contribute zeros through both ops).

Interpret mode is not a choice: the kernels compile on a TPU backend and run
in the Pallas interpreter on any other (:func:`interpret_mode`), so a TPU
never times the interpreter and a CPU test still exercises the kernel body.

Compute dtype (DESIGN.md §10.3): ``compute_dtype="bf16"`` stores the static
operands — the dictionary and the Phi values — in bfloat16 while every
reduction accumulates in fp32 (the kernels' output dtype is pinned to the
original dictionary dtype, and contributions are cast up before the
reductions).  Dynamic operands (``w``, ``Y``) stay fp32, so products promote
to fp32 before any accumulation; only per-element storage rounding (~2^-8
relative) enters the result — the documented ``repro.tune.plan.BF16_RTOL``
contract.
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.tree_util import Partial

from repro.core.inspector import TilePlan
from repro.core.std import PhiTensor
from repro.kernels import dsc as dsc_kernel
from repro.kernels import wc as wc_kernel
from repro.kernels.dsc import LANES


def interpret_mode(platform: str | None = None) -> bool:
    """Whether Pallas must interpret on ``platform`` (default: the default
    backend's) — everywhere except on a TPU."""
    return (platform or jax.default_backend()) != "tpu"


def pad_lanes(x: jax.Array, multiple: int = LANES) -> jax.Array:
    pad = (-x.shape[-1]) % multiple
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def storage_cast(x: jax.Array, compute_dtype: str) -> jax.Array:
    """Cast a *static* operand to its storage dtype ("bf16" halves resident
    bytes; anything else is identity).  Never used on accumulators."""
    if compute_dtype == "bf16":
        return jnp.asarray(x).astype(jnp.bfloat16)
    return jnp.asarray(x)


def sell_lanes(slots) -> np.ndarray:
    """Slot-major copy of SELL slot arrays: ``(..., n_rows_padded, width)``
    -> ``(..., width, rows)`` with rows padded to a ``LANES`` multiple, the
    orientation the SELL kernels block as ``(slot_tile, LANES)``.  Padding
    rows hold index 0 and value 0, so they are inert."""
    slots = np.swapaxes(np.asarray(slots), -1, -2)
    pad = (-slots.shape[-1]) % LANES
    return np.pad(slots, [(0, 0)] * (slots.ndim - 1) + [(0, pad)])


def _padded_operands(phi: PhiTensor, plan: TilePlan):
    """Static executor operands from a plan (host-side, cached)."""
    sel = jnp.asarray(plan.sel)
    atoms_pad = jnp.concatenate([phi.atoms, jnp.zeros((1,), phi.atoms.dtype)])
    fibers_pad = jnp.concatenate([phi.fibers, jnp.zeros((1,), phi.fibers.dtype)])
    voxels_pad = jnp.concatenate([phi.voxels, jnp.zeros((1,), phi.voxels.dtype)])
    values_pad = jnp.concatenate([phi.values, jnp.zeros((1,), phi.values.dtype)])
    shape = (plan.n_tiles, plan.c_tile)
    return dict(
        atoms_p=jnp.take(atoms_pad, sel).reshape(shape),
        fibers_p=jnp.take(fibers_pad, sel).reshape(shape),
        voxels_p=jnp.take(voxels_pad, sel).reshape(shape),
        values_p=jnp.take(values_pad, sel).reshape(shape),
        local_row_p=jnp.asarray(plan.local_row).reshape(shape),
        row_block=jnp.asarray(plan.row_block),
        # padding slots got values 0 via values_pad, so they contribute 0.
    )


def _visited_mask(plan: TilePlan, n_rows: int) -> jax.Array:
    """Row mask zeroing row-blocks never visited by any tile (kernel leaves
    them uninitialized)."""
    visited = np.zeros(plan.n_rows_padded // plan.row_tile, bool)
    visited[np.asarray(plan.row_block)] = True
    mask = np.repeat(visited, plan.row_tile)[:n_rows]
    return jnp.asarray(mask, jnp.float32)


def bind(fn: Callable, operands: dict) -> Partial:
    """``x -> fn(operands, x)`` as a pytree (a ``jax.tree_util.Partial``):
    a program that takes it as an argument (``core/sbbnnls.py``) gets the
    operands as arguments too, in their device placement, instead of
    baking them into its code as constants."""
    return Partial(jax.jit(fn), operands)


def make_dsc(phi_voxel_sorted: PhiTensor, dictionary: jax.Array,
             plan: TilePlan, *, compute_dtype: str = "fp32") -> Partial:
    """Returns matvec(w) -> (Nv, Ntheta) running the DSC Pallas executor."""
    tiles = _padded_operands(phi_voxel_sorted, plan)
    n_theta = dictionary.shape[1]
    n_voxels = phi_voxel_sorted.n_voxels
    n_row_blocks = plan.n_rows_padded // plan.row_tile
    kernel = dsc_kernel.dsc_factory(row_tile=plan.row_tile,
                                    out_dtype=dictionary.dtype,
                                    interpret=interpret_mode())

    def matvec(op, w: jax.Array) -> jax.Array:
        fibers_p = op["fibers_p"]
        scaled_p = jnp.take(w, fibers_p.reshape(-1)).reshape(
            fibers_p.shape) * op["values_p"]
        y = kernel(op["row_block"], op["atoms_p"], scaled_p,
                   op["local_row_p"], op["d_pad"], n_row_blocks=n_row_blocks)
        # where (not multiply): unvisited blocks are uninitialized memory
        return jnp.where(op["mask"][:, None] > 0, y[:n_voxels, :n_theta], 0.0)

    return bind(matvec, dict(
        row_block=tiles["row_block"], atoms_p=tiles["atoms_p"],
        fibers_p=tiles["fibers_p"], local_row_p=tiles["local_row_p"],
        values_p=storage_cast(tiles["values_p"], compute_dtype),
        d_pad=pad_lanes(storage_cast(dictionary, compute_dtype)),
        mask=_visited_mask(plan, n_voxels)))


def make_dsc_sell(sell, dictionary: jax.Array, *,
                  compute_dtype: str = "fp32") -> Partial:
    """matvec(w) -> (Nv, Ntheta) over a ``formats/sell.py:SellPhi`` (op="dsc").

    No TilePlan, no prefetch operands: the layout's static slot arrays are
    the whole plan (DESIGN.md §7)."""
    if sell.op != "dsc":
        raise ValueError(f"need a dsc-layout SellPhi, got op={sell.op!r}")
    n_theta = dictionary.shape[1]
    n_voxels = sell.n_voxels
    kernel = dsc_kernel.dsc_sell_factory(
        slot_tile=sell.slot_tile, out_dtype=dictionary.dtype,
        interpret=interpret_mode())

    def matvec(op, w: jax.Array) -> jax.Array:
        # padding slots stay 0
        scaled = jnp.take(w, op["fibers"]) * op["values"]
        y = kernel(op["atoms"], scaled, op["d_pad"])
        return y[:n_voxels, :n_theta]

    return bind(matvec, dict(
        atoms=jnp.asarray(sell_lanes(sell.atoms)),
        fibers=jnp.asarray(sell_lanes(sell.others)),
        values=storage_cast(sell_lanes(sell.values), compute_dtype),
        d_pad=pad_lanes(storage_cast(dictionary, compute_dtype))))


def make_wc_sell(sell, dictionary: jax.Array, *,
                 compute_dtype: str = "fp32") -> Partial:
    """rmatvec(Y) -> (Nf,) over a ``formats/sell.py:SellPhi`` (op="wc")."""
    if sell.op != "wc":
        raise ValueError(f"need a wc-layout SellPhi, got op={sell.op!r}")
    n_fibers = sell.n_fibers
    kernel = wc_kernel.wc_sell_factory(
        slot_tile=sell.slot_tile, out_dtype=dictionary.dtype,
        interpret=interpret_mode())

    def rmatvec(op, y: jax.Array) -> jax.Array:
        # coalesced XLA pre-gather of Y rows, one (W, rows, T) stream;
        # padding slots gather row 0 but carry value 0, so they are inert
        yg = jnp.take(pad_lanes(y), op["voxels"], axis=0)
        return kernel(op["atoms"], yg, op["values"], op["d_pad"])[:n_fibers]

    return bind(rmatvec, dict(
        atoms=jnp.asarray(sell_lanes(sell.atoms)),
        voxels=jnp.asarray(sell_lanes(sell.others)),
        values=storage_cast(sell_lanes(sell.values), compute_dtype),
        d_pad=pad_lanes(storage_cast(dictionary, compute_dtype))))


def make_fcoo_ops(fc, dictionary: jax.Array, *,
                  compute_dtype: str = "fp32") -> Tuple[Partial, Partial]:
    """(matvec, rmatvec) over ONE resident ``formats/fcoo.py:FcooPhi``.

    Both closures share the same device arrays — the stream is uploaded
    once; the WC view is a per-call in-jit gather through ``wc_perm``, not
    a second resident copy (the one-copy residency the 0.6x-of-SELL gate
    in benchmarks/check_regression.py holds).  The kernels emit per-chunk
    segment partials; the batched scatter-add over ``seg_rows_*`` here is
    the chunk-boundary combine (a run split across chunks lands twice on
    the same output row) and routes padding segments to the dummy row that
    the final trim drops."""
    from repro.kernels import fcoo as fcoo_kernel
    n_theta = dictionary.shape[1]
    n_voxels, n_fibers = fc.n_voxels, fc.n_fibers
    n_chunks, c_tile = fc.n_chunks, fc.c_tile
    if n_chunks == 0:                       # empty Phi: no kernel to launch
        zero_y = jnp.zeros((n_voxels, n_theta), dictionary.dtype)
        zero_w = jnp.zeros((n_fibers,), dictionary.dtype)
        return (bind(lambda op, w: op, zero_y),
                bind(lambda op, y: op, zero_w))

    shape = (n_chunks, c_tile)
    stream = dict(
        atoms=jnp.asarray(fc.atoms).reshape(shape),
        values=storage_cast(fc.values, compute_dtype).reshape(shape),
        d_pad=pad_lanes(storage_cast(dictionary, compute_dtype)))
    out_dtype = dictionary.dtype
    dsc_k = fcoo_kernel.fcoo_dsc_factory(out_dtype=out_dtype,
                                         interpret=interpret_mode())
    wc_k = fcoo_kernel.fcoo_wc_factory(out_dtype=out_dtype,
                                       interpret=interpret_mode())

    def matvec(op, w: jax.Array) -> jax.Array:
        scaled = jnp.take(w, op["fibers"].reshape(-1)).reshape(shape) \
            * op["values"]
        parts = dsc_k(op["atoms"], op["dsc_ranks"], scaled, op["d_pad"],
                      seg_k=fc.k_dsc)
        y = jnp.zeros((n_voxels + 1, parts.shape[-1]), parts.dtype)
        return y.at[op["seg_rows_dsc"]].add(parts)[:n_voxels, :n_theta]

    def rmatvec(op, y: jax.Array) -> jax.Array:
        y_pad = pad_lanes(y)
        wc_perm = op["wc_perm"]
        # per-call in-jit gathers materialize the fiber-major view without
        # keeping a second resident copy of the stream
        atoms_w = jnp.take(op["atoms"].reshape(-1), wc_perm).reshape(shape)
        vals_w = jnp.take(op["values"].reshape(-1), wc_perm).reshape(shape)
        yg = jnp.take(y_pad, jnp.take(op["voxels"], wc_perm), axis=0
                      ).reshape(n_chunks, c_tile, y_pad.shape[1])
        parts = wc_k(atoms_w, op["wc_ranks"], vals_w, yg, op["d_pad"],
                     seg_k=fc.k_wc)
        w = jnp.zeros((n_fibers + 1,), parts.dtype)
        return w.at[op["seg_rows_wc"]].add(parts)[:n_fibers]

    return (bind(matvec, dict(
                stream, fibers=jnp.asarray(fc.fibers).reshape(shape),
                dsc_ranks=jnp.asarray(fc.dsc_ranks).reshape(shape),
                seg_rows_dsc=jnp.asarray(fc.seg_rows_dsc))),
            bind(rmatvec, dict(
                stream, voxels=jnp.asarray(fc.voxels),
                wc_perm=jnp.asarray(fc.wc_perm),
                wc_ranks=jnp.asarray(fc.wc_ranks).reshape(shape),
                seg_rows_wc=jnp.asarray(fc.seg_rows_wc))))


def make_wc(phi_fiber_sorted: PhiTensor, dictionary: jax.Array,
            plan: TilePlan, *, compute_dtype: str = "fp32") -> Partial:
    """Returns rmatvec(Y) -> (Nf,) running the WC Pallas executor."""
    tiles = _padded_operands(phi_fiber_sorted, plan)
    n_fibers = phi_fiber_sorted.n_fibers
    n_fib_blocks = plan.n_rows_padded // plan.row_tile
    kernel = wc_kernel.wc_factory(fib_tile=plan.row_tile,
                                  out_dtype=dictionary.dtype,
                                  interpret=interpret_mode())

    def rmatvec(op, y: jax.Array) -> jax.Array:
        y_pad = pad_lanes(y)
        voxels_p = op["voxels_p"]
        # coalesced XLA pre-gather of Y rows (beyond-paper: output-side sort
        # moves the irregularity to a streaming gather; see DESIGN.md §2)
        yg_p = jnp.take(
            jnp.concatenate([y_pad, jnp.zeros((1, y_pad.shape[1]), y_pad.dtype)]),
            voxels_p.reshape(-1), axis=0,
        ).reshape(*voxels_p.shape, y_pad.shape[1])
        w = kernel(op["row_block"], op["atoms_p"], yg_p, op["values_p"],
                   op["local_row_p"], op["d_pad"], n_fib_blocks=n_fib_blocks)
        return jnp.where(op["mask"] > 0, w.reshape(-1)[:n_fibers], 0.0)

    return bind(rmatvec, dict(
        row_block=tiles["row_block"], atoms_p=tiles["atoms_p"],
        voxels_p=tiles["voxels_p"], local_row_p=tiles["local_row_p"],
        values_p=storage_cast(tiles["values_p"], compute_dtype),
        d_pad=pad_lanes(storage_cast(dictionary, compute_dtype)),
        mask=_visited_mask(plan, n_fibers)))
