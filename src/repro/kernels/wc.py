"""Pallas TPU kernel for WC (w = M^T y), the weight computation.

Executor for a ``TilePlan`` over **fiber-sorted** coefficients — the
beyond-paper TPU restructuring choice (the paper picks atom-sorted WC on
CPU/GPU for dictionary reuse; on TPU the scatter is the serial hazard, so we
sort by the output dimension and let XLA pre-gather ``Y`` rows as one
coalesced stream; see DESIGN.md §2).

Per grid step (coefficients on the lane axis):

  * ``D`` stays VMEM-resident.  The dot-product inner loop (paper: BLAS
    ``dot`` / warp ``SHFL`` reduction) is one MXU matmul
    ``D @ Yg_t^T`` of shape ``(Na, C_TILE)`` — every atom against every
    gathered Y row — and the atom one-hot then picks each coefficient's own
    atom with a sublane reduction: ``dots = sum(onehot * (D @ Yg^T), 0)``,
  * the fiber scatter is the one-hot segment reduction into a
    (1, FIB_TILE) output block, accumulated across consecutive tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dsc import HIGHEST, LANES, _NT, onehot_rows

# Frozen fallbacks only — see kernels/dsc.py; production binds launch
# parameters through the factories below.
DEFAULT_C_TILE = 256
DEFAULT_FIB_TILE = 128


def wc_factory(*, fib_tile: int = DEFAULT_FIB_TILE, out_dtype=None,
               interpret: bool = False):
    """Bind COO-WC launch parameters once (e.g. from a TunePlan)."""
    return functools.partial(wc_pallas, fib_tile=fib_tile,
                             out_dtype=out_dtype, interpret=interpret)


def wc_sell_factory(*, slot_tile: int = 32, out_dtype=None,
                    interpret: bool = False):
    """Bind SELL-WC launch parameters once (e.g. from a TunePlan)."""
    return functools.partial(wc_sell_pallas, slot_tile=slot_tile,
                             out_dtype=out_dtype, interpret=interpret)


def atom_dots(atoms: jax.Array, yg: jax.Array, d: jax.Array) -> jax.Array:
    """``(1, C)`` row of ``dot(D[atoms[c]], yg[c])`` without a gather."""
    every_atom = jax.lax.dot_general(
        d.astype(jnp.float32), yg.astype(jnp.float32), _NT,
        precision=HIGHEST, preferred_element_type=jnp.float32)  # (Na, C)
    return jnp.sum(onehot_rows(atoms, d.shape[0]) * every_atom, axis=0,
                   keepdims=True)


def segment_row(dots: jax.Array, ids: jax.Array, n: int) -> jax.Array:
    """``(1, n)`` sums of a ``(1, C)`` row over its ``(1, C)`` segment ids."""
    return jax.lax.dot_general(
        dots, onehot_rows(ids, n), _NT,
        precision=HIGHEST, preferred_element_type=jnp.float32)


def _wc_kernel(row_block_ref,             # scalar prefetch: (T,) int32
               atoms_ref,                 # (1, C_TILE) int32
               yg_ref,                    # (C_TILE, Ntheta_p) fp
               vals_ref,                  # (1, C_TILE) fp
               local_row_ref,             # (1, C_TILE) int32
               d_ref,                     # (Na, Ntheta_p) fp, VMEM-resident
               w_ref):                    # (1, FIB_TILE) output block
    t = pl.program_id(0)
    prev = row_block_ref[jnp.maximum(t - 1, 0)]
    is_first_visit = jnp.logical_or(t == 0, row_block_ref[t] != prev)

    @pl.when(is_first_visit)
    def _():
        w_ref[...] = jnp.zeros_like(w_ref)

    dots = (atom_dots(atoms_ref[...], yg_ref[...], d_ref[...])
            * vals_ref[...].astype(jnp.float32))            # (1, C_TILE)
    w_ref[...] += segment_row(dots, local_row_ref[...],
                              w_ref.shape[1]).astype(w_ref.dtype)


def wc_pallas(row_block: jax.Array, atoms_p: jax.Array, yg_p: jax.Array,
              vals_p: jax.Array, local_row_p: jax.Array,
              dictionary_padded: jax.Array, *, fib_tile: int,
              n_fib_blocks: int, out_dtype=None,
              interpret: bool = False) -> jax.Array:
    """Run the WC executor.  Returns (n_fib_blocks, fib_tile) partial weights.

    ``yg_p`` is ``(n_tiles, c_tile, Ntheta_p)``; the other tile operands are
    ``(n_tiles, c_tile)``.  ``out_dtype`` pins the accumulator/output dtype
    independently of the storage dtype (bf16 storage keeps fp32
    accumulation)."""
    n_tiles, c_tile = atoms_p.shape
    n_theta_p = dictionary_padded.shape[1]
    out_dtype = dictionary_padded.dtype if out_dtype is None else out_dtype
    tile = pl.BlockSpec((None, 1, c_tile), lambda t, rb: (t, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[
            tile,
            pl.BlockSpec((None, c_tile, n_theta_p), lambda t, rb: (t, 0, 0)),
            tile, tile,
            pl.BlockSpec(dictionary_padded.shape, lambda t, rb: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, fib_tile),
                               lambda t, rb: (rb[t], 0, 0)),
    )
    as_tiles = lambda x: x.reshape(n_tiles, 1, c_tile)
    w = pl.pallas_call(
        _wc_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_fib_blocks, 1, fib_tile),
                                       out_dtype),
        interpret=interpret,
    )(row_block, as_tiles(atoms_p), yg_p, as_tiles(vals_p),
      as_tiles(local_row_p), dictionary_padded)
    return w.reshape(n_fib_blocks, fib_tile)


# ----------------------------------------------------------------------------
# SELL fast path: direct fiber-block accumulation, no prefetch, no segment
# one-hot (DESIGN.md §7; layout from formats/sell.py with op="wc" — rows =
# fibers, taken slot-major so rows sit on the lane axis).
# ----------------------------------------------------------------------------

def _wc_sell_kernel(atoms_ref,            # (SLOT_TILE, LANES) int32
                    yg_ref,               # (SLOT_TILE, LANES, Ntheta_p) fp
                    vals_ref,             # (SLOT_TILE, LANES) fp
                    d_ref,                # (Na, Ntheta_p) fp, VMEM-resident
                    w_ref):               # (1, LANES) output block
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        w_ref[...] = jnp.zeros_like(w_ref)

    # slot [s, r] belongs to fiber row r by layout: the slot sum IS the
    # fiber reduction, so the per-slot dot rows accumulate directly
    acc = jnp.zeros(w_ref.shape, jnp.float32)
    for s in range(atoms_ref.shape[0]):
        acc += (atom_dots(atoms_ref[pl.ds(s, 1), :], yg_ref[s], d_ref[...])
                * vals_ref[pl.ds(s, 1), :].astype(jnp.float32))
    w_ref[...] += acc.astype(w_ref.dtype)


def wc_sell_pallas(atoms_t: jax.Array, yg_t: jax.Array, vals_t: jax.Array,
                   dictionary_padded: jax.Array, *, slot_tile: int,
                   out_dtype=None, interpret: bool = False) -> jax.Array:
    """WC over a slot-major fiber-row SELL layout.

    ``atoms_t``/``vals_t`` are ``(width, n_rows_padded)`` (see
    :func:`repro.kernels.ops.sell_lanes`); ``yg_t`` is the pre-gathered
    ``(width, n_rows_padded, Ntheta_p)`` stream of Y rows (padding slots
    carry value 0 so their gathered rows are inert).  Returns
    ``(n_rows_padded,)`` weights (trim to Nf)."""
    width, n_rows_padded = atoms_t.shape
    n_theta_p = dictionary_padded.shape[1]
    out_dtype = dictionary_padded.dtype if out_dtype is None else out_dtype
    grid = (n_rows_padded // LANES, width // slot_tile)
    slots = pl.BlockSpec((slot_tile, LANES), lambda i, j: (j, i))
    w = pl.pallas_call(
        _wc_sell_kernel,
        grid=grid,
        in_specs=[
            slots,
            pl.BlockSpec((slot_tile, LANES, n_theta_p),
                         lambda i, j: (j, i, 0)),
            slots,
            pl.BlockSpec(dictionary_padded.shape, lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, LANES), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_rows_padded), out_dtype),
        interpret=interpret,
    )(atoms_t, yg_t, vals_t, dictionary_padded)
    return w.reshape(n_rows_padded)
