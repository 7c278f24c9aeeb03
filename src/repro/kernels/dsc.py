"""Pallas TPU kernel for DSC (y = M w), the diffusion-signal computation.

Executor for an inspector ``TilePlan`` over voxel-sorted coefficients
(DESIGN.md §2).  Geometry per grid step ``t``:

  * ``D`` (dictionary) is VMEM-resident for the whole grid — the TPU analogue
    of the paper keeping D rows in GPU shared memory.
  * a coefficient tile holds ``C_TILE`` coefficients on the lane axis.  Its
    contribution to the output row-block is two MXU matmuls through
    one-hots (the TPU has no in-VMEM row gather):
    ``A = (rows_onehot * scaled) @ atoms_onehot^T`` of shape
    ``(ROW_TILE, Na)`` folds the tile's coefficients per (row, atom), and
    ``A @ D`` expands them to ``(ROW_TILE, Ntheta)``.  The row one-hot is
    the synchronization-free reduction: the tile plan guarantees a tile
    touches exactly one row-block, and the sequential TPU grid makes
    consecutive-tile accumulation race-free (no atomics exist or are needed).

Scalar-prefetched ``row_block`` drives the output BlockSpec index_map, which
is exactly the inspector/executor split of the paper: the host-side sort +
tile plan is the inspector, this kernel is the executor.

``dsc_sell_pallas`` is the SELL fast path (DESIGN.md §7): over the blocked
ELL layout of ``formats/sell.py`` the tile -> output-block mapping is the
identity on grid axis 0, so there is **no** scalar prefetch and no row
one-hot — slot ``[s, r]`` belongs to output row ``r`` by construction.  The
kernel takes the layout slot-major (slots on sublanes, rows on lanes), so a
block is ``(slot_tile, LANES)`` for any ``slot_tile`` multiple of 8.

Every matmul runs at ``Precision.HIGHEST``: the one-hot side is exact in
any precision, but the scaled weights and ``D`` are fp32 and the MXU's
default pass would round them to bf16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Frozen fallbacks only: production paths bind launch parameters through the
# factories below (fed by a repro.tune TunePlan); these constants are what
# tune="off" and the pre-tune call sites get.
DEFAULT_C_TILE = 256
DEFAULT_ROW_TILE = 8
LANES = 128                 # SELL rows per block: the lane width

HIGHEST = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))          # a @ b.T
_NN = (((1,), (0,)), ((), ()))          # a @ b
_TN = (((0,), (0,)), ((), ()))          # a.T @ b


def onehot_rows(ids: jax.Array, n: int, dtype=jnp.float32) -> jax.Array:
    """``(n, C)`` one-hot of a ``(1, C)`` lane vector of ids in ``[0, n)``."""
    return (jax.lax.broadcasted_iota(jnp.int32, (n, ids.shape[1]), 0)
            == ids).astype(dtype)


def dsc_factory(*, row_tile: int = DEFAULT_ROW_TILE, out_dtype=None,
                interpret: bool = False):
    """Bind COO-DSC launch parameters once (e.g. from a TunePlan).

    Returns a callable with the :func:`dsc_pallas` signature minus the bound
    keywords — the parameterized replacement for reading module constants."""
    return functools.partial(dsc_pallas, row_tile=row_tile,
                             out_dtype=out_dtype, interpret=interpret)


def dsc_sell_factory(*, slot_tile: int = 32, out_dtype=None,
                     interpret: bool = False):
    """Bind SELL-DSC launch parameters once (e.g. from a TunePlan)."""
    return functools.partial(dsc_sell_pallas, slot_tile=slot_tile,
                             out_dtype=out_dtype, interpret=interpret)


def fold_tile(weights: jax.Array, atoms: jax.Array,
              d: jax.Array) -> jax.Array:
    """``weights (R, C)`` x ``D[atoms] (C, Ntheta)`` without a gather:
    fold the tile per (row, atom) against the atom one-hot, then expand
    through ``D`` — both on the MXU."""
    per_atom = jax.lax.dot_general(
        weights, onehot_rows(atoms, d.shape[0]), _NT,
        precision=HIGHEST, preferred_element_type=jnp.float32)  # (R, Na)
    return jax.lax.dot_general(
        per_atom, d.astype(jnp.float32), _NN,
        precision=HIGHEST, preferred_element_type=jnp.float32)


def _dsc_kernel(row_block_ref,            # scalar prefetch: (T,) int32
                atoms_ref,                # (1, C_TILE) int32
                scaled_ref,               # (1, C_TILE) fp
                local_row_ref,            # (1, C_TILE) int32
                d_ref,                    # (Na, Ntheta_p) fp, VMEM-resident
                y_ref):                   # (ROW_TILE, Ntheta_p) output block
    t = pl.program_id(0)
    prev = row_block_ref[jnp.maximum(t - 1, 0)]
    is_first_visit = jnp.logical_or(t == 0, row_block_ref[t] != prev)

    @pl.when(is_first_visit)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    row_tile = y_ref.shape[0]
    # segment reduction on the MXU (replaces atomicAdd / warp shuffle)
    weights = (onehot_rows(local_row_ref[...], row_tile)
               * scaled_ref[...].astype(jnp.float32))       # (R, C)
    y_ref[...] += fold_tile(weights, atoms_ref[...],
                            d_ref[...]).astype(y_ref.dtype)


def dsc_pallas(row_block: jax.Array, atoms_p: jax.Array, scaled_p: jax.Array,
               local_row_p: jax.Array, dictionary_padded: jax.Array,
               *, row_tile: int, n_row_blocks: int, out_dtype=None,
               interpret: bool = False) -> jax.Array:
    """Run the DSC executor.  Returns (n_row_blocks*row_tile, Ntheta_padded).

    ``atoms_p``/``scaled_p``/``local_row_p`` are ``(n_tiles, c_tile)``
    tile arrays, pre-padded by :mod:`repro.kernels.ops` from a TilePlan.
    ``out_dtype`` pins the accumulator/output dtype independently of the
    storage dtype of ``dictionary_padded`` (bf16 storage keeps fp32
    accumulation: pass out_dtype=float32).
    """
    n_tiles, c_tile = atoms_p.shape
    n_theta_p = dictionary_padded.shape[1]
    out_dtype = dictionary_padded.dtype if out_dtype is None else out_dtype
    tile = pl.BlockSpec((None, 1, c_tile), lambda t, rb: (t, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[
            tile, tile, tile,
            pl.BlockSpec(dictionary_padded.shape, lambda t, rb: (0, 0)),
        ],
        out_specs=pl.BlockSpec((row_tile, n_theta_p), lambda t, rb: (rb[t], 0)),
    )
    as_tiles = lambda x: x.reshape(n_tiles, 1, c_tile)
    return pl.pallas_call(
        _dsc_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (n_row_blocks * row_tile, n_theta_p), out_dtype),
        interpret=interpret,
    )(row_block, as_tiles(atoms_p), as_tiles(scaled_p),
      as_tiles(local_row_p), dictionary_padded)


# ----------------------------------------------------------------------------
# SELL fast path: direct row-block accumulation, no prefetch, no row one-hot.
# ----------------------------------------------------------------------------

def _dsc_sell_kernel(atoms_ref,           # (SLOT_TILE, LANES) int32
                     scaled_ref,          # (SLOT_TILE, LANES) fp
                     d_ref,               # (Na, Ntheta_p) fp, VMEM-resident
                     y_ref):              # (LANES, Ntheta_p) output block
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    n_atoms = d_ref.shape[0]
    # per_atom_t[a, r] = sum_s scaled[s, r] * (atoms[s, r] == a): slot [s, r]
    # belongs to output row r by layout, so the slot sum IS the row reduction
    per_atom_t = jnp.zeros((n_atoms, atoms_ref.shape[1]), jnp.float32)
    for s in range(atoms_ref.shape[0]):
        per_atom_t += (onehot_rows(atoms_ref[pl.ds(s, 1), :], n_atoms)
                       * scaled_ref[pl.ds(s, 1), :].astype(jnp.float32))
    y_ref[...] += jax.lax.dot_general(
        per_atom_t, d_ref[...].astype(jnp.float32), _TN,
        precision=HIGHEST, preferred_element_type=jnp.float32
    ).astype(y_ref.dtype)


def dsc_sell_pallas(atoms_t: jax.Array, scaled_t: jax.Array,
                    dictionary_padded: jax.Array, *, slot_tile: int,
                    out_dtype=None, interpret: bool = False) -> jax.Array:
    """DSC over a slot-major SELL layout.

    ``atoms_t``/``scaled_t`` are ``(width, n_rows_padded)``: the transposed
    slot arrays of ``formats/sell.py:SellPhi`` (``scaled = w[fibers] *
    values``, padding slots 0) with rows padded to a ``LANES`` multiple
    (:func:`repro.kernels.ops.sell_lanes`).  Returns ``(n_rows_padded,
    Ntheta_padded)``; grid axis 0 IS the output block index, axis 1 sweeps
    slot chunks into the resident block."""
    width, n_rows_padded = atoms_t.shape
    n_theta_p = dictionary_padded.shape[1]
    out_dtype = dictionary_padded.dtype if out_dtype is None else out_dtype
    grid = (n_rows_padded // LANES, width // slot_tile)
    slots = pl.BlockSpec((slot_tile, LANES), lambda i, j: (j, i))
    return pl.pallas_call(
        _dsc_sell_kernel,
        grid=grid,
        in_specs=[
            slots, slots,
            pl.BlockSpec(dictionary_padded.shape, lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((LANES, n_theta_p), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rows_padded, n_theta_p), out_dtype),
        interpret=interpret,
    )(atoms_t, scaled_t, dictionary_padded)
