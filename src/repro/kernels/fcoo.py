"""Pallas TPU kernels for F-COO: both SpMV ops off ONE resident layout.

The SELL kernels (kernels/dsc.py / wc.py) buy direct row-block accumulation
with a per-op padded copy; the F-COO pair (Liu et al., arXiv:1705.09905)
spends segment metadata instead of bytes.  Geometry per grid step ``t``:

  * one fixed ``c_tile`` chunk of the linearized coefficient stream
    (formats/fcoo.py) is loaded; ``D`` stays VMEM-resident as everywhere,
  * the chunk's precomputed segment ranks turn the within-chunk segment
    reduction into a one-hot ``(K, c_tile)`` MXU matmul — the same
    synchronization-free trick (and the same gather-free atom one-hot) as
    the COO kernels, but against *chunk-local* segments instead of a
    planned output row-block,
  * each step writes its own ``(K, .)`` partials block — no cross-step
    accumulation, no scalar prefetch, no ``@pl.when`` zero-init hazard; the
    caller (kernels/ops.py) folds chunk-boundary segments with one batched
    scatter-add over the format's ``seg_rows_*`` map.

bf16 storage keeps fp32 accumulation: operands are cast to fp32 before any
reduction and every matmul runs at ``Precision.HIGHEST`` with
``preferred_element_type=float32`` (DESIGN.md §10.3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dsc import fold_tile, onehot_rows
from repro.kernels.wc import atom_dots, segment_row


def fcoo_dsc_factory(*, out_dtype=None, interpret: bool = False):
    """Bind F-COO DSC launch parameters once (e.g. from a TunePlan); the
    tile geometry itself (c_tile, K) is carried by the operand shapes."""
    return functools.partial(dsc_fcoo_pallas, out_dtype=out_dtype,
                             interpret=interpret)


def fcoo_wc_factory(*, out_dtype=None, interpret: bool = False):
    """Bind F-COO WC launch parameters once (e.g. from a TunePlan)."""
    return functools.partial(wc_fcoo_pallas, out_dtype=out_dtype,
                             interpret=interpret)


def _dsc_fcoo_kernel(atoms_ref,           # (1, C_TILE) int32
                     ranks_ref,           # (1, C_TILE) int32, chunk-local
                     scaled_ref,          # (1, C_TILE) fp (w[fiber] * value)
                     d_ref,               # (Na, Ntheta_p) fp, VMEM-resident
                     out_ref):            # (K, Ntheta_p) segment partials
    # within-chunk segment reduction on the MXU; the block is exclusively
    # this grid step's, so plain assignment (no accumulation) is race-free
    weights = (onehot_rows(ranks_ref[...], out_ref.shape[0])
               * scaled_ref[...].astype(jnp.float32))       # (K, C)
    out_ref[...] = fold_tile(weights, atoms_ref[...],
                             d_ref[...]).astype(out_ref.dtype)


def dsc_fcoo_pallas(atoms: jax.Array, ranks: jax.Array, scaled: jax.Array,
                    dictionary_padded: jax.Array, *, seg_k: int,
                    out_dtype=None, interpret: bool = False) -> jax.Array:
    """DSC segment partials over the F-COO stream.

    ``atoms``/``ranks``/``scaled`` are the ``(n_chunks, c_tile)`` chunked
    views of the resident stream (``scaled = w[fibers] * values``; padding
    slots carry value 0).  Returns ``(n_chunks, seg_k, Ntheta_padded)``
    partials — the caller scatter-adds them over ``seg_rows_dsc``."""
    n_chunks, c_tile = atoms.shape
    n_theta_p = dictionary_padded.shape[1]
    out_dtype = dictionary_padded.dtype if out_dtype is None else out_dtype
    chunk = pl.BlockSpec((None, 1, c_tile), lambda t: (t, 0, 0))
    as_chunks = lambda x: x.reshape(n_chunks, 1, c_tile)
    return pl.pallas_call(
        _dsc_fcoo_kernel,
        grid=(n_chunks,),
        in_specs=[
            chunk, chunk, chunk,
            pl.BlockSpec(dictionary_padded.shape, lambda t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, seg_k, n_theta_p), lambda t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_chunks, seg_k, n_theta_p),
                                       out_dtype),
        interpret=interpret,
    )(as_chunks(atoms), as_chunks(ranks), as_chunks(scaled),
      dictionary_padded)


def _wc_fcoo_kernel(atoms_ref,            # (1, C_TILE) int32 (WC order)
                    ranks_ref,            # (1, C_TILE) int32, chunk-local
                    vals_ref,             # (1, C_TILE) fp
                    yg_ref,               # (C_TILE, Ntheta_p) fp
                    d_ref,                # (Na, Ntheta_p) fp, VMEM-resident
                    out_ref):             # (1, K) segment partials
    dots = (atom_dots(atoms_ref[...], yg_ref[...], d_ref[...])
            * vals_ref[...].astype(jnp.float32))            # (1, C_TILE)
    out_ref[...] = segment_row(dots, ranks_ref[...],
                               out_ref.shape[1]).astype(out_ref.dtype)


def wc_fcoo_pallas(atoms: jax.Array, ranks: jax.Array, vals: jax.Array,
                   yg: jax.Array, dictionary_padded: jax.Array, *,
                   seg_k: int, out_dtype=None,
                   interpret: bool = False) -> jax.Array:
    """WC segment partials over the fiber-major view of the same stream.

    ``atoms``/``vals`` are the ``wc_perm``-gathered chunked views, ``yg``
    the pre-gathered ``(n_chunks, c_tile, Ntheta_p)`` Y rows (padding slots
    gather a real row but carry value 0, so they are inert).  Returns
    ``(n_chunks, seg_k)`` partials for the ``seg_rows_wc`` scatter-add."""
    n_chunks, c_tile = atoms.shape
    n_theta_p = dictionary_padded.shape[1]
    out_dtype = dictionary_padded.dtype if out_dtype is None else out_dtype
    chunk = pl.BlockSpec((None, 1, c_tile), lambda t: (t, 0, 0))
    as_chunks = lambda x: x.reshape(n_chunks, 1, c_tile)
    parts = pl.pallas_call(
        _wc_fcoo_kernel,
        grid=(n_chunks,),
        in_specs=[
            chunk, chunk, chunk,
            pl.BlockSpec((None, c_tile, n_theta_p), lambda t: (t, 0, 0)),
            pl.BlockSpec(dictionary_padded.shape, lambda t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, seg_k), lambda t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_chunks, 1, seg_k), out_dtype),
        interpret=interpret,
    )(as_chunks(atoms), as_chunks(ranks), as_chunks(vals), yg,
      dictionary_padded)
    return parts.reshape(n_chunks, seg_k)
