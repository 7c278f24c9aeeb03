"""The two LiFE SpMV operations in pure JAX (executor layer).

Implements the paper's Figure-3 ops with the optimization ladder as separate,
benchmarkable code versions (mirroring §6 "code versions"):

  * ``*_naive``      — direct translation (per-coefficient scatter/gather via
                       XLA scatter-add). The CPU-naive analogue.
  * ``dsc`` / ``wc`` — restructured executors: contributions computed as a
                       dense (Nc, Ntheta) tile stream + segment reduction over
                       the sorted output dimension.  The CPU/GPU-opt analogue
                       and the building block that shard_map distributes.

All functions treat the *index* arrays as static-shaped operands, so they jit
cleanly and lower to the same HLO the dry-run mesh sees.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.std import PhiTensor

Array = jax.Array
# fp32 dot products at full precision: the TPU default rounds operands to bf16
HIGHEST = jax.lax.Precision.HIGHEST


# ----------------------------------------------------------------------------
# Naive code versions (paper Figure 3): per-coefficient indirect ops.
# ----------------------------------------------------------------------------

@partial(jax.jit, static_argnames=())
def dsc_naive(phi: PhiTensor, dictionary: Array, w: Array) -> Array:
    """y = M w via scatter-add, no restructuring assumed. (Nv, Ntheta)."""
    scaled = w[phi.fibers] * phi.values                       # hoisted w*val
    contrib = dictionary[phi.atoms] * scaled[:, None]          # (Nc, Ntheta)
    out = jnp.zeros((phi.n_voxels, dictionary.shape[1]), contrib.dtype)
    return out.at[phi.voxels].add(contrib)


@partial(jax.jit, static_argnames=())
def wc_naive(phi: PhiTensor, dictionary: Array, y: Array) -> Array:
    """w = M^T y via gather-dot-scatter, no restructuring assumed. (Nf,)."""
    dots = jnp.einsum("ct,ct->c", dictionary[phi.atoms], y[phi.voxels],
                      precision=HIGHEST)
    vals = dots * phi.values
    out = jnp.zeros((phi.n_fibers,), vals.dtype)
    return out.at[phi.fibers].add(vals)


# ----------------------------------------------------------------------------
# Restructured executors (paper §4.1.2 + §4.1.3): sorted segment reduction.
# On TPU these lower to efficient sorted-segment sums; they are also exactly
# what each device runs inside the shard_map 2-D partition.
# ----------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("num_segments",))
def segment_sum_sorted(data: Array, segment_ids: Array, num_segments: int) -> Array:
    return jax.ops.segment_sum(
        data, segment_ids, num_segments=num_segments,
        indices_are_sorted=True, unique_indices=False,
    )


@partial(jax.jit, static_argnames=())
def dsc(phi_sorted: PhiTensor, dictionary: Array, w: Array) -> Array:
    """y = M w assuming coefficients sorted by voxel (restructured).

    contributions stream as a (Nc, Ntheta) dense tile; the voxel scatter is a
    *sorted* segment sum — the sync-free reduction of DESIGN.md §2.
    """
    scaled = jnp.take(w, phi_sorted.fibers) * phi_sorted.values
    contrib = jnp.take(dictionary, phi_sorted.atoms, axis=0) * scaled[:, None]
    return segment_sum_sorted(contrib, phi_sorted.voxels, phi_sorted.n_voxels)


@partial(jax.jit, static_argnames=())
def wc(phi_sorted: PhiTensor, dictionary: Array, y: Array) -> Array:
    """w = M^T y assuming coefficients sorted by fiber (TPU-optimized sort).

    Gathers are coalesced XLA takes; the fiber scatter is a sorted segment
    sum.  The paper's atom-sorted CPU/GPU variant is `wc_atom_sorted`.
    """
    dots = jnp.einsum(
        "ct,ct->c",
        jnp.take(dictionary, phi_sorted.atoms, axis=0),
        jnp.take(y, phi_sorted.voxels, axis=0),
        precision=HIGHEST,
    )
    vals = dots * phi_sorted.values
    return segment_sum_sorted(vals, phi_sorted.fibers, phi_sorted.n_fibers)


@partial(jax.jit, static_argnames=())
def wc_atom_sorted(phi_sorted: PhiTensor, dictionary: Array, y: Array) -> Array:
    """Paper-faithful WC: atom-sorted (D reuse), unsorted fiber scatter."""
    dots = jnp.einsum(
        "ct,ct->c",
        jnp.take(dictionary, phi_sorted.atoms, axis=0),
        jnp.take(y, phi_sorted.voxels, axis=0),
        precision=HIGHEST,
    )
    vals = dots * phi_sorted.values
    out = jnp.zeros((phi_sorted.n_fibers,), vals.dtype)
    return out.at[phi_sorted.fibers].add(vals)


@partial(jax.jit, static_argnames=())
def dsc_atom_sorted(phi_sorted: PhiTensor, dictionary: Array, w: Array) -> Array:
    """Paper Table-2 variant: DSC with atom-sorted data (D reuse, unsorted Y)."""
    scaled = jnp.take(w, phi_sorted.fibers) * phi_sorted.values
    contrib = jnp.take(dictionary, phi_sorted.atoms, axis=0) * scaled[:, None]
    out = jnp.zeros((phi_sorted.n_voxels, dictionary.shape[1]), contrib.dtype)
    return out.at[phi_sorted.voxels].add(contrib)


def matvec_dense_oracle(m: Array, w: Array) -> Array:
    return m @ w


def rmatvec_dense_oracle(m: Array, y: Array) -> Array:
    return m.T @ y
