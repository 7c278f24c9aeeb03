"""Compulsory work of the two LiFE SpMVs, from their shapes alone.

Bytes are what any implementation over COO Phi must move at least once:
16 bytes a coefficient (int32 atom, voxel and fiber ids and a float32
value), the dictionary, the input vector and the output, all float32 at
``Ntheta`` lanes (not at a padded lane width).  Operations are those of
the equations (``bench/reference.py``), ``2 Ntheta + 1`` per coefficient:
for DSC one product ``w[f] * val`` and ``Ntheta`` products and sums; for
WC ``Ntheta`` products and ``Ntheta - 1`` sums for the dot, one product by
``val`` and one sum into ``w[f]``.
"""
from __future__ import annotations

COO_BYTES_PER_COEFF = 16
F32 = 4


def dsc(n_coeffs: int, n_voxels: int, n_fibers: int, n_atoms: int,
        n_theta: int) -> dict:
    """``y = M w``: reads Phi, ``D`` and ``w``; writes ``y``."""
    return {"bytes": COO_BYTES_PER_COEFF * n_coeffs
            + F32 * (n_atoms * n_theta + n_fibers + n_voxels * n_theta),
            "flops": n_coeffs * (2 * n_theta + 1)}


def wc(n_coeffs: int, n_voxels: int, n_fibers: int, n_atoms: int,
       n_theta: int) -> dict:
    """``w = M^T y``: reads Phi, ``D`` and ``y``; writes ``w``."""
    return {"bytes": COO_BYTES_PER_COEFF * n_coeffs
            + F32 * (n_atoms * n_theta + n_voxels * n_theta + n_fibers),
            "flops": n_coeffs * (2 * n_theta + 1)}


def roofline_seconds(work: dict, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(work["flops"] / peak["flops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"])
