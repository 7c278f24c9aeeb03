"""Plain reference of the LiFE solve: DSC, WC, SBBNNLS and the lesion edit.

Written from the paper's equations in blocked ``jax.numpy``, float32, and
nothing of the program under test:

    DSC  (y = M w):    Y[voxel_k, :] += D[atom_k, :] * w[fiber_k] * val_k
    WC   (w = M^T y):  w[fiber_k]    += val_k * <D[atom_k, :], Y[voxel_k, :]>

Coefficients go through in blocks of :data:`BLOCK` (an unsorted
scatter-add per block), so the reference fits beside whatever else is on
the device.  Products and sums are elementwise, so no matrix-unit
precision setting enters; long sums are taken as trees.  SBBNNLS is Algorithm 1 of the paper
(Kim, Sra & Dhillon 2013): per iteration a DSC for the residual, a WC for
the gradient, the gradient projected onto the free set, a DSC of it, and
the Barzilai-Borwein step, whose even iterations (counting from 0) take
one more WC.  The loss recorded for an iteration is that of the weights
it starts from.

:func:`objective` judges an answer by what it says: ``1/2 |M w - b|^2``
in float64 on the host (``scipy.sparse``), so its own rounding lies far
below a float32 solve's.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse

BLOCK = 1 << 16


@dataclasses.dataclass
class Blocked:
    """Phi in COO, padded with zero-valued coefficients to whole blocks."""

    atoms: jax.Array            # int32 (n_blocks, BLOCK)
    voxels: jax.Array
    fibers: jax.Array
    values: jax.Array           # float32 (n_blocks, BLOCK)
    n_voxels: int
    n_fibers: int


def blocked(subject) -> Blocked:
    """Block a subject's coefficients (any object with COO fields)."""
    nc = int(subject.values.size)
    n_blocks = max(1, -(-nc // BLOCK))

    def pad(a, dtype):
        out = np.zeros(n_blocks * BLOCK, dtype)
        out[:nc] = np.asarray(a)
        return jnp.asarray(out.reshape(n_blocks, BLOCK))

    return Blocked(atoms=pad(subject.atoms, np.int32),
                   voxels=pad(subject.voxels, np.int32),
                   fibers=pad(subject.fibers, np.int32),
                   values=pad(subject.values, np.float32),
                   n_voxels=subject.n_voxels, n_fibers=subject.n_fibers)


def dsc(phi: Blocked, d, w) -> jax.Array:
    """``y = M w``, shape (Nv, Ntheta)."""
    return _dsc(phi.atoms, phi.voxels, phi.fibers, phi.values,
                jnp.asarray(d), jnp.asarray(w), n_voxels=phi.n_voxels)


def wc(phi: Blocked, d, y) -> jax.Array:
    """``w = M^T y``, shape (Nf,)."""
    return _wc(phi.atoms, phi.voxels, phi.fibers, phi.values,
               jnp.asarray(d), jnp.asarray(y), n_fibers=phi.n_fibers)


def _dsc_blocks(blocks, d, w, n_voxels):
    def body(y, blk):
        a, v, f, val = blk
        return y.at[v].add(d[a] * (w[f] * val)[:, None]), None

    y0 = jnp.zeros((n_voxels, d.shape[1]), jnp.float32)
    return jax.lax.scan(body, y0, blocks)[0]


def _wc_blocks(blocks, d, y, n_fibers):
    def body(w, blk):
        a, v, f, val = blk
        return w.at[f].add(jnp.sum(d[a] * y[v], axis=1) * val), None

    return jax.lax.scan(body, jnp.zeros((n_fibers,), jnp.float32), blocks)[0]


@partial(jax.jit, static_argnames=("n_voxels",))
def _dsc(a, v, f, val, d, w, *, n_voxels):
    return _dsc_blocks((a, v, f, val), d, w, n_voxels)


@partial(jax.jit, static_argnames=("n_fibers",))
def _wc(a, v, f, val, d, y, *, n_fibers):
    return _wc_blocks((a, v, f, val), d, y, n_fibers)


def _sum(x):
    """Sum in a tree of 128-wide partial sums, so that float32 rounding
    grows with the tree's depth and not with the number of terms."""
    x = x.reshape(-1)
    while x.size > 1:
        x = jnp.pad(x, (0, -x.size % 128)).reshape(-1, 128).sum(axis=1)
    return x[0]


def _project(w, g):
    """Zero the gradient where it would push a zero weight negative."""
    return jnp.where((w > 0) | (g < 0), g, 0.0)


def _ratio(num, den):
    return jnp.where(den > 0, num / jnp.where(den > 0, den, 1.0), 0.0)


@partial(jax.jit, static_argnames=("n_voxels", "n_fibers", "even"))
def _iteration(a, v, f, val, d, b, w, *, n_voxels, n_fibers, even):
    blocks = (a, v, f, val)
    y = _dsc_blocks(blocks, d, w, n_voxels) - b
    g = _project(w, _wc_blocks(blocks, d, y, n_fibers))
    u = _dsc_blocks(blocks, d, g, n_voxels)
    if even:
        uu = _project(w, _wc_blocks(blocks, d, u, n_fibers))
        alpha = _ratio(_sum(u * u), _sum(uu * uu))
    else:
        alpha = _ratio(_sum(g * g), _sum(u * u))
    return jnp.maximum(w - alpha * g, 0.0), 0.5 * _sum(y * y)


def sbbnnls(phi: Blocked, d, b, w0, n_iters: int
            ) -> Tuple[jax.Array, np.ndarray]:
    """``n_iters`` iterations from ``w0``: (weights, per-iteration loss)."""
    d, b, w = jnp.asarray(d), jnp.asarray(b), jnp.asarray(w0, jnp.float32)
    losses = []
    for it in range(n_iters):
        w, loss = _iteration(phi.atoms, phi.voxels, phi.fibers, phi.values,
                             d, b, w, n_voxels=phi.n_voxels,
                             n_fibers=phi.n_fibers, even=it % 2 == 0)
        losses.append(loss)
    return w, np.asarray(jnp.stack(losses))


def objective(subject, d, w) -> float:
    """``1/2 |M w - b|^2`` of weights ``w`` in float64: ``M w`` as the
    sparse (voxel, atom) matrix of ``w[fiber] * value``, duplicates summed,
    times the dictionary."""
    w = np.asarray(w, np.float64).reshape(-1)
    s = scipy.sparse.csr_matrix(
        (w[subject.fibers] * np.asarray(subject.values, np.float64),
         (subject.voxels, subject.atoms)),
        shape=(subject.n_voxels, np.shape(d)[0]))
    r = s @ np.asarray(d, np.float64) - np.asarray(subject.b, np.float64)
    return 0.5 * float(np.vdot(r, r))


def lesion(subject, fiber_ids: Sequence[int]):
    """The subject with a bundle's coefficients removed and the fiber id
    space kept, so weight vectors stay compatible."""
    keep = ~np.isin(subject.fibers, np.asarray(fiber_ids))
    return dataclasses.replace(
        subject, atoms=subject.atoms[keep], voxels=subject.voxels[keep],
        fibers=subject.fibers[keep], values=subject.values[keep])

