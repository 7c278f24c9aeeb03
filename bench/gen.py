"""Synthetic LiFE subjects made from a seed, vectorised over fibers.

The model is the one of the program's generator (``data/dmri.py``):
streamlines stepped through a voxel grid with per-step direction noise,
each step quantised to the dictionary atom nearest its direction (axial
symmetry), repeated (atom, voxel, fiber) triples deduped with their
lengths summed, and a measured signal ``b = M w_true + noise`` from a
sparse nonnegative ``w_true``.  It steps every fiber at once, one step at
a time, so a 50,000-fiber subject takes seconds rather than the loop's
minute; the random streams differ from the loop's, the statistics do not
(``bench/tests/test_gen.py``).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

#: tractography name -> (curvature, mean steps, step-count jitter), as in
#: the program's generator
TRACTOGRAPHY = {
    "DET": (0.05, 24, 4),
    "PROB": (0.35, 24, 8),
    "iFOD1": (0.50, 36, 12),
    "SD_STREAM": (0.20, 20, 6),
    "FACT": (0.00, 16, 4),
}
STEP = 0.75


@dataclasses.dataclass
class Subject:
    """One candidate connectome: Phi in COO, the signal, the ground truth."""

    atoms: np.ndarray           # int32 (Nc,)
    voxels: np.ndarray          # int32 (Nc,)
    fibers: np.ndarray          # int32 (Nc,)
    values: np.ndarray          # float32 (Nc,)
    n_atoms: int
    n_voxels: int
    n_fibers: int
    grid: Tuple[int, int, int]
    w_true: np.ndarray          # float32 (Nf,)
    b: Optional[np.ndarray] = None   # float32 (Nv, Ntheta), set by make_signal

    @property
    def n_coeffs(self) -> int:
        return int(self.values.size)


def fibonacci_sphere(n: int) -> np.ndarray:
    """``n`` quasi-uniform unit vectors (the atom orientations)."""
    i = np.arange(n, dtype=np.float64) + 0.5
    phi = np.arccos(1 - 2 * i / n)
    theta = np.pi * (1 + 5 ** 0.5) * i
    return np.stack([np.cos(theta) * np.sin(phi),
                     np.sin(theta) * np.sin(phi), np.cos(phi)], axis=1)


def dictionary(n_atoms: int, n_theta: int) -> np.ndarray:
    """Stick-model atoms over ``n_theta`` gradient directions, demeaned per
    atom: the acquisition every subject of a configuration shares.  The
    gradient directions come from the fixed key 7, as in the program."""
    import jax
    k1, _ = jax.random.split(jax.random.PRNGKey(7))
    grad = np.array(jax.random.normal(k1, (n_theta, 3)), np.float64)
    grad /= np.linalg.norm(grad, axis=1, keepdims=True)
    sig = np.exp(-2.0 * (grad @ fibonacci_sphere(n_atoms).T) ** 2).T
    return (sig - sig.mean(axis=1, keepdims=True)).astype(np.float32)


def nearest_atoms(dirs: np.ndarray, atom_dirs: np.ndarray) -> np.ndarray:
    """Index of the atom whose orientation is nearest each unit direction,
    up to sign (axial symmetry).  For unit vectors the largest ``|d . a|``
    is the smallest distance from ``d`` or ``-d`` to ``a``, so a k-d tree
    over the atoms finds it without the (steps x atoms) product."""
    from scipy.spatial import cKDTree
    tree = cKDTree(atom_dirs)
    d_pos, i_pos = tree.query(dirs)
    d_neg, i_neg = tree.query(-dirs)
    return np.where(d_neg < d_pos, i_neg, i_pos).astype(np.int64)


def connectome(rng: np.random.Generator, *, n_fibers: int, n_atoms: int,
               grid: Sequence[int], algorithm: str, active_frac: float,
               steps: Optional[Sequence[int]] = None) -> Subject:
    """Step ``n_fibers`` streamlines through ``grid`` and encode them.
    ``steps`` (mean, jitter) overrides the algorithm's step counts."""
    if algorithm not in TRACTOGRAPHY:
        raise ValueError(f"unknown tractography {algorithm!r}")
    curvature, mean_len, jitter = TRACTOGRAPHY[algorithm]
    if steps is not None:
        mean_len, jitter = steps
    g = np.asarray(grid, np.int64)
    n_voxels = int(np.prod(g))
    pos = rng.uniform(2.0, g - 2.0, size=(n_fibers, 3))
    d = rng.normal(size=(n_fibers, 3))
    d /= np.linalg.norm(d, axis=1, keepdims=True)
    n_steps = np.maximum(
        4, np.trunc(rng.normal(mean_len, jitter, n_fibers)).astype(np.int64))
    s_max = int(n_steps.max())
    alive = np.ones(n_fibers, bool)
    voxel = np.zeros((s_max, n_fibers), np.int64)
    dirs = np.zeros((s_max, n_fibers, 3))
    valid = np.zeros((s_max, n_fibers), bool)
    for s in range(s_max):
        if curvature > 0:
            d = d + curvature * rng.normal(size=(n_fibers, 3))
            d /= np.linalg.norm(d, axis=1, keepdims=True)
        elif algorithm == "FACT":
            # axis-aligned steps (fiber assignment by continuous tracking)
            d = np.eye(3)[np.argmax(np.abs(d), axis=1)]
        pos = pos + STEP * d
        v = np.floor(pos).astype(np.int64)
        # a fiber stops at its step count or where it leaves the grid
        alive &= (s < n_steps) & (v >= 0).all(axis=1) & (v < g).all(axis=1)
        valid[s] = alive
        voxel[s] = (v[:, 0] * g[1] + v[:, 1]) * g[2] + v[:, 2]
        dirs[s] = d
    fiber_raw = np.broadcast_to(np.arange(n_fibers), (s_max, n_fibers))[valid]
    voxel_raw = voxel[valid]
    dirs_raw = dirs[valid]
    atom_raw = nearest_atoms(dirs_raw, fibonacci_sphere(n_atoms))
    # dedupe repeated (atom, voxel, fiber) triples, summing their lengths
    key = (atom_raw * n_voxels + voxel_raw) * n_fibers + fiber_raw
    uniq, inv = np.unique(key, return_inverse=True)
    values = np.bincount(inv.reshape(-1), minlength=uniq.size) * STEP
    w_true = rng.uniform(0.0, 1.0, n_fibers)
    w_true[rng.uniform(size=n_fibers) > active_frac] = 0.0
    return Subject(
        atoms=((uniq // n_fibers) // n_voxels).astype(np.int32),
        voxels=((uniq // n_fibers) % n_voxels).astype(np.int32),
        fibers=(uniq % n_fibers).astype(np.int32),
        values=values.astype(np.float32),
        n_atoms=n_atoms, n_voxels=n_voxels, n_fibers=n_fibers,
        grid=tuple(int(x) for x in g), w_true=w_true.astype(np.float32))


def make_signal(subject: Subject, d: np.ndarray, noise: float,
                rng: np.random.Generator) -> None:
    """``b = M w_true + noise``, with ``M w_true`` from the reference DSC."""
    from bench import reference
    clean = np.asarray(reference.dsc(reference.blocked(subject), d,
                                     subject.w_true))
    subject.b = (clean + noise * rng.standard_normal(
        clean.shape, dtype=np.float32)).astype(np.float32)


def subjects(config: dict, count: int, seed: int) -> List[Subject]:
    """``count`` subjects of one configuration, each from its own stream
    of the seed: the same seed gives the same subjects."""
    d = dictionary(config["n_atoms"], config["n_theta"])
    streams = np.random.SeedSequence(seed % 2 ** 64).spawn(count)
    out = []
    for stream in streams:
        rng = np.random.default_rng(stream)
        s = connectome(rng, n_fibers=config["n_fibers"],
                       n_atoms=config["n_atoms"], grid=config["grid"],
                       algorithm=config["tractography"],
                       active_frac=config["active_frac"],
                       steps=config.get("fiber_steps"))
        make_signal(s, d, config["noise"], rng)
        out.append(s)
    return out
