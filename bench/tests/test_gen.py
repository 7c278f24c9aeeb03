"""The vectorised generator against the program's loop generator."""
import numpy as np
import pytest

from bench import gen

SMALL = dict(n_theta=96, n_atoms=96, n_fibers=1500, grid=[24, 24, 24],
             tractography="PROB", active_frac=0.35, noise=0.01)


def _stats(atoms, voxels, fibers, n_fibers, n_voxels):
    return (atoms.size / n_fibers,
            np.unique(voxels).size / n_voxels,
            np.unique(fibers).size / n_fibers)


@pytest.mark.parametrize("algorithm", ["PROB", "DET", "FACT"])
def test_statistics_match_the_loop_generator(algorithm):
    from repro.data.dmri import synth_connectome
    cfg = dict(SMALL, tractography=algorithm)
    ours, loop = [], []
    for seed in range(3):
        s = gen.subjects(cfg, 1, seed)[0]
        ours.append(_stats(s.atoms, s.voxels, s.fibers, s.n_fibers,
                           s.n_voxels))
        p = synth_connectome(n_fibers=1500, grid=(24, 24, 24),
                             algorithm=algorithm, seed=seed)
        loop.append(_stats(*(np.asarray(a) for a in (
            p.phi.atoms, p.phi.voxels, p.phi.fibers)),
            p.phi.n_fibers, p.phi.n_voxels))
    # coefficients per fiber, voxels touched, fibers with coefficients:
    # means over three subjects of 1,500 fibers, within 5%
    np.testing.assert_allclose(np.mean(ours, 0), np.mean(loop, 0),
                               rtol=0.05)


def test_dedupe_invariants():
    s = gen.subjects(SMALL, 1, 4)[0]
    key = ((s.atoms.astype(np.int64) * s.n_voxels + s.voxels) * s.n_fibers
           + s.fibers)
    assert np.all(np.diff(key) > 0), "triples unique and sorted"
    steps = s.values / gen.STEP
    assert np.all(steps >= 1) and np.allclose(steps, np.round(steps))
    assert s.atoms.min() >= 0 and s.atoms.max() < s.n_atoms
    assert s.voxels.min() >= 0 and s.voxels.max() < s.n_voxels
    assert s.fibers.min() >= 0 and s.fibers.max() < s.n_fibers
    assert s.b.shape == (s.n_voxels, 96) and s.b.dtype == np.float32
    assert 0.25 < np.mean(s.w_true > 0) < 0.45


def test_same_seed_same_subjects_large_seed():
    seed = 2 ** 31 + 977
    a = gen.subjects(SMALL, 2, seed)
    b = gen.subjects(SMALL, 2, seed)
    for x, y in zip(a, b):
        for f in ("atoms", "voxels", "fibers", "values", "w_true", "b"):
            np.testing.assert_array_equal(getattr(x, f), getattr(y, f))
    assert a[0].n_coeffs != a[1].n_coeffs or not np.array_equal(
        a[0].fibers, a[1].fibers)


def test_dictionary_matches_the_acquisition():
    from repro.core.std import make_dictionary
    np.testing.assert_allclose(gen.dictionary(96, 96),
                               np.asarray(make_dictionary(96, 96)),
                               rtol=1e-6, atol=1e-7)


def test_nearest_atoms_is_the_largest_absolute_dot():
    rng = np.random.default_rng(3)
    d = rng.normal(size=(4000, 3))
    d /= np.linalg.norm(d, axis=1, keepdims=True)
    for n_atoms in (96, 1160):
        atoms = gen.fibonacci_sphere(n_atoms)
        dots = np.abs(d @ atoms.T)
        got = gen.nearest_atoms(d, atoms)
        # the same atom, or one whose |dot| ties it to rounding
        np.testing.assert_allclose(dots[np.arange(len(d)), got],
                                   dots.max(axis=1), rtol=0, atol=1e-12)


def test_fiber_steps_set_the_coefficients_per_fiber():
    short = gen.subjects(dict(SMALL, fiber_steps=[12, 4]), 1, 5)[0]
    long = gen.subjects(SMALL, 1, 5)[0]
    assert short.n_coeffs < 0.7 * long.n_coeffs
