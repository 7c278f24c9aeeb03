"""The plain reference against the program's own SpMVs and solver, on the
CPU at a small size (the reference takes nothing from the program; this
only shows both implement the same equations)."""
import numpy as np
import pytest

from bench import gen, reference, traffic

SMALL = dict(n_theta=96, n_atoms=96, n_fibers=600, grid=[14, 14, 14],
             tractography="PROB", active_frac=0.35, noise=0.01)


@pytest.fixture(scope="module")
def subject():
    return gen.subjects(SMALL, 1, 21)[0]


@pytest.fixture(scope="module")
def program(subject):
    import jax.numpy as jnp
    from repro.core.std import PhiTensor
    phi = PhiTensor(atoms=jnp.asarray(subject.atoms),
                    voxels=jnp.asarray(subject.voxels),
                    fibers=jnp.asarray(subject.fibers),
                    values=jnp.asarray(subject.values),
                    n_atoms=subject.n_atoms, n_voxels=subject.n_voxels,
                    n_fibers=subject.n_fibers)
    return phi, jnp.asarray(gen.dictionary(96, 96))


def _f64_dsc(s, d, w):
    y = np.zeros((s.n_voxels, d.shape[1]))
    np.add.at(y, s.voxels, d[s.atoms].astype(np.float64)
              * (w[s.fibers] * s.values)[:, None])
    return y


def test_dsc_and_wc_match_core_spmv(subject, program):
    from repro.core import spmv
    phi, d = program
    rng = np.random.default_rng(0)
    w = rng.uniform(size=subject.n_fibers).astype(np.float32)
    y = rng.standard_normal((subject.n_voxels, 96)).astype(np.float32)
    blk = reference.blocked(subject)
    ours_y, ours_w = reference.dsc(blk, d, w), reference.wc(blk, d, y)
    # float32 sums in different orders: 1e-5 of the largest magnitude
    np.testing.assert_allclose(ours_y, spmv.dsc_naive(phi, d, w),
                               atol=1e-5 * np.abs(ours_y).max())
    np.testing.assert_allclose(ours_w, spmv.wc_naive(phi, d, y),
                               atol=1e-5 * np.abs(ours_w).max())
    truth = _f64_dsc(subject, np.asarray(d, np.float64), w)
    np.testing.assert_allclose(ours_y, truth, atol=1e-6 * np.abs(truth).max())


def test_blocks_span_several_and_padding_is_inert(subject, program, monkeypatch):
    _, d = program
    w = np.ones(subject.n_fibers, np.float32)
    whole = np.asarray(reference.dsc(reference.blocked(subject), d, w))
    monkeypatch.setattr(reference, "BLOCK", 1000)   # several blocks + a tail
    blk = reference.blocked(subject)
    assert blk.values.shape[0] > 1
    np.testing.assert_allclose(reference.dsc(blk, d, w), whole,
                               atol=1e-6 * np.abs(whole).max())


def test_sbbnnls_follows_the_program_solver(subject, program):
    from repro.core import spmv
    from repro.core.sbbnnls import sbbnnls_run
    from jax.tree_util import Partial
    phi, d = program
    w0 = np.ones(subject.n_fibers, np.float32)
    w, losses = reference.sbbnnls(reference.blocked(subject), d, subject.b,
                                  w0, 30)
    pw, plosses = sbbnnls_run(Partial(spmv.dsc_naive, phi, d),
                              Partial(spmv.wc_naive, phi, d),
                              subject.b, w0, 30)
    assert np.all(np.asarray(w) >= 0)
    assert losses[-1] < 0.01 * losses[0]
    # float32 in different orders over 30 iterations
    np.testing.assert_allclose(losses, plosses.reshape(-1),
                               atol=1e-3 * losses[0])
    np.testing.assert_allclose(w, pw.w, atol=1e-3 * np.abs(w).max())


def test_objective_is_half_the_squared_residual_in_float64(subject):
    d = gen.dictionary(96, 96)
    w = np.random.default_rng(3).uniform(size=subject.n_fibers)
    r = _f64_dsc(subject, d, w) - subject.b.astype(np.float64)
    want = 0.5 * float(np.sum(r * r))
    assert reference.objective(subject, d, w) == pytest.approx(want,
                                                               rel=1e-12)
    # the float32 reference's DSC agrees to its own rounding
    y32 = np.asarray(reference.dsc(reference.blocked(subject), d, w))
    r32 = y32 - subject.b
    assert 0.5 * float(np.sum(r32.astype(np.float64) ** 2)) == (
        pytest.approx(want, rel=1e-5))


def test_lesion_edit_matches_science_lesion(subject):
    import jax.numpy as jnp
    from repro.data.dmri import LifeProblem
    from repro.core.std import PhiTensor
    from repro.science.lesion import lesion_problem
    bundle = traffic.bundles(subject, size=40, count=1, seed=5)[0]
    ours = reference.lesion(subject, bundle)
    phi = PhiTensor(*(jnp.asarray(getattr(subject, f)) for f in
                      ("atoms", "voxels", "fibers", "values")),
                    n_atoms=subject.n_atoms, n_voxels=subject.n_voxels,
                    n_fibers=subject.n_fibers)
    theirs = lesion_problem(LifeProblem(
        phi=phi, dictionary=jnp.zeros((96, 96)), b=jnp.asarray(subject.b),
        w_true=jnp.asarray(subject.w_true), stats={}, grid=subject.grid),
        bundle).phi
    for f in ("atoms", "voxels", "fibers", "values"):
        np.testing.assert_array_equal(getattr(ours, f),
                                      np.asarray(getattr(theirs, f)))
    assert not np.isin(ours.fibers, bundle).any()
    assert ours.n_fibers == subject.n_fibers


def test_bundles_are_disjoint_and_coherent(subject):
    bundles = traffic.bundles(subject, size=30, count=4, seed=9)
    flat = np.concatenate(bundles)
    assert flat.size == np.unique(flat).size == 120
    assert all(np.isin(b, subject.fibers).all() for b in bundles)
