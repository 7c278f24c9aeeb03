"""Pallas kernels (interpret mode) vs pure-jnp oracles — shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

pytestmark = pytest.mark.slow        # Pallas interpret sweeps

from repro.core import spmv
from repro.core.inspector import plan_tiles
from repro.core.restructure import sort_by_host
from repro.core.std import PhiTensor, make_dictionary
from repro.data.dmri import synth_connectome
from repro.kernels import ops as kops
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.ref import moe_gmm_ref


def _problem(nc, na, nv, nf, seed):
    r = np.random.default_rng(seed)
    return PhiTensor(
        atoms=jnp.asarray(r.integers(0, na, nc), jnp.int32),
        voxels=jnp.asarray(r.integers(0, nv, nc), jnp.int32),
        fibers=jnp.asarray(r.integers(0, nf, nc), jnp.int32),
        values=jnp.asarray(r.normal(size=nc), jnp.float32),
        n_atoms=na, n_voxels=nv, n_fibers=nf)


@pytest.mark.parametrize("nc,nv,nf,c_tile,row_tile", [
    (50, 40, 30, 16, 4),
    (513, 100, 64, 64, 8),
    (1000, 17, 23, 128, 8),      # many coeffs per row
    (7, 300, 200, 32, 16),       # sparse rows
])
@pytest.mark.parametrize("n_theta", [8, 96])
def test_dsc_kernel_shapes(nc, nv, nf, c_tile, row_tile, n_theta):
    phi = _problem(nc, 12, nv, nf, seed=nc + n_theta)
    d = make_dictionary(12, n_theta)
    w = jnp.asarray(np.random.default_rng(1).uniform(size=nf), jnp.float32)
    phi_v, _ = sort_by_host(phi, "voxel")
    plan = plan_tiles(np.asarray(phi_v.voxels), nv, c_tile=c_tile,
                      row_tile=row_tile)
    mv = kops.make_dsc(phi_v, d, plan)
    want = spmv.dsc_naive(phi, d, w)
    np.testing.assert_allclose(np.asarray(mv(w)), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("nc,nv,nf,c_tile,row_tile", [
    (50, 40, 30, 16, 8),
    (513, 100, 64, 64, 8),
    (600, 25, 11, 128, 8),
])
def test_wc_kernel_shapes(nc, nv, nf, c_tile, row_tile):
    phi = _problem(nc, 12, nv, nf, seed=7 * nc)
    d = make_dictionary(12, 16)
    y = jnp.asarray(np.random.default_rng(2).normal(size=(nv, 16)), jnp.float32)
    phi_f, _ = sort_by_host(phi, "fiber")
    plan = plan_tiles(np.asarray(phi_f.fibers), nf, c_tile=c_tile,
                      row_tile=row_tile)
    rv = kops.make_wc(phi_f, d, plan)
    want = spmv.wc_naive(phi, d, y)
    np.testing.assert_allclose(np.asarray(rv(y)), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dsc_kernel_dtypes(dtype):
    phi = _problem(200, 12, 50, 40, seed=3)
    d = make_dictionary(12, 16, dtype=dtype)
    phi = phi.astype(dtype)
    w = jnp.asarray(np.random.default_rng(1).uniform(size=40), dtype)
    phi_v, _ = sort_by_host(phi, "voxel")
    plan = plan_tiles(np.asarray(phi_v.voxels), 50, c_tile=64, row_tile=8)
    mv = kops.make_dsc(phi_v, d, plan)
    want = spmv.dsc_naive(phi.astype(jnp.float32),
                          d.astype(jnp.float32), w.astype(jnp.float32))
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(mv(w), np.float32),
                               np.asarray(want), rtol=tol, atol=tol)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 400), st.integers(2, 60), st.integers(2, 40),
       st.integers(0, 1000))
def test_property_dsc_kernel(nc, nv, nf, seed):
    phi = _problem(nc, 8, nv, nf, seed)
    d = make_dictionary(8, 8)
    w = jnp.asarray(np.random.default_rng(seed).uniform(size=nf), jnp.float32)
    phi_v, _ = sort_by_host(phi, "voxel")
    plan = plan_tiles(np.asarray(phi_v.voxels), nv, c_tile=32, row_tile=8)
    mv = kops.make_dsc(phi_v, d, plan)
    np.testing.assert_allclose(
        np.asarray(mv(w)), np.asarray(spmv.dsc_naive(phi, d, w)),
        rtol=1e-3, atol=1e-3)


def test_kernel_on_synthetic_connectome(tiny_problem):
    """End-to-end kernel executor on tractography-shaped data."""
    p = tiny_problem
    phi_v, _ = sort_by_host(p.phi, "voxel")
    phi_f, _ = sort_by_host(p.phi, "fiber")
    dsc_plan = plan_tiles(np.asarray(phi_v.voxels), p.phi.n_voxels,
                          c_tile=128, row_tile=8)
    wc_plan = plan_tiles(np.asarray(phi_f.fibers), p.phi.n_fibers,
                         c_tile=128, row_tile=8)
    mv = kops.make_dsc(phi_v, p.dictionary, dsc_plan)
    rv = kops.make_wc(phi_f, p.dictionary, wc_plan)
    w = jnp.ones((p.phi.n_fibers,), jnp.float32)
    y = mv(w)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(spmv.dsc_naive(p.phi, p.dictionary, w)),
        rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(rv(y)), np.asarray(spmv.wc_naive(p.phi, p.dictionary, y)),
        rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("E,d,f,tiles,t_tile,f_tile", [
    (4, 32, 64, 8, 16, 64),
    (2, 16, 32, 4, 8, 32),
    (8, 64, 128, 16, 32, 128),
])
def test_moe_gmm_kernel(E, d, f, tiles, t_tile, f_tile):
    r = np.random.default_rng(E + d)
    xs = jnp.asarray(r.normal(size=(tiles * t_tile, d)), jnp.float32)
    wexp = jnp.asarray(r.normal(size=(E, d, f)), jnp.float32)
    eot = jnp.asarray(r.integers(0, E, size=(tiles,)), jnp.int32)
    out = moe_gmm(eot, xs, wexp, t_tile=t_tile, f_tile=f_tile, interpret=True)
    ref = moe_gmm_ref(xs.reshape(tiles, t_tile, d), wexp, eot).reshape(-1, f)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
