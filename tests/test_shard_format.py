"""ShardPhi partitioned layout: round-trips, partition invariants, inert
padding (DESIGN.md §9).

The pure-numpy references over the stacked cell arrays are what lets multi-cell
layouts (R*C > 1) be exercised in a single-device test process — the
shard_map executors themselves are covered by test_conformance.py.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.inspector import run_lengths
from repro.core.std import PhiTensor
from repro.formats import FORMATS, canonical_triples
from repro.formats.shard import (CELL_FORMATS, ShardPhi, dsc_reference,
                                 partition_cuts, wc_reference)


@st.composite
def small_phi(draw):
    nc = draw(st.integers(1, 400))
    nv = draw(st.integers(1, 40))
    nf = draw(st.integers(1, 24))
    na = draw(st.integers(1, 8))
    skewed = draw(st.booleans())
    seed = draw(st.integers(0, 2**31 - 1))
    r = np.random.default_rng(seed)
    voxels = r.integers(0, nv, nc)
    fibers = r.integers(0, nf, nc)
    if skewed:
        # concentrate most coefficients on one id per mode — the regime
        # where an equal-nnz cut can land at coefficient offset 0 and the
        # snapping/monotonicity corner cases live
        voxels[: (6 * nc) // 10] = int(r.integers(0, nv))
        fibers[: (6 * nc) // 10] = int(r.integers(0, nf))
    return PhiTensor(
        atoms=jnp.asarray(r.integers(0, na, nc), jnp.int32),
        voxels=jnp.asarray(voxels, jnp.int32),
        fibers=jnp.asarray(fibers, jnp.int32),
        values=jnp.asarray(r.normal(size=nc).astype(np.float32)),
        n_atoms=na, n_voxels=nv, n_fibers=nf)


def _assert_same_multiset(a: PhiTensor, b: PhiTensor):
    for x, y in zip(canonical_triples(a), canonical_triples(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ----------------------------------------------------------------------------
# round-trip: encode/decode preserves the coefficient multiset exactly
# ----------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(small_phi(), st.sampled_from(["dsc", "wc"]),
       st.sampled_from(CELL_FORMATS), st.integers(1, 4), st.integers(1, 4))
def test_shard_roundtrip_exact(phi, op, cell_format, R, C):
    sp = ShardPhi.encode(phi, op=op, cell_format=cell_format, R=R, C=C,
                         row_tile=4, slot_tile=8)
    assert sp.n_coeffs == phi.n_coeffs
    _assert_same_multiset(phi, sp.decode())


def test_shard_is_not_a_leaf_format():
    """ShardPhi satisfies the PhiFormat contract but stays out of the
    selectable FORMATS registry — the registry citizens are the executors
    that consume it (shard / shard-sell)."""
    from repro.core.registry import REGISTRY
    assert "shard" not in FORMATS
    assert REGISTRY.consumes("shard") == "coo"
    assert REGISTRY.consumes("shard-sell") == "sell"
    assert REGISTRY.mesh_executor_for("coo") == "shard"
    assert REGISTRY.mesh_executor_for("sell") == "shard-sell"
    assert REGISTRY.mesh_executor_for("alto") is None


def test_encode_rejects_unknown_cell_format(tiny_problem):
    with pytest.raises(ValueError, match="cell format"):
        ShardPhi.encode(tiny_problem.phi, cell_format="csr")
    with pytest.raises(ValueError, match="positive"):
        partition_cuts(tiny_problem.phi, 0, 2)


def test_mesh_request_is_never_silently_dropped(tiny_problem):
    """A multi-cell mesh request either runs a sharded executor or raises —
    it must not fall back to a single-device solve (ISSUE 4 review fix)."""
    from repro.core.life import LifeConfig, LifeEngine
    # format="alto" has no sharded path -> refused outright
    with pytest.raises(ValueError, match="mesh executor"):
        LifeEngine(tiny_problem, LifeConfig(
            executor="opt", format="alto", shard_rows=2, shard_cols=1,
            plan_cache_dir=""))
    # default format="coo" with a single-device executor routes to `shard`;
    # on a host without enough devices that surfaces as a loud error
    # instead of a silent single-device run
    import jax
    n = len(jax.devices())
    cfg = LifeConfig(executor="opt", shard_rows=n + 1, shard_cols=1,
                     plan_cache_dir="")
    with pytest.raises(ValueError, match="devices"):
        LifeEngine(tiny_problem, cfg)
    # with enough devices the mesh request lands on the sharded executor
    ok = LifeEngine(tiny_problem, LifeConfig(
        executor="opt", shard_rows=1, shard_cols=1, format="coo",
        plan_cache_dir=""))
    assert ok.executor.name == "opt"    # 1x1 mesh request = no mesh request


# ----------------------------------------------------------------------------
# partition invariants: disjoint, covering, equal-nnz within sub-vector
# tolerance, snapped to id boundaries
# ----------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(small_phi(), st.integers(1, 6), st.integers(1, 6))
def test_partition_cuts_invariants(phi, R, C):
    plan = partition_cuts(phi, R, C)
    voxels = np.asarray(phi.voxels, np.int64)
    fibers = np.asarray(phi.fibers, np.int64)
    for cuts, n_ids, ids, k in ((plan.voxel_cuts, phi.n_voxels, voxels, R),
                                (plan.fiber_cuts, phi.n_fibers, fibers, C)):
        # id-space ranges are monotone and cover [0, n_ids) exactly —
        # disjointness and coverage of the cells follow
        assert cuts[0] == 0 and cuts[-1] == n_ids
        assert (np.diff(cuts) >= 0).all()
        # equal-nnz within sub-vector tolerance: no range exceeds the ideal
        # share by more than the largest run of one id (Figure 5b snapping)
        counts = np.asarray([np.sum((ids >= cuts[i]) & (ids < cuts[i + 1]))
                             for i in range(k)])
        assert counts.sum() == phi.n_coeffs
        largest_run = int(run_lengths(ids).max()) if ids.size else 0
        assert (counts <= ids.size / k + largest_run).all()
    # the (R x C) cells partition the coefficient set
    sp = ShardPhi.encode(phi, op="dsc", cell_format="coo", plan=plan)
    assert int(sp.cell_nnz.sum()) == phi.n_coeffs


def test_id_cuts_monotone_on_dominant_first_id():
    """Regression: an interior shard_boundaries cut at coefficient offset 0
    (the smallest id owns >= its shard's whole nnz share) must map to an
    empty leading range, not to a non-monotone n_ids boundary that sends
    later ids' contributions to never-written padded rows."""
    from repro.formats.shard import _id_cuts
    ids = np.sort(np.asarray([0] * 10 + [1, 2, 3], np.int64))
    cuts = _id_cuts(ids, 4, 4)
    assert (np.diff(cuts) >= 0).all(), cuts
    assert cuts[0] == 0 and cuts[-1] == 4
    # and the full sharded SpMV stays correct under that skew
    r = np.random.default_rng(0)
    phi = PhiTensor(
        atoms=jnp.asarray(r.integers(0, 4, 13), jnp.int32),
        voxels=jnp.asarray(ids, jnp.int32),
        fibers=jnp.asarray(r.integers(0, 5, 13), jnp.int32),
        values=jnp.asarray(r.normal(size=13).astype(np.float32)),
        n_atoms=4, n_voxels=4, n_fibers=5)
    d = r.normal(size=(4, 6)).astype(np.float32)
    w = r.uniform(0, 1, 5).astype(np.float32)
    from repro.core.spmv import dsc_naive
    want = np.asarray(dsc_naive(phi, jnp.asarray(d), jnp.asarray(w)))
    sp = ShardPhi.encode(phi, op="dsc", cell_format="coo", R=4, C=2)
    np.testing.assert_allclose(dsc_reference(sp, d, w), want,
                               rtol=1e-5, atol=1e-6)


# ----------------------------------------------------------------------------
# inert padding: value-0 slots never change DSC/WC results
# ----------------------------------------------------------------------------

def _inflate_coo(sp: ShardPhi, extra: int) -> ShardPhi:
    """Append `extra` all-zero padding slots to every cell."""
    pad = [(0, 0), (0, 0), (0, extra)]
    return dataclasses.replace(
        sp, arrays={k: np.pad(v, pad) for k, v in sp.arrays.items()})


def _inflate_sell(sp: ShardPhi) -> ShardPhi:
    """Grow every cell by one slot chunk and one row block of zeros."""
    arrays = dict(sp.arrays)
    pad = [(0, 0), (0, 0), (0, sp.row_tile), (0, sp.slot_tile)]
    for k in ("atoms", "others", "values"):
        arrays[k] = np.pad(arrays[k], pad)
    return dataclasses.replace(sp, arrays=arrays)


@settings(max_examples=15, deadline=None)
@given(small_phi(), st.sampled_from(CELL_FORMATS), st.integers(1, 3),
       st.integers(1, 3), st.integers(0, 2**31 - 1))
def test_padded_cells_are_inert(phi, cell_format, R, C, seed):
    """Inflating the per-cell padding (pure value-0 slots) leaves both ops
    bit-identical — the §4.2.1.2 sync-free invariant the sharded layouts
    rely on."""
    r = np.random.default_rng(seed)
    d = r.normal(size=(phi.n_atoms, 6)).astype(np.float32)
    w = r.uniform(0, 1, phi.n_fibers).astype(np.float32)
    y = r.normal(size=(phi.n_voxels, 6)).astype(np.float32)

    sp_dsc = ShardPhi.encode(phi, op="dsc", cell_format=cell_format, R=R,
                             C=C, row_tile=4, slot_tile=8)
    sp_wc = ShardPhi.encode(phi, op="wc", cell_format=cell_format, R=R,
                            C=C, row_tile=4, slot_tile=8)
    inflate = (_inflate_sell if cell_format == "sell"
               else lambda s: _inflate_coo(s, 7))
    np.testing.assert_array_equal(dsc_reference(sp_dsc, d, w),
                                  dsc_reference(inflate(sp_dsc), d, w))
    np.testing.assert_array_equal(wc_reference(sp_wc, d, y),
                                  wc_reference(inflate(sp_wc), d, y))


@settings(max_examples=10, deadline=None)
@given(small_phi(), st.integers(1, 3), st.integers(1, 3),
       st.integers(1, 50), st.integers(0, 2**31 - 1))
def test_zero_value_coefficients_are_inert(phi, R, C, n_zero, seed):
    """Appending explicit value-0 coefficients (anywhere in the tensor)
    never changes either op — they may shift the equal-nnz boundaries, so
    the comparison runs in float64 where the re-partitioned summation
    order is exact to ~1e-12."""
    r = np.random.default_rng(seed)
    aug = PhiTensor(
        atoms=jnp.concatenate([phi.atoms, jnp.asarray(
            r.integers(0, phi.n_atoms, n_zero), jnp.int32)]),
        voxels=jnp.concatenate([phi.voxels, jnp.asarray(
            r.integers(0, phi.n_voxels, n_zero), jnp.int32)]),
        fibers=jnp.concatenate([phi.fibers, jnp.asarray(
            r.integers(0, phi.n_fibers, n_zero), jnp.int32)]),
        values=jnp.concatenate([phi.values,
                                jnp.zeros((n_zero,), phi.values.dtype)]),
        n_atoms=phi.n_atoms, n_voxels=phi.n_voxels, n_fibers=phi.n_fibers)
    d = r.normal(size=(phi.n_atoms, 6)).astype(np.float64)
    w = r.uniform(0, 1, phi.n_fibers).astype(np.float64)
    y = r.normal(size=(phi.n_voxels, 6)).astype(np.float64)
    for cell_format in CELL_FORMATS:
        a = ShardPhi.encode(phi, op="dsc", cell_format=cell_format, R=R,
                            C=C, row_tile=4, slot_tile=8)
        b = ShardPhi.encode(aug, op="dsc", cell_format=cell_format, R=R,
                            C=C, row_tile=4, slot_tile=8)
        np.testing.assert_allclose(dsc_reference(a, d, w),
                                   dsc_reference(b, d, w),
                                   rtol=1e-10, atol=1e-10)
        aw = ShardPhi.encode(phi, op="wc", cell_format=cell_format, R=R,
                             C=C, row_tile=4, slot_tile=8)
        bw = ShardPhi.encode(aug, op="wc", cell_format=cell_format, R=R,
                             C=C, row_tile=4, slot_tile=8)
        np.testing.assert_allclose(wc_reference(aw, d, y),
                                   wc_reference(bw, d, y),
                                   rtol=1e-10, atol=1e-10)


# ----------------------------------------------------------------------------
# accounting
# ----------------------------------------------------------------------------

def test_padding_overhead_and_nbytes(tiny_problem):
    phi = tiny_problem.phi
    for cell_format in CELL_FORMATS:
        sp = ShardPhi.encode(phi, op="dsc", cell_format=cell_format, R=2,
                             C=2, slot_tile=8)
        assert sp.padding_overhead >= 0.0
        assert sp.nbytes > 0
        allocated = sp.arrays["values"].size
        assert allocated == pytest.approx(
            (1.0 + sp.padding_overhead) * sp.n_coeffs, rel=1e-6)


def test_references_match_dense_oracle(tiny_problem, tiny_dense, rng):
    """Multi-cell reference SpMVs agree with the dense oracle (the same
    contract the shard_map executors are held to in test_conformance)."""
    p = tiny_problem
    m = np.asarray(tiny_dense, np.float64)
    n_theta = p.dictionary.shape[1]
    w = rng.uniform(0, 1, p.phi.n_fibers).astype(np.float32)
    y = rng.normal(size=(p.phi.n_voxels, n_theta)).astype(np.float32)
    for cell_format in CELL_FORMATS:
        sp = ShardPhi.encode(p.phi, op="dsc", cell_format=cell_format,
                             R=3, C=2, slot_tile=8)
        got = dsc_reference(sp, p.dictionary, w).astype(np.float64)
        np.testing.assert_allclose(got.reshape(-1),
                                   m @ w.astype(np.float64),
                                   rtol=2e-4, atol=2e-5)
        spw = ShardPhi.encode(p.phi, op="wc", cell_format=cell_format,
                              R=3, C=2, slot_tile=8)
        gotw = wc_reference(spw, p.dictionary, y).astype(np.float64)
        np.testing.assert_allclose(gotw, m.T @ y.astype(np.float64).reshape(-1),
                                   rtol=2e-4, atol=2e-5)
