"""F-COO linearization + segment-scan primitive: property suite
(DESIGN.md §11; structure mirrors test_shard_format.py).

The pure-jnp references exercise the layout's semantics; the Pallas kernel
pair itself is additionally held to the dense oracle by the conformance
matrix (test_conformance.py) the moment ``kernel-fcoo`` registers.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.std import PhiTensor
from repro.formats import canonical_triples
from repro.formats.fcoo import (FcooPhi, chunk_segment_map, dsc_reference,
                                wc_reference)


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
        # concentrate most coefficients on one id per mode — long runs
        # spanning several chunks, the chunk-boundary combine's hard case
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


def _assert_same_multiset_unordered(a: PhiTensor, b: PhiTensor):
    """Multiset equality with values in the sort key — canonical_triples
    alone leaves duplicate triples in input-relative order, which a
    shuffled input legitimately changes."""
    def key(p):
        at = np.asarray(p.atoms, np.int64)
        v = np.asarray(p.voxels, np.int64)
        f = np.asarray(p.fibers, np.int64)
        vals = np.asarray(p.values)
        order = np.lexsort((vals, f, v, at))
        return at[order], v[order], f[order], vals[order]
    for x, y in zip(key(a), key(b)):
        np.testing.assert_array_equal(x, y)


def _np_dsc(fc: FcooPhi, d: np.ndarray, w: np.ndarray) -> np.ndarray:
    """float64 scatter-add DSC over the linearized stream (jax runs fp32
    here, so exactness claims go through numpy)."""
    scaled = w[fc.fibers] * fc.values.astype(np.float64)
    y = np.zeros((fc.n_voxels, d.shape[1]))
    np.add.at(y, fc.voxels, d[fc.atoms] * scaled[:, None])
    return y


def _np_wc(fc: FcooPhi, d: np.ndarray, y: np.ndarray) -> np.ndarray:
    dots = (d[fc.atoms] * y[fc.voxels]).sum(-1) * fc.values.astype(np.float64)
    w = np.zeros(fc.n_fibers)
    np.add.at(w, fc.fibers, dots)
    return w


# ----------------------------------------------------------------------------
# the segment-scan primitive (host side of the kernels' one-hot reduction)
# ----------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(1, 8), st.integers(1, 32), st.integers(0, 2**31 - 1),
       st.sampled_from([4, 8, 16]), st.booleans())
def test_chunk_segment_map_invariants(n_chunks, c_tile, seed, seg_tile,
                                      sort_ids):
    r = np.random.default_rng(seed)
    n_rows = int(r.integers(1, 50))
    ids = r.integers(0, n_rows, n_chunks * c_tile)
    if sort_ids:
        ids = np.sort(ids)                # sortedness is NOT required
    seg_rows, ranks, k = chunk_segment_map(ids, c_tile, seg_tile, n_rows)
    assert k % seg_tile == 0 and seg_rows.shape == (n_chunks, k)
    ranks2 = ranks.reshape(n_chunks, c_tile)
    ids2 = ids.reshape(n_chunks, c_tile)
    # ranks: chunk-local prefix sum of the segment flags
    assert (ranks2[:, 0] == 0).all()
    flags = (ids2[:, 1:] != ids2[:, :-1]).astype(np.int32)
    np.testing.assert_array_equal(np.diff(ranks2, axis=1), flags)
    # every slot's segment names exactly its own output row
    np.testing.assert_array_equal(
        seg_rows[np.repeat(np.arange(n_chunks), c_tile), ranks], ids)
    # entries past a chunk's last segment hold the dummy row
    for t in range(n_chunks):
        assert (seg_rows[t, ranks2[t, -1] + 1:] == n_rows).all()


def test_chunk_segment_map_rejects_ragged_stream():
    with pytest.raises(ValueError, match="c_tile"):
        chunk_segment_map(np.zeros(10, np.int64), 4, 8, 3)


def test_chunk_segment_map_empty_stream():
    seg_rows, ranks, k = chunk_segment_map(np.zeros(0, np.int64), 4, 8, 3)
    assert seg_rows.shape == (0, 8) and ranks.size == 0 and k == 8


def test_segment_scan_matches_scatter_sum():
    """The chunked one-hot segment reduction + seg_rows scatter (exactly
    the kernel dataflow, in numpy) equals a direct scatter-add — including
    runs that span chunk boundaries."""
    r = np.random.default_rng(7)
    n_rows, c_tile, seg_tile = 9, 8, 4
    ids = np.sort(r.integers(0, n_rows, 40))
    vals = r.normal(size=40)
    seg_rows, ranks, k = chunk_segment_map(ids, c_tile, seg_tile, n_rows)
    out = np.zeros(n_rows + 1)
    for t in range(ids.size // c_tile):
        sl = slice(t * c_tile, (t + 1) * c_tile)
        onehot = (np.arange(k)[:, None] == ranks[sl][None, :])
        np.add.at(out, seg_rows[t], onehot @ vals[sl])
    want = np.zeros(n_rows + 1)
    np.add.at(want, ids, vals)
    np.testing.assert_allclose(out[:n_rows], want[:n_rows], rtol=1e-12)


# ----------------------------------------------------------------------------
# format properties
# ----------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(small_phi(), st.sampled_from([16, 64]), st.sampled_from([8, 16]))
def test_roundtrip_exact(phi, c_tile, seg_tile):
    fc = FcooPhi.encode(phi, c_tile=c_tile, seg_tile=seg_tile)
    assert fc.n_coeffs == phi.n_coeffs
    assert fc.atoms.size % c_tile == 0
    _assert_same_multiset(phi, fc.decode())


@settings(max_examples=15, deadline=None)
@given(small_phi(), st.integers(0, 2**31 - 1))
def test_permutation_invariance_of_input_order(phi, seed):
    """Encoding any permutation of the input triples yields the same
    results — the linearization is a total order over the triples.  With
    duplicate triples the within-segment summation *order* may differ, so
    results are compared to fp tolerance; layouts of deduplicated streams
    are compared bit-exactly below."""
    r = np.random.default_rng(seed)
    perm = r.permutation(phi.n_coeffs)
    shuffled = phi.take(jnp.asarray(perm))
    a = FcooPhi.encode(phi, c_tile=32, seg_tile=8)
    b = FcooPhi.encode(shuffled, c_tile=32, seg_tile=8)
    _assert_same_multiset_unordered(a.decode(), b.decode())
    d = jnp.asarray(r.normal(size=(phi.n_atoms, 6)).astype(np.float32))
    w = jnp.asarray(r.uniform(0, 1, phi.n_fibers).astype(np.float32))
    y = jnp.asarray(r.normal(size=(phi.n_voxels, 6)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(dsc_reference(a, d, w)),
                               np.asarray(dsc_reference(b, d, w)),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(wc_reference(a, d, y)),
                               np.asarray(wc_reference(b, d, y)),
                               rtol=1e-5, atol=1e-6)


def test_permutation_invariance_bitwise_on_unique_triples():
    """With all-distinct triples the layout itself (every resident array)
    is identical under any input permutation."""
    r = np.random.default_rng(3)
    nv, nf, na = 7, 5, 4
    trip = np.array([(v, f, a) for v in range(nv) for f in range(nf)
                     for a in range(na)], np.int64)
    trip = trip[r.permutation(len(trip))[:60]]
    phi = PhiTensor(
        atoms=jnp.asarray(trip[:, 2], jnp.int32),
        voxels=jnp.asarray(trip[:, 0], jnp.int32),
        fibers=jnp.asarray(trip[:, 1], jnp.int32),
        values=jnp.asarray(r.normal(size=60).astype(np.float32)),
        n_atoms=na, n_voxels=nv, n_fibers=nf)
    a = FcooPhi.encode(phi, c_tile=16, seg_tile=8)
    b = FcooPhi.encode(phi.take(jnp.asarray(r.permutation(60))),
                       c_tile=16, seg_tile=8)
    for fld in ("atoms", "voxels", "fibers", "values", "wc_perm",
                "dsc_ranks", "wc_ranks", "seg_rows_dsc", "seg_rows_wc"):
        np.testing.assert_array_equal(getattr(a, fld), getattr(b, fld),
                                      err_msg=fld)


@settings(max_examples=15, deadline=None)
@given(small_phi(), st.integers(1, 30), st.integers(0, 2**31 - 1))
def test_duplicate_indices_accumulate(phi, n_dup, seed):
    """Repeating existing triples with extra values accumulates (never
    overwrites) — equal to the dense operator of the concatenated tensor.
    The layout semantics are checked in float64 numpy (exact to
    summation-order noise ~1e-12); the jnp references confirm at fp32."""
    r = np.random.default_rng(seed)
    pick = r.integers(0, phi.n_coeffs, n_dup)
    aug = PhiTensor(
        atoms=jnp.concatenate([phi.atoms, phi.atoms[pick]]),
        voxels=jnp.concatenate([phi.voxels, phi.voxels[pick]]),
        fibers=jnp.concatenate([phi.fibers, phi.fibers[pick]]),
        values=jnp.concatenate(
            [phi.values, jnp.asarray(r.normal(size=n_dup), phi.values.dtype)]),
        n_atoms=phi.n_atoms, n_voxels=phi.n_voxels, n_fibers=phi.n_fibers)
    fc = FcooPhi.encode(aug, c_tile=32, seg_tile=8)
    d64 = r.normal(size=(phi.n_atoms, 6))
    w64 = r.uniform(0, 1, phi.n_fibers)
    m = np.zeros((phi.n_voxels * 6, phi.n_fibers))
    for a, v, f, val in zip(np.asarray(aug.atoms), np.asarray(aug.voxels),
                            np.asarray(aug.fibers),
                            np.asarray(aug.values, np.float64)):
        m[v * 6:(v + 1) * 6, f] += d64[a] * val
    np.testing.assert_allclose(_np_dsc(fc, d64, w64).reshape(-1), m @ w64,
                               rtol=1e-9, atol=1e-9)
    d = jnp.asarray(d64.astype(np.float32))
    w = jnp.asarray(w64.astype(np.float32))
    got = np.asarray(dsc_reference(fc, d, w), np.float64).reshape(-1)
    np.testing.assert_allclose(got, m @ w64, rtol=2e-4, atol=2e-5)


def test_empty_segment_rows_are_exact_zeros():
    """Output rows no coefficient touches never appear in any segment map,
    so they come out as exact (bitwise) zeros from both ops."""
    r = np.random.default_rng(11)
    nv, nf = 20, 15
    phi = PhiTensor(                       # only even voxels / fibers < 5
        atoms=jnp.asarray(r.integers(0, 4, 50), jnp.int32),
        voxels=jnp.asarray(2 * r.integers(0, nv // 2, 50), jnp.int32),
        fibers=jnp.asarray(r.integers(0, 5, 50), jnp.int32),
        values=jnp.asarray(r.normal(size=50).astype(np.float32)),
        n_atoms=4, n_voxels=nv, n_fibers=nf)
    fc = FcooPhi.encode(phi, c_tile=16, seg_tile=8)
    touched_v = set(np.asarray(phi.voxels).tolist())
    touched_f = set(np.asarray(phi.fibers).tolist())
    assert set(fc.seg_rows_dsc.reshape(-1).tolist()) <= touched_v | {nv}
    assert set(fc.seg_rows_wc.reshape(-1).tolist()) <= touched_f | {nf}
    d = jnp.asarray(r.normal(size=(4, 6)).astype(np.float32))
    y_dsc = np.asarray(dsc_reference(fc, d, jnp.ones((nf,), jnp.float32)))
    w_wc = np.asarray(wc_reference(
        fc, d, jnp.asarray(r.normal(size=(nv, 6)).astype(np.float32))))
    for v in range(nv):
        if v not in touched_v:
            assert (y_dsc[v] == 0.0).all()
    for f in range(nf):
        if f not in touched_f:
            assert w_wc[f] == 0.0
    # and the kernel executors agree bit-for-bit on the untouched rows
    from repro.kernels.ops import make_fcoo_ops
    mv, rmv = make_fcoo_ops(fc, d)
    yk = np.asarray(mv(jnp.ones((nf,), jnp.float32)))
    for v in range(nv):
        if v not in touched_v:
            assert (yk[v] == 0.0).all()


@settings(max_examples=10, deadline=None)
@given(small_phi(), st.integers(1, 50), st.integers(0, 2**31 - 1))
def test_zero_value_coefficients_are_inert(phi, n_zero, seed):
    """Appending explicit value-0 coefficients (anywhere) never changes
    either op — they may shift chunk boundaries and segment counts, so the
    comparison runs in float64 numpy where re-chunked summation order is
    exact to ~1e-12."""
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
    d = r.normal(size=(phi.n_atoms, 6))
    w = r.uniform(0, 1, phi.n_fibers)
    y = r.normal(size=(phi.n_voxels, 6))
    a = FcooPhi.encode(phi, c_tile=32, seg_tile=8)
    b = FcooPhi.encode(aug, c_tile=32, seg_tile=8)
    np.testing.assert_allclose(_np_dsc(a, d, w), _np_dsc(b, d, w),
                               rtol=1e-10, atol=1e-10)
    np.testing.assert_allclose(_np_wc(a, d, y), _np_wc(b, d, y),
                               rtol=1e-10, atol=1e-10)


# ----------------------------------------------------------------------------
# kernels off the single resident copy + accounting
# ----------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(small_phi(), st.sampled_from([8, 32]), st.sampled_from([8, 16]),
       st.integers(0, 2**31 - 1))
def test_kernel_pair_matches_references(phi, c_tile, seg_tile, seed):
    """Both Pallas ops off one FcooPhi equal the pure-jnp references on
    arbitrary shapes — small c_tile forces many chunks, so runs spanning
    chunk boundaries (the scatter-add combine) are exercised hard."""
    from repro.kernels.ops import make_fcoo_ops
    r = np.random.default_rng(seed)
    d = jnp.asarray(r.normal(size=(phi.n_atoms, 6)).astype(np.float32))
    w = jnp.asarray(r.uniform(0, 1, phi.n_fibers).astype(np.float32))
    y = jnp.asarray(r.normal(size=(phi.n_voxels, 6)).astype(np.float32))
    fc = FcooPhi.encode(phi, c_tile=c_tile, seg_tile=seg_tile)
    mv, rmv = make_fcoo_ops(fc, d)
    np.testing.assert_allclose(np.asarray(mv(w)),
                               np.asarray(dsc_reference(fc, d, w)),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(rmv(y)),
                               np.asarray(wc_reference(fc, d, y)),
                               rtol=1e-5, atol=1e-6)


def test_empty_phi_encodes_and_runs():
    from repro.kernels.ops import make_fcoo_ops
    phi = PhiTensor(atoms=jnp.zeros((0,), jnp.int32),
                    voxels=jnp.zeros((0,), jnp.int32),
                    fibers=jnp.zeros((0,), jnp.int32),
                    values=jnp.zeros((0,), jnp.float32),
                    n_atoms=3, n_voxels=4, n_fibers=5)
    fc = FcooPhi.encode(phi, c_tile=16, seg_tile=8)
    assert fc.n_chunks == 0 and fc.nbytes == 0
    d = jnp.ones((3, 6), jnp.float32)
    mv, rmv = make_fcoo_ops(fc, d)
    assert (np.asarray(mv(jnp.ones((5,), jnp.float32))) == 0.0).all()
    assert (np.asarray(rmv(jnp.ones((4, 6), jnp.float32))) == 0.0).all()
    _assert_same_multiset(phi, fc.decode())


def test_one_copy_beats_two_sell_encodes(tiny_problem):
    """The residency claim on a real connectome: one fcoo copy, with every
    resident array counted, stays under 0.6x of SELL(DSC)+SELL(WC) — the
    same ratio benchmarks/check_regression.py gates on the bench shape."""
    from repro.formats.sell import SellPhi
    phi = tiny_problem.phi
    fc = FcooPhi.encode(phi)
    sell = (SellPhi.encode(phi, op="dsc").nbytes
            + SellPhi.encode(phi, op="wc").nbytes)
    assert fc.nbytes > 0
    assert fc.nbytes <= 0.6 * sell, (fc.nbytes, sell)
    assert fc.padding_overhead >= 0.0
    allocated = fc.values.size
    assert allocated == pytest.approx(
        (1.0 + fc.padding_overhead) * fc.n_coeffs, rel=1e-6)
