"""Sparse-format subsystem: round-trips, SELL kernels vs oracle, selection."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import spmv
from repro.core.inspector import phi_stats
from repro.core.restructure import compact_by_weight
from repro.core.std import PhiTensor, make_dictionary, materialize_dense
from repro.formats import (AltoPhi, CooPhi, SellPhi, canonical_triples,
                           format_names, get_format)
from repro.formats import select as fsel
from repro.formats.base import FormatPlan
from repro.formats.sell import dsc_reference, wc_reference


@st.composite
def coo(draw):
    nc = draw(st.integers(0, 300))
    na = draw(st.integers(1, 16))
    nv = draw(st.integers(1, 40))
    nf = draw(st.integers(1, 30))
    seed = draw(st.integers(0, 2**31 - 1))
    r = np.random.default_rng(seed)
    return PhiTensor(
        atoms=jnp.asarray(r.integers(0, na, nc), jnp.int32),
        voxels=jnp.asarray(r.integers(0, nv, nc), jnp.int32),
        fibers=jnp.asarray(r.integers(0, nf, nc), jnp.int32),
        values=jnp.asarray(r.normal(size=nc), jnp.float32),
        n_atoms=na, n_voxels=nv, n_fibers=nf), seed


def _assert_same_triples(got: PhiTensor, want: PhiTensor):
    for g, w in zip(canonical_triples(got), canonical_triples(want)):
        np.testing.assert_array_equal(g, w)


# ----------------------------------------------------------------------------
# Round-trips: every format reproduces the COO triples/values exactly
# ----------------------------------------------------------------------------

def test_registry_lists_formats():
    assert format_names() == ("alto", "coo", "fcoo", "sell")
    assert get_format("sell") is SellPhi
    with pytest.raises(ValueError):
        get_format("csr")


@settings(max_examples=15, deadline=None)
@given(coo(), st.sampled_from(["dsc", "wc"]))
def test_property_roundtrip_all_formats(case, op):
    phi, _ = case
    for name in format_names():
        enc = get_format(name).encode(phi, op=op)
        _assert_same_triples(enc.decode(), phi)
        assert enc.padding_overhead >= 0.0
        assert enc.nbytes > 0 or phi.n_coeffs == 0


def test_coo_roundtrip_preserves_order(tiny_problem):
    enc = CooPhi.encode(tiny_problem.phi, op="dsc")
    dec = enc.decode()
    np.testing.assert_array_equal(np.asarray(dec.atoms),
                                  np.asarray(tiny_problem.phi.atoms))
    np.testing.assert_array_equal(np.asarray(dec.values),
                                  np.asarray(tiny_problem.phi.values))


def test_alto_sort_and_compact(tiny_problem):
    enc = AltoPhi.encode(tiny_problem.phi)
    srt, order = enc.sort()
    assert np.all(np.diff(srt.lin.astype(np.uint64)) >= 0)
    _assert_same_triples(srt.decode(), tiny_problem.phi)
    np.testing.assert_array_equal(srt.fibers_of(),
                                  np.asarray(srt.decode().fibers))
    # compaction via the linearized fiber view == compact_by_weight
    w = np.zeros(tiny_problem.phi.n_fibers, np.float32)
    w[: len(w) // 3] = 1.0
    kept_enc = enc.compact(w[enc.fibers_of()] > 0)
    want = compact_by_weight(tiny_problem.phi, jnp.asarray(w))
    _assert_same_triples(kept_enc.decode(), want)


def test_alto_bit_budget_guard():
    phi = PhiTensor(atoms=jnp.zeros(1, jnp.int32), voxels=jnp.zeros(1, jnp.int32),
                    fibers=jnp.zeros(1, jnp.int32), values=jnp.ones(1),
                    n_atoms=2**30, n_voxels=2**30, n_fibers=2**30)
    with pytest.raises(ValueError, match="bits"):
        AltoPhi.encode(phi)


# ----------------------------------------------------------------------------
# compact_by_weight + formats: executors agree with the dense oracle
# ----------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(coo())
def test_property_compaction_preserves_dsc(case):
    """Dropping zero-weight fibers' coefficients never changes y = M w."""
    phi, seed = case
    r = np.random.default_rng(seed + 11)
    d = make_dictionary(phi.n_atoms, 8)
    w = r.uniform(size=phi.n_fibers).astype(np.float32)
    w[r.uniform(size=phi.n_fibers) < 0.5] = 0.0
    compacted = compact_by_weight(phi, w)
    np.testing.assert_allclose(
        np.asarray(spmv.dsc_naive(compacted, d, jnp.asarray(w))),
        np.asarray(spmv.dsc_naive(phi, d, jnp.asarray(w))),
        rtol=1e-4, atol=1e-5)
    # and every format round-trips the compacted tensor too
    for name in format_names():
        _assert_same_triples(get_format(name).encode(compacted).decode(),
                             compacted)


@settings(max_examples=10, deadline=None)
@given(coo())
def test_property_sell_references_match_dense(case):
    phi, seed = case
    r = np.random.default_rng(seed + 5)
    d = make_dictionary(phi.n_atoms, 8)
    w = jnp.asarray(r.uniform(size=phi.n_fibers), jnp.float32)
    y = jnp.asarray(r.normal(size=(phi.n_voxels, 8)), jnp.float32)
    m = materialize_dense(phi, d)
    got_y = dsc_reference(SellPhi.encode(phi, op="dsc"), d, w)
    got_w = wc_reference(SellPhi.encode(phi, op="wc"), d, y)
    np.testing.assert_allclose(np.asarray(got_y).reshape(-1),
                               np.asarray(m @ w), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(got_w),
                               np.asarray(m.T @ y.reshape(-1)),
                               rtol=1e-3, atol=1e-3)


def test_sell_kernel_executor_matches_dense(tiny_problem, tiny_dense, rng):
    """The SELL-backed Pallas executor (interpret) vs the dense oracle."""
    from repro.kernels import ops as kops
    p = tiny_problem
    w = jnp.asarray(rng.uniform(size=p.phi.n_fibers), jnp.float32)
    mv = kops.make_dsc_sell(SellPhi.encode(p.phi, op="dsc"), p.dictionary)
    np.testing.assert_allclose(
        np.asarray(mv(w)).reshape(-1), np.asarray(tiny_dense @ w),
        rtol=2e-4, atol=2e-4)
    y = jnp.asarray(rng.normal(size=(p.phi.n_voxels, 16)), jnp.float32)
    rv = kops.make_wc_sell(SellPhi.encode(p.phi, op="wc"), p.dictionary)
    np.testing.assert_allclose(
        np.asarray(rv(y)), np.asarray(tiny_dense.T @ y.reshape(-1)),
        rtol=2e-4, atol=2e-4)


@pytest.mark.slow
@settings(max_examples=8, deadline=None)
@given(coo())
def test_property_sell_kernels(case):
    """Pallas SELL kernels (interpret) vs naive, random COO sweep."""
    from repro.kernels import ops as kops
    phi, seed = case
    r = np.random.default_rng(seed + 7)
    d = make_dictionary(phi.n_atoms, 8)
    w = jnp.asarray(r.uniform(size=phi.n_fibers), jnp.float32)
    y = jnp.asarray(r.normal(size=(phi.n_voxels, 8)), jnp.float32)
    mv = kops.make_dsc_sell(SellPhi.encode(phi, op="dsc"), d)
    rv = kops.make_wc_sell(SellPhi.encode(phi, op="wc"), d)
    np.testing.assert_allclose(np.asarray(mv(w)),
                               np.asarray(spmv.dsc_naive(phi, d, w)),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(rv(y)),
                               np.asarray(spmv.wc_naive(phi, d, y)),
                               rtol=1e-3, atol=1e-3)


# ----------------------------------------------------------------------------
# Selection: heuristic, autotune fallback, FormatPlan caching
# ----------------------------------------------------------------------------

def _uniform_phi(nv=32, nf=32, na=8, per_row=32):
    """Every voxel and every fiber gets exactly per_row coefficients:
    SELL padding overhead ~0 on both ops."""
    nc = nv * per_row
    r = np.random.default_rng(3)
    return PhiTensor(
        atoms=jnp.asarray(r.integers(0, na, nc), jnp.int32),
        voxels=jnp.asarray(np.repeat(np.arange(nv), per_row), jnp.int32),
        fibers=jnp.asarray(np.tile(np.arange(nf), nc // nf), jnp.int32),
        values=jnp.asarray(r.normal(size=nc), jnp.float32),
        n_atoms=na, n_voxels=nv, n_fibers=nf)


def _skewed_phi(nv=64, nf=64, na=8):
    """One voxel and one fiber hoard most coefficients: SELL pads wildly."""
    r = np.random.default_rng(4)
    hot = 256
    cold = 64
    voxels = np.concatenate([np.zeros(hot, np.int64),
                             r.integers(1, nv, cold)])
    fibers = np.concatenate([np.zeros(hot, np.int64),
                             r.integers(1, nf, cold)])
    nc = hot + cold
    return PhiTensor(
        atoms=jnp.asarray(r.integers(0, na, nc), jnp.int32),
        voxels=jnp.asarray(voxels, jnp.int32),
        fibers=jnp.asarray(fibers, jnp.int32),
        values=jnp.asarray(r.normal(size=nc), jnp.float32),
        n_atoms=na, n_voxels=nv, n_fibers=nf)


@settings(max_examples=10, deadline=None)
@given(coo())
def test_property_predicted_sell_overhead_matches_encode(case):
    """The selector's O(Nc) overhead prediction must equal what
    SellPhi.encode actually allocates (shared sell_geometry)."""
    phi, _ = case
    stats = phi_stats(phi, row_tile=8, slot_tile=32)
    for op in ("dsc", "wc"):
        enc = SellPhi.encode(phi, op=op, row_tile=8, slot_tile=32)
        np.testing.assert_allclose(stats[f"{op}.sell_overhead"],
                                   enc.padding_overhead, rtol=1e-12)


def test_phi_stats_shapes(tiny_problem):
    s = phi_stats(tiny_problem.phi)
    for k in ("dsc.sell_overhead", "wc.sell_overhead", "dsc.run_mean",
              "wc.run_max", "nc_per_fiber"):
        assert k in s and np.isfinite(s[k])
    assert s["dsc.sell_overhead"] >= 0.0


def test_heuristic_picks_sell_on_uniform_rows():
    phi = _uniform_phi()
    d = make_dictionary(phi.n_atoms, 8)
    plan = fsel.choose_format(phi, d)
    assert plan.format == "sell" and plan.reason == "heuristic"
    assert plan.stats["dsc.sell_overhead"] <= fsel.DEFAULT_SELL_ACCEPT


def test_heuristic_rejects_sell_on_skew():
    phi = _skewed_phi()
    d = make_dictionary(phi.n_atoms, 8)
    # sell vs coo only: rejection leaves one candidate -> pure heuristic
    plan = fsel.choose_format(phi, d, allowed=("coo", "sell"))
    assert plan.format == "coo" and plan.reason == "heuristic"
    assert plan.stats["dsc.sell_overhead"] >= fsel.DEFAULT_SELL_REJECT
    # with alto/fcoo also in the running the survivors are measured, so
    # those candidates stay live — only sell is struck by the skew
    plan = fsel.choose_format(phi, d)
    assert plan.reason == "autotune"
    assert plan.format in ("coo", "alto", "fcoo")


def test_autotune_fallback_runs_in_ambiguous_zone(tiny_problem):
    d = tiny_problem.dictionary
    plan = fsel.choose_format(tiny_problem.phi, d, sell_accept=-1.0,
                              sell_reject=float("inf"))
    assert plan.reason == "autotune"
    assert plan.format in format_names()


def test_sell_only_candidate_set_survives_rejection():
    """An explicit allowed=("sell",) wins over the skew heuristic — and
    never crashes on an empty candidate set."""
    phi = _skewed_phi()
    d = make_dictionary(phi.n_atoms, 8)
    plan = fsel.choose_format(phi, d, allowed=("sell",))
    assert plan.format == "sell" and plan.reason == "heuristic"
    with pytest.raises(ValueError, match="at least one"):
        fsel.choose_format(phi, d, allowed=())


def test_threshold_change_misses_format_cache(tmp_path):
    """Different sell thresholds may choose differently -> different key."""
    from repro.core.plan_cache import PlanCache
    phi = _uniform_phi()
    d = make_dictionary(phi.n_atoms, 8)
    cache = PlanCache(str(tmp_path))
    p1 = fsel.choose_format(phi, d, cache=cache)
    assert p1.format == "sell"
    p2 = fsel.choose_format(phi, d, cache=cache, sell_accept=-1.0,
                            sell_reject=-0.5)
    assert p2.format != "sell"            # not served the stale choice
    assert cache.stats.misses == 2


def test_format_plan_cache_roundtrip(tmp_path):
    from repro.core.plan_cache import PlanCache, format_plan_key
    cache = PlanCache(str(tmp_path))
    key = format_plan_key(np.arange(5), np.arange(5), np.arange(5),
                          sizes=(8, 16, 8), row_tile=8, slot_tile=32,
                          allowed=("coo", "sell"))
    assert cache.get_format_plan(key) is None
    plan = FormatPlan("sell", "heuristic", dict(row_tile=8, slot_tile=32),
                      {"dsc.sell_overhead": 0.25})
    cache.put_format_plan(key, plan)
    got = cache.get_format_plan(key)
    assert (got.format, got.reason) == ("sell", "heuristic")
    assert got.params == plan.params
    assert got.stats == {"dsc.sell_overhead": 0.25}
    # candidate set is part of the key
    other = format_plan_key(np.arange(5), np.arange(5), np.arange(5),
                            sizes=(8, 16, 8), row_tile=8, slot_tile=32,
                            allowed=("coo",))
    assert other != key


# ----------------------------------------------------------------------------
# Engine integration
# ----------------------------------------------------------------------------

def test_engine_explicit_formats_match_oracle(tiny_problem, tiny_dense, rng):
    from repro.core.life import LifeConfig, LifeEngine
    w = jnp.asarray(rng.uniform(size=tiny_problem.phi.n_fibers), jnp.float32)
    y = jnp.asarray(rng.normal(size=(tiny_problem.phi.n_voxels, 16)),
                    jnp.float32)
    for fmt, exec_name in (("sell", "kernel-sell"), ("alto", "alto")):
        eng = LifeEngine(tiny_problem,
                         LifeConfig(format=fmt, plan_cache_dir=""))
        assert eng.executor.name == exec_name
        assert eng.format_plan.format == fmt
        np.testing.assert_allclose(
            np.asarray(eng.matvec(w)).reshape(-1),
            np.asarray(tiny_dense @ w), rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(
            np.asarray(eng.rmatvec(y)),
            np.asarray(tiny_dense.T @ y.reshape(-1)), rtol=2e-4, atol=2e-4)


def test_engine_auto_format_warm_cache_skips_selection(tiny_problem,
                                                       tmp_path, monkeypatch):
    """Warm rebuild must load the FormatPlan, not re-run the selector."""
    from repro.core.life import LifeConfig, LifeEngine
    cfg = LifeConfig(format="auto", plan_cache_dir=str(tmp_path))
    eng1 = LifeEngine(tiny_problem, cfg)
    assert eng1.format_plan is not None

    def boom(*a, **k):
        raise AssertionError("selection re-ran despite cached FormatPlan")

    monkeypatch.setattr(fsel, "phi_stats", boom)
    monkeypatch.setattr(fsel, "_measure_formats", boom)
    eng2 = LifeEngine(tiny_problem, cfg)
    assert eng2.format_plan.format == eng1.format_plan.format
    assert eng2.cache_stats.hits >= 1


def test_engine_auto_format_runs_sbbnnls(tiny_problem):
    from repro.core.life import LifeConfig, LifeEngine
    eng = LifeEngine(tiny_problem,
                     LifeConfig(format="auto", n_iters=10, plan_cache_dir=""))
    w, losses = eng.run()
    assert losses[-1] < losses[0]


def test_batched_engine_auto_format(tmp_path):
    from repro.core.batched import BatchedLifeEngine
    from repro.core.life import LifeConfig
    from repro.data.dmri import synth_cohort
    cohort = synth_cohort(2, n_fibers=48, n_theta=12, n_atoms=16,
                          grid=(8, 8, 8))
    eng = BatchedLifeEngine(cohort, LifeConfig(
        executor="opt", format="auto", n_iters=5,
        plan_cache_dir=str(tmp_path)))
    assert eng.format_plan is not None
    assert eng.format_plan.format in ("coo", "alto")   # vmappable subset
    w, losses = eng.run()
    assert w.shape == (2, 48)
    assert np.isfinite(losses).all()


def test_batched_engine_rejects_sell():
    from repro.core.batched import BatchedLifeEngine
    from repro.core.life import LifeConfig
    from repro.data.dmri import synth_cohort
    cohort = synth_cohort(2, n_fibers=32, n_theta=8, n_atoms=8, grid=(6, 6, 6))
    with pytest.raises(ValueError, match="sell"):
        BatchedLifeEngine(cohort, LifeConfig(executor="opt", format="sell",
                                             plan_cache_dir=""))


def test_engine_sell_with_compaction(tiny_problem):
    """Weight compaction re-encodes the SELL layout mid-run and converges."""
    from repro.core.life import LifeConfig, LifeEngine
    eng = LifeEngine(tiny_problem, LifeConfig(
        format="sell", n_iters=8, compact_every=4, plan_cache_dir=""))
    w, losses = eng.run()
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_measure_formats_times_registry_alto_executor(tiny_problem,
                                                      monkeypatch):
    """Regression: format arbitration used to time ALTO as dsc_naive over
    a decoded COO tensor — never building the registry executor whose cost
    the measured rung is supposed to charge, so ALTO kept "winning" on a
    code path it never runs in production."""
    from repro.core.registry import REGISTRY
    built = []
    real = REGISTRY._factories["alto"]

    def counting(*args, **kwargs):
        built.append(1)
        return real(*args, **kwargs)

    monkeypatch.setitem(REGISTRY._factories, "alto", counting)
    fmt = fsel._measure_formats(tiny_problem.phi, tiny_problem.dictionary,
                                ("coo", "alto"), 8, 32)
    assert built, "arbitration must build the registry alto executor"
    assert fmt in ("coo", "alto")
