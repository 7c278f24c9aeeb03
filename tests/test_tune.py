"""Kernel autotuning subsystem (DESIGN.md §10): plan keys, cache behaviour,
zero-measurement warm rebuilds, and the bf16 accuracy contract across the
whole executor x format conformance matrix."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.life import LifeConfig, LifeEngine
from repro.core.plan_cache import PlanCache, tune_plan_key
from repro.core.registry import REGISTRY, create_for_format
from repro.formats import format_names
from repro.tune import (BF16_ATOL, BF16_RTOL, TunePlan, search_space,
                        tile_axes)
from repro.tune.plan import COMPUTE_DTYPES
from repro.tune.tuner import backend_name

#: same derivation as tests/test_conformance.py — the registry is the truth
MATRIX = [(ex, fmt) for fmt in format_names()
          for ex in REGISTRY.executors_for_format(fmt)]

_CFG = LifeConfig(executor="opt", c_tile=64, row_tile=8, slot_tile=16,
                  plan_cache_dir="")


def _make_executor(name, fmt, problem, cfg):
    if fmt == "coo":
        return REGISTRY.create(name, problem.phi, problem, cfg, PlanCache(""))
    return create_for_format(problem.phi, problem, cfg, PlanCache(""))


def _ids():
    rng = np.random.default_rng(3)
    return (rng.integers(0, 24, 200), rng.integers(0, 40, 200),
            rng.integers(0, 64, 200))


_KEY_BASE = dict(sizes=(24, 40, 64), n_theta=16, executor="kernel-sell",
                 fmt="sell", backend="cpu", n_devices=1,
                 compute_dtype="fp32", budget=12)


# ----------------------------------------------------------------------------
# key schema: content addressing across every axis the plan depends on
# ----------------------------------------------------------------------------

def test_tune_plan_key_is_content_addressed():
    ids = _ids()
    base = tune_plan_key(*ids, **_KEY_BASE)
    # same content, different buffers -> same key (warm hit on identical
    # inputs)
    assert tune_plan_key(*(a.copy() for a in ids), **_KEY_BASE) == base
    # any platform / config axis change -> clean miss
    for change in (dict(backend="tpu"), dict(n_devices=8),
                   dict(compute_dtype="bf16"), dict(compute_dtype="auto"),
                   dict(executor="kernel"), dict(fmt="coo"),
                   dict(n_theta=32), dict(sizes=(24, 40, 65)),
                   dict(budget=4), dict(mesh=(2, 1)), dict(mesh=(1, 2))):
        assert tune_plan_key(*ids, **{**_KEY_BASE, **change}) != base, change
    # index-content change -> clean miss
    bumped = (ids[0].copy(), ids[1], ids[2])
    bumped[0][0] = (bumped[0][0] + 1) % 24
    if not np.array_equal(bumped[0], ids[0]):
        assert tune_plan_key(*bumped, **_KEY_BASE) != base


def test_tune_plan_roundtrip(tmp_path):
    cache = PlanCache(str(tmp_path))
    plan = TunePlan(executor="kernel-sell", backend="cpu", n_devices=1,
                    params=dict(row_tile=16, slot_tile=32),
                    compute_dtype="bf16", reason="search",
                    measurements={"a": 1.5e-3, "b": 2.5e-3})
    key = tune_plan_key(*_ids(), **_KEY_BASE)
    assert cache.get_tune_plan(key) is None           # cold
    cache.put_tune_plan(key, plan)
    got = cache.get_tune_plan(key)
    assert got == plan
    assert cache.stats.hits == 1 and cache.stats.misses == 1


def test_tune_plan_apply_replaces_only_declared_fields():
    plan = TunePlan(executor="kernel-sell", backend="cpu", n_devices=1,
                    params=dict(row_tile=16, slot_tile=64, bogus_axis=3),
                    compute_dtype="bf16")
    cfg = plan.apply(_CFG)
    assert (cfg.row_tile, cfg.slot_tile) == (16, 64)
    assert cfg.compute_dtype == "bf16"
    assert not hasattr(cfg, "bogus_axis")
    assert _CFG.row_tile == 8                          # original untouched


# ----------------------------------------------------------------------------
# search space: the default config is never truncated away
# ----------------------------------------------------------------------------

def test_search_space_keeps_default_under_budget():
    for budget in (2, 4, 6):
        cands = search_space("kernel-sell", _CFG, budget=budget)
        assert len(cands) <= max(budget, 1)
        assert cands[0] == dict(params=dict(slot_tile=16),
                                compute_dtype="fp32")


def test_search_space_dtype_axis():
    cfg = dataclasses.replace(_CFG, compute_dtype="auto")
    cands = search_space("opt", cfg)          # no tile axes: dtype axis only
    assert [c["compute_dtype"] for c in cands] == list(COMPUTE_DTYPES)
    assert all(c["params"] == {} for c in cands)
    assert tile_axes("opt") == ()
    assert tile_axes("kernel") == ("c_tile", "row_tile")
    assert tile_axes("kernel-fcoo") == ("c_tile", "seg_tile")


# ----------------------------------------------------------------------------
# engine integration: full -> cached rebuild performs ZERO measurements
# ----------------------------------------------------------------------------

def _tuned_cfg(tmp_path, **kw):
    return LifeConfig(executor="opt", format="sell", slot_tile=16, row_tile=8,
                      n_iters=2, tune="full", tune_budget=4,
                      plan_cache_dir=str(tmp_path), **kw)


def test_full_then_cached_zero_measurements(tmp_path, tiny_problem,
                                            monkeypatch):
    """The acceptance contract: tune="full" then rebuild with tune="cached"
    loads the persisted TunePlan and never measures anything."""
    cfg = _tuned_cfg(tmp_path)
    eng1 = LifeEngine(tiny_problem, cfg)
    plan1 = eng1.tune_plan
    assert plan1 is not None and plan1.reason == "search"
    assert plan1.measurements                      # the search did measure

    from repro.tune import search as tsearch

    def boom(*a, **k):
        raise AssertionError("measurement despite warm tune-plan cache")

    monkeypatch.setattr(tsearch, "time_call", boom)
    eng2 = LifeEngine(tiny_problem,
                      dataclasses.replace(cfg, tune="cached"))
    assert eng2.tune_plan == plan1
    # ... and a warm tune="full" rebuild also skips the search
    eng3 = LifeEngine(tiny_problem, cfg)
    assert eng3.tune_plan == plan1


def test_full_then_cached_zero_measurements_fcoo(tmp_path, tiny_problem,
                                                 monkeypatch):
    """Same warm-rebuild contract for the F-COO executor: its tune axes
    (c_tile, seg_tile) are searched once, then every rebuild — cached or
    full — loads the persisted TunePlan without a single measurement."""
    cfg = LifeConfig(executor="opt", format="fcoo", c_tile=64, seg_tile=16,
                     n_iters=2, tune="full", tune_budget=4,
                     plan_cache_dir=str(tmp_path))
    eng1 = LifeEngine(tiny_problem, cfg)
    plan1 = eng1.tune_plan
    assert plan1 is not None and plan1.reason == "search"
    assert plan1.executor == "kernel-fcoo"
    assert plan1.measurements

    from repro.tune import search as tsearch

    def boom(*a, **k):
        raise AssertionError("measurement despite warm tune-plan cache")

    monkeypatch.setattr(tsearch, "time_call", boom)
    eng2 = LifeEngine(tiny_problem, dataclasses.replace(cfg, tune="cached"))
    assert eng2.tune_plan == plan1
    eng3 = LifeEngine(tiny_problem, cfg)           # warm tune="full"
    assert eng3.tune_plan == plan1


def test_cached_miss_uses_defaults_without_measuring(tmp_path, tiny_problem,
                                                     monkeypatch):
    """tune="cached" on a cold cache must fall back to the config constants
    immediately — intake paths never stall on a search."""
    from repro.tune import search as tsearch

    def boom(*a, **k):
        raise AssertionError('tune="cached" measured on a miss')

    monkeypatch.setattr(tsearch, "time_call", boom)
    cfg = dataclasses.replace(_tuned_cfg(tmp_path), tune="cached")
    eng = LifeEngine(tiny_problem, cfg)
    plan = eng.tune_plan
    assert plan.reason == "untuned"
    assert plan.params == dict(slot_tile=16)
    # the miss persisted nothing: a later "cached" engine still misses
    eng2 = LifeEngine(tiny_problem, cfg)
    assert eng2.tune_plan.reason == "untuned"


def test_backend_change_is_clean_miss(tmp_path, tiny_problem, monkeypatch):
    """A plan tuned on one backend must not be replayed on another."""
    cfg = _tuned_cfg(tmp_path)
    LifeEngine(tiny_problem, cfg)                   # tune + persist on "cpu"
    import repro.tune.tuner as tuner_mod
    monkeypatch.setattr(tuner_mod, "backend_name", lambda: "faketpu")
    eng = LifeEngine(tiny_problem,
                     dataclasses.replace(cfg, tune="cached"))
    assert eng.tune_plan.reason == "untuned"        # miss, not a stale hit


def test_dtype_change_is_clean_miss(tmp_path, tiny_problem, monkeypatch):
    cfg = _tuned_cfg(tmp_path)
    LifeEngine(tiny_problem, cfg)                   # fp32-keyed plan
    from repro.tune import search as tsearch
    monkeypatch.setattr(tsearch, "time_call",
                        lambda *a, **k: (_ for _ in ()).throw(
                            AssertionError("measured")))
    eng = LifeEngine(tiny_problem, dataclasses.replace(
        cfg, tune="cached", compute_dtype="bf16"))
    assert eng.tune_plan.reason == "untuned"


def test_tuned_engine_matches_oracle(tmp_path, tiny_problem, tiny_dense,
                                     rng):
    """Whatever configuration the search picks, the tuned executor still
    satisfies the conformance contract."""
    eng = LifeEngine(tiny_problem,
                     _tuned_cfg(tmp_path, compute_dtype="auto"))
    m = np.asarray(tiny_dense, np.float64)
    w = jnp.asarray(rng.uniform(0, 1, tiny_problem.phi.n_fibers),
                    jnp.float32)
    got = np.asarray(eng.matvec(w), np.float64).reshape(-1)
    want = m @ np.asarray(w, np.float64)
    np.testing.assert_allclose(got, want, rtol=BF16_RTOL, atol=BF16_ATOL)


def test_auto_dtype_requires_tuning(tiny_problem):
    with pytest.raises(ValueError, match="searched axis"):
        LifeEngine(tiny_problem, LifeConfig(executor="opt", tune="off",
                                            compute_dtype="auto",
                                            plan_cache_dir=""))
    with pytest.raises(ValueError, match="tune must be one of"):
        LifeEngine(tiny_problem, LifeConfig(executor="opt", tune="always",
                                            plan_cache_dir=""))
    with pytest.raises(ValueError, match="compute_dtype"):
        LifeEngine(tiny_problem, LifeConfig(executor="opt",
                                            compute_dtype="fp16",
                                            plan_cache_dir=""))


# ----------------------------------------------------------------------------
# bf16 storage / fp32 accumulate: documented atol across the whole matrix
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("executor,fmt", MATRIX)
def test_bf16_within_documented_atol_of_fp32(executor, fmt, tiny_problem,
                                             rng):
    """compute_dtype="bf16" stays within BF16_RTOL/BF16_ATOL of the fp32
    executor for every executor x format pair the registry declares."""
    p = tiny_problem
    n_theta = p.dictionary.shape[1]
    w = jnp.asarray(rng.uniform(0, 1, p.phi.n_fibers), jnp.float32)
    y = jnp.asarray(rng.normal(size=(p.phi.n_voxels, n_theta)), jnp.float32)
    outs = {}
    for dt in ("fp32", "bf16"):
        cfg = dataclasses.replace(_CFG, executor=executor, format=fmt,
                                  compute_dtype=dt)
        ex = _make_executor(executor, fmt, p, cfg)
        outs[dt] = (np.asarray(ex.matvec(w), np.float64),
                    np.asarray(ex.rmatvec(y), np.float64))
    # fp32 outputs keep fp32 dtype end to end (accumulators never narrow)
    np.testing.assert_allclose(outs["bf16"][0], outs["fp32"][0],
                               rtol=BF16_RTOL, atol=BF16_ATOL,
                               err_msg=f"{executor}/{fmt} matvec")
    np.testing.assert_allclose(outs["bf16"][1], outs["fp32"][1],
                               rtol=BF16_RTOL,
                               atol=BF16_ATOL * max(
                                   1.0, np.abs(outs["fp32"][1]).max()),
                               err_msg=f"{executor}/{fmt} rmatvec")


def test_bf16_output_dtype_stays_fp32(tiny_problem):
    """bf16 is a *storage* dtype: matvec/rmatvec still return fp32."""
    cfg = dataclasses.replace(_CFG, executor="kernel-sell", format="sell",
                              compute_dtype="bf16")
    ex = _make_executor("kernel-sell", "sell", tiny_problem, cfg)
    w = jnp.ones((tiny_problem.phi.n_fibers,), jnp.float32)
    y = ex.matvec(w)
    assert y.dtype == jnp.float32
    assert ex.rmatvec(y).dtype == jnp.float32


def test_bf16_batched_engine(tiny_cohort):
    """The batched engine honors compute_dtype: bf16 trajectories track
    fp32 within the documented tolerance."""
    from repro.core.batched import BatchedLifeEngine
    cfg32 = LifeConfig(executor="opt", n_iters=4, plan_cache_dir="")
    cfg16 = dataclasses.replace(cfg32, compute_dtype="bf16")
    _, l32 = BatchedLifeEngine(tiny_cohort, cfg32).run()
    _, l16 = BatchedLifeEngine(tiny_cohort, cfg16).run()
    np.testing.assert_allclose(l16, l32, rtol=BF16_RTOL)


# ----------------------------------------------------------------------------
# serving: tuning settings partition micro-batches
# ----------------------------------------------------------------------------

def test_scheduler_buckets_split_on_tune_settings(tiny_cohort):
    from repro.serve.scheduler import Job, Scheduler
    s = Scheduler(LifeConfig(executor="opt", n_iters=4, plan_cache_dir=""))
    s.submit(Job(job_id="a", problem=tiny_cohort[0], n_iters=4,
                 format="coo"))
    s.submit(Job(job_id="b", problem=tiny_cohort[1], n_iters=4,
                 format="coo", compute_dtype="bf16"))
    s.submit(Job(job_id="c", problem=tiny_cohort[2], n_iters=4,
                 format="coo"))
    s._admit()
    members = sorted(tuple(sorted(j.job_id for j in b.jobs))
                     for b in s._buckets.values())
    assert members == [("a", "c"), ("b",)]
    done = s.run_until_idle()
    assert sorted(j.job_id for j in done) == ["a", "b", "c"]


def test_fcoo_jobs_never_share_a_microbatch(tiny_cohort):
    """F-COO is a solo format AND tune settings are part of the bucket
    key: two fcoo jobs never co-batch, whether their tuning matches or
    not — differently-tuned jobs sharing a micro-batch would force one
    tenant's tile plan on the other."""
    from repro.serve.scheduler import Job, Scheduler
    s = Scheduler(LifeConfig(executor="opt", n_iters=4, plan_cache_dir=""))
    s.submit(Job(job_id="a", problem=tiny_cohort[0], n_iters=4,
                 format="fcoo"))
    s.submit(Job(job_id="b", problem=tiny_cohort[1], n_iters=4,
                 format="fcoo", compute_dtype="bf16"))
    s.submit(Job(job_id="c", problem=tiny_cohort[2], n_iters=4,
                 format="fcoo"))
    s._admit()
    members = sorted(tuple(sorted(j.job_id for j in b.jobs))
                     for b in s._buckets.values())
    assert members == [("a",), ("b",), ("c",)]
    keys = {b.key for b in s._buckets.values()}
    assert len(keys) == 3                      # distinct bucket identities
    done = s.run_until_idle()
    assert sorted(j.job_id for j in done) == ["a", "b", "c"]


def test_scheduler_rejects_bad_tune_values(tiny_cohort):
    from repro.serve.scheduler import Job, Scheduler
    s = Scheduler(LifeConfig(executor="opt", plan_cache_dir=""))
    with pytest.raises(ValueError, match="tune must be"):
        s.submit(Job(job_id="x", problem=tiny_cohort[0], n_iters=2,
                     format="coo", tune="sometimes"))
    with pytest.raises(ValueError, match="searched axis"):
        s.submit(Job(job_id="y", problem=tiny_cohort[0], n_iters=2,
                     format="coo", compute_dtype="auto"))


def test_auto_dtype_pins_resolved_value_in_checkpoints(tmp_path,
                                                       tiny_cohort):
    """A compute_dtype="auto" job is pinned to the tuner's resolved dtype
    the moment its engine builds: the checkpoint manifest must record the
    numerics that actually ran, never the open "auto" request (a re-search
    after cache eviction could resolve differently on resume)."""
    from repro.serve.service import LifeService
    svc = LifeService(LifeConfig(executor="opt", n_iters=8,
                                 plan_cache_dir=str(tmp_path / "plans")),
                      ckpt_dir=str(tmp_path / "ck"), checkpoint_every=1,
                      slice_iters=2)
    jid = svc.submit(tiny_cohort[0], n_iters=8, tune="full",
                     compute_dtype="auto")
    svc.step()
    job = svc.scheduler.job(jid)
    assert job.compute_dtype in COMPUTE_DTYPES           # pinned, not "auto"
    from repro.checkpoint import manager as ckpt
    _, _, manifest = ckpt.load_latest(str(tmp_path / "ck"))
    assert manifest["jobs"][jid]["compute_dtype"] == job.compute_dtype


def test_service_resume_rejects_conflicting_compute_dtype(tmp_path,
                                                          tiny_cohort):
    """A checkpointed solve's numerics are part of its identity: resuming
    under a different compute_dtype is an error, not a silent override."""
    from repro.serve.service import LifeService
    ck = str(tmp_path / "ck")
    svc = LifeService(LifeConfig(executor="opt", n_iters=8,
                                 plan_cache_dir=""),
                      ckpt_dir=ck, checkpoint_every=1, slice_iters=2)
    jid = svc.submit(tiny_cohort[0], n_iters=8, compute_dtype="bf16")
    svc.step()
    svc.checkpoint()
    svc2 = LifeService(LifeConfig(executor="opt", n_iters=8,
                                  plan_cache_dir=""), ckpt_dir=ck)
    assert jid in svc2.resumable_jobs
    with pytest.raises(ValueError, match="compute_dtype"):
        svc2.submit(tiny_cohort[0], job_id=jid, compute_dtype="fp32")
    # omitted -> inherited from the checkpoint, resume proceeds
    svc2.submit(tiny_cohort[0], job_id=jid)
    assert svc2.scheduler.job(jid).compute_dtype == "bf16"


# ----------------------------------------------------------------------------
# tuner internals
# ----------------------------------------------------------------------------

def test_tuner_measures_within_budget(tmp_path, tiny_problem):
    cfg = _tuned_cfg(tmp_path, compute_dtype="auto")
    cfg = dataclasses.replace(cfg, tune_budget=4)
    eng = LifeEngine(tiny_problem, cfg)
    plan = eng.tune_plan
    assert plan.reason == "search"
    assert len(plan.measurements) <= 4
    assert plan.compute_dtype in COMPUTE_DTYPES
    assert plan.backend == backend_name()
    assert plan.n_devices == len(jax.devices())


def test_degenerate_search_space_persists_default_plan(tmp_path,
                                                       tiny_problem,
                                                       monkeypatch):
    """No tile axes + fixed dtype: nothing to measure, but the plan is
    persisted so tune="cached" rebuilds hit."""
    from repro.tune import search as tsearch
    monkeypatch.setattr(tsearch, "time_call",
                        lambda *a, **k: (_ for _ in ()).throw(
                            AssertionError("measured a 1-candidate space")))
    cfg = LifeConfig(executor="opt", n_iters=2, tune="full",
                     plan_cache_dir=str(tmp_path))
    eng = LifeEngine(tiny_problem, cfg)
    assert eng.tune_plan.reason == "default"
    eng2 = LifeEngine(tiny_problem, dataclasses.replace(cfg, tune="cached"))
    assert eng2.tune_plan.reason == "default"       # warm hit, not untuned


def test_measure_candidates_keeps_duplicate_labels():
    """Regression: two candidates stringifying to the same label used to
    silently overwrite each other in the measurements dict, so persisted
    TunePlans under-counted the search."""
    from repro.tune import search as tsearch
    costs_seen = iter([2.0, 1.0])
    with pytest.warns(UserWarning, match="duplicate search candidate"):
        best, costs = tsearch.measure_candidates(
            [dict(row_tile=8), dict(row_tile=8)],
            lambda c: next(costs_seen))
    assert best == 1                          # the cheaper repeat still wins
    assert len(costs) == 2                    # both measurements audited
    assert set(costs.values()) == {2.0, 1.0}
