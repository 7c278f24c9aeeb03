"""Learned zero-measurement selection (repro.learn, DESIGN.md §14).

Covers the feature schema, the dependency-free models, harvesting from the
plan cache, the predicted cold-start contract (a cache miss answered with
zero timing measurements), background refinement upgrading predicted plans
in place, and the serve frontend's idle-tick drain hook.
"""
import dataclasses
import json
import time

import numpy as np
import pytest

from repro.core.inspector import phi_stats
from repro.core.life import LifeConfig, LifeEngine
from repro.core.plan_cache import PlanCache
from repro.data.dmri import synth_connectome
from repro.formats.base import FormatPlan
from repro.learn import (FEATURE_NAMES, CentroidClassifier, NearestExample,
                         Predictor, feature_vector, harvest, load_predictor,
                         predictor_path, run_pending, train_predictor)
from repro.learn import refine
from repro.tune import search as tsearch

TRAIN_SPECS = (
    dict(n_fibers=96, n_theta=16, n_atoms=24, grid=(8, 8, 8),
         algorithm="PROB", seed=71),
    dict(n_fibers=128, n_theta=16, n_atoms=24, grid=(8, 8, 8),
         algorithm="DET", seed=72),
)


def _boom(*a, **k):
    raise AssertionError("timing measurement on a zero-measurement path")


def _train_cfg(cache_dir, **kw):
    base = dict(executor="opt", format="auto", n_iters=1, tune="full",
                compute_dtype="auto", tune_budget=4, predict="off",
                plan_cache_dir=str(cache_dir))
    base.update(kw)
    return LifeConfig(**base)


def _trained_cache(cache_dir, **kw):
    """Fill ``cache_dir`` with measured plans for the training fleet and
    train the predictor beside them."""
    for spec in TRAIN_SPECS:
        LifeEngine(synth_connectome(**spec), _train_cfg(cache_dir, **kw))
    cache = PlanCache(str(cache_dir))
    return cache, train_predictor(cache)


# ----------------------------------------------------------------------------
# features
# ----------------------------------------------------------------------------

def test_feature_vector_schema(tiny_problem):
    stats = phi_stats(tiny_problem.phi)
    x = feature_vector(stats)
    assert x is not None and x.shape == (len(FEATURE_NAMES),)
    assert np.all(np.isfinite(x)) and np.all(x >= 0.0)   # log1p of >= 0
    # any missing feature -> None (old plans are skipped, never padded)
    partial = dict(stats)
    del partial["dsc.run_p99"]
    assert feature_vector(partial) is None
    # non-finite values -> None
    assert feature_vector(dict(stats, n_coeffs=float("nan"))) is None


# ----------------------------------------------------------------------------
# models
# ----------------------------------------------------------------------------

def _toy_training_set():
    r = np.random.default_rng(9)
    a = r.normal(loc=0.0, size=(10, 4))
    b = r.normal(loc=6.0, size=(10, 4))
    x = np.vstack([a, b])
    y = ["coo"] * 10 + ["sell"] * 10
    return x, y, a, b


def test_centroid_classifier_predicts_and_respects_allowed():
    x, y, a, b = _toy_training_set()
    clf = CentroidClassifier.fit(x, y)
    assert clf.predict(a[0]) == "coo"
    assert clf.predict(b[0]) == "sell"
    # restriction to the caller's candidate set is honored...
    assert clf.predict(a[0], allowed=("sell",)) == "sell"
    # ...and an allowed set with no trained class yields None, not a guess
    assert clf.predict(a[0], allowed=("alto", "fcoo")) is None
    assert clf.predict(a[0], allowed=()) is None


def test_nearest_example_replays_group_payloads():
    r = np.random.default_rng(11)
    x = r.normal(size=(4, 3))
    keys = [NearestExample.group_key("kernel-sell", "cpu")] * 2 + \
           [NearestExample.group_key("opt", "cpu")] * 2
    payloads = [dict(row_tile=8, slot_tile=16, compute_dtype="fp32"),
                dict(row_tile=16, slot_tile=32, compute_dtype="bf16"),
                dict(compute_dtype="fp32"), dict(compute_dtype="bf16")]
    nn = NearestExample.fit(x, keys, payloads)
    got = nn.predict(x[1], executor="kernel-sell", backend="cpu")
    assert got == payloads[1]
    # neighbours never cross (executor, backend) groups
    assert nn.predict(x[0], executor="opt", backend="cpu") in payloads[2:]
    assert nn.predict(x[0], executor="alto", backend="cpu") is None


def test_predictor_json_roundtrip(tmp_path):
    r = np.random.default_rng(13)
    n_feat = len(FEATURE_NAMES)
    x = np.vstack([r.normal(loc=0.0, size=(8, n_feat)),
                   r.normal(loc=6.0, size=(8, n_feat))])
    y = ["coo"] * 8 + ["sell"] * 8
    pred = Predictor(format_model=CentroidClassifier.fit(x, y),
                     n_format_examples=len(y))
    blob = json.dumps(pred.to_json())
    back = Predictor.from_json(json.loads(blob))
    stats = {name: float(i + 1) for i, name in enumerate(FEATURE_NAMES)}
    assert (back.predict_format(stats, allowed=("coo", "sell"))
            == pred.predict_format(stats, allowed=("coo", "sell")))
    # a schema bump must refuse to load (silent reorder = wrong predictions)
    stale = json.loads(blob)
    stale["schema"] = -1
    assert Predictor.from_json(stale) is None
    stale = json.loads(blob)
    stale["feature_names"] = list(reversed(stale["feature_names"]))
    assert Predictor.from_json(stale) is None


# ----------------------------------------------------------------------------
# harvest + train + load
# ----------------------------------------------------------------------------

def test_harvest_excludes_non_training_reasons(tmp_path, tiny_problem):
    cache = PlanCache(str(tmp_path / "c"))
    stats = phi_stats(tiny_problem.phi)
    params = dict(row_tile=8, slot_tile=32)
    cache.put_format_plan("k1", FormatPlan("sell", "heuristic", params, stats))
    cache.put_format_plan("k2", FormatPlan("coo", "autotune", params, stats))
    cache.put_format_plan("k3", FormatPlan("alto", "explicit", params, stats))
    cache.put_format_plan("k4", FormatPlan("coo", "predicted", params, stats))
    cache.put_format_plan("k5", FormatPlan("coo", "heuristic", params, {}))
    fmt, tune = harvest(cache)
    # explicit (user-forced), predicted (model's own output) and stats-less
    # plans are all excluded from the training set
    assert sorted(lab for _, lab in fmt) == ["coo", "sell"]
    assert tune == []


def test_train_and_load_predictor(tmp_path, tiny_problem):
    cache, predictor = _trained_cache(tmp_path / "train")
    assert predictor is not None
    assert predictor.n_format_examples >= 2
    assert predictor.n_tune_examples >= 2      # dtype axis forces a search
    # persisted beside the plans, reloadable, memo invalidates on retrain
    loaded = load_predictor(cache.directory)
    assert loaded is not None
    assert loaded.n_format_examples == predictor.n_format_examples
    stats = phi_stats(tiny_problem.phi)
    assert loaded.predict_format(stats, allowed=("coo", "sell", "alto",
                                                 "fcoo")) is not None
    # an empty cache trains nothing and writes nothing
    empty = PlanCache(str(tmp_path / "empty"))
    assert train_predictor(empty) is None
    assert load_predictor(empty.directory) is None


def test_predictor_survives_npz_pruning(tmp_path, tiny_problem):
    """The trained model must not be evicted by the cache's size cap —
    pruning only touches .npz entries."""
    cache, _ = _trained_cache(tmp_path / "train")
    capped = PlanCache(cache.directory, max_bytes=1)
    stats = phi_stats(tiny_problem.phi)
    capped.put_format_plan(
        "evictor", FormatPlan("coo", "heuristic",
                              dict(row_tile=8, slot_tile=32), stats))
    assert load_predictor(cache.directory) is not None


# ----------------------------------------------------------------------------
# the cold-start contract (tentpole acceptance)
# ----------------------------------------------------------------------------

def test_predicted_cold_start_zero_measurements(tmp_path, tiny_problem,
                                                monkeypatch):
    """A cache miss on an unseen dataset with a warm-trained predictor
    yields a usable engine with reason="predicted" plans and not a single
    timing measurement."""
    cache, predictor = _trained_cache(tmp_path / "train")
    assert predictor is not None

    n0 = tsearch.measurement_count()
    monkeypatch.setattr(tsearch, "time_call", _boom)
    cfg = LifeConfig(executor="opt", format="auto", n_iters=2, tune="cached",
                     compute_dtype="auto", plan_cache_dir=cache.directory)
    eng = LifeEngine(tiny_problem, cfg)
    assert tsearch.measurement_count() == n0
    assert eng.format_plan.reason == "predicted"
    assert eng.format_plan.format in ("coo", "sell", "alto", "fcoo")
    # the engine is usable, not just constructed
    w, losses = eng.run()
    assert losses[-1] <= losses[0]


def test_predicted_tune_plan_zero_measurements(tmp_path, tiny_problem,
                                               monkeypatch):
    """tune="cached" miss on a trained cache replays the nearest example's
    launch params as a predicted TunePlan — no search, params legal."""
    cache, predictor = _trained_cache(tmp_path / "train", format="sell",
                                      slot_tile=16)
    assert predictor is not None and predictor.tune_model is not None

    monkeypatch.setattr(tsearch, "time_call", _boom)
    cfg = LifeConfig(executor="opt", format="sell", slot_tile=16, n_iters=1,
                     tune="cached", compute_dtype="auto",
                     plan_cache_dir=cache.directory)
    eng = LifeEngine(tiny_problem, cfg)
    plan = eng.tune_plan
    assert plan is not None and plan.reason == "predicted"
    assert plan.executor == "kernel-sell"
    assert set(plan.params) == {"slot_tile"}
    assert plan.compute_dtype in ("fp32", "bf16")       # resolved, not auto
    # predicted plans are persisted: a second cached build replays it
    eng2 = LifeEngine(tiny_problem, dataclasses.replace(cfg))
    assert eng2.tune_plan == plan


def test_predicted_format_respects_allowed_and_mesh(tmp_path, tiny_problem):
    """Predicted plans always name a format from the caller's allowed /
    mesh-capable candidate set, even when the model's favourite class is
    excluded from it."""
    from repro.core.registry import REGISTRY
    from repro.formats import select as fsel
    cache, predictor = _trained_cache(tmp_path / "train")
    assert predictor is not None
    d = tiny_problem.dictionary
    for allowed in (("coo",), ("alto",), ("coo", "fcoo")):
        plan = fsel.choose_format(tiny_problem.phi, d, allowed=allowed,
                                  predictor=predictor)
        assert plan.format in allowed
    # a multi-cell mesh restricts "auto" to mesh-capable formats before
    # the predictor sees the candidate set
    cfg = LifeConfig(format="auto", shard_rows=2, shard_cols=1,
                     plan_cache_dir=cache.directory, tune="off")
    plan = fsel.resolve_format(tiny_problem.phi, tiny_problem, cfg,
                               cache=PlanCache(cache.directory))
    assert REGISTRY.mesh_executor_for(plan.format) is not None


def test_selection_determinism_across_rebuilds(tmp_path, tiny_problem):
    """Same phi + same cache dir => byte-identical FormatPlan/TunePlan on
    every rebuild (warm replay, no re-selection drift)."""
    cfg = _train_cfg(tmp_path / "c", format="auto")
    engines = [LifeEngine(tiny_problem, cfg) for _ in range(3)]
    plans = [e.format_plan for e in engines]
    tunes = [e.tune_plan for e in engines]
    assert plans[0] == plans[1] == plans[2]
    assert tunes[0] == tunes[1] == tunes[2]
    assert tunes[0] is not None and tunes[0].reason in ("search", "default")


# ----------------------------------------------------------------------------
# background refinement
# ----------------------------------------------------------------------------

def test_refine_queue_dedups_and_survives_failure():
    q = refine.RefineQueue(max_tasks=2)
    ran = []
    assert q.push("format", "k", lambda: ran.append(1))
    assert not q.push("format", "k", lambda: ran.append(2))   # dup identity
    assert q.push("tune", "k", lambda: 1 / 0)                 # distinct kind
    assert not q.push("format", "k2", lambda: None)           # full
    assert len(q) == 2
    assert q.run_one() and ran == [1]
    assert q.run_one()            # the failing task runs, is dropped, no raise
    assert not q.run_one() and len(q) == 0


def test_refinement_upgrades_predicted_plan_in_place(tmp_path, tiny_problem,
                                                     monkeypatch):
    """Draining the refine queue re-runs the measured pipeline and
    overwrites the predicted cache entries; the next rebuild replays the
    measured plans with zero measurements."""
    cache, _ = _trained_cache(tmp_path / "train", format="sell", slot_tile=16)
    cfg = LifeConfig(executor="opt", format="sell", slot_tile=16, n_iters=1,
                     tune="cached", compute_dtype="auto",
                     plan_cache_dir=cache.directory)
    monkeypatch.setattr(tsearch, "time_call", _boom)
    eng = LifeEngine(tiny_problem, cfg)
    assert eng.tune_plan.reason == "predicted"
    assert len(refine.QUEUE) >= 1

    monkeypatch.undo()            # refinement is allowed to measure
    assert run_pending() >= 1
    monkeypatch.setattr(tsearch, "time_call", _boom)
    eng2 = LifeEngine(tiny_problem, cfg)
    assert eng2.tune_plan.reason == "search"
    assert eng2.tune_plan.measurements


def test_format_refinement_upgrades_predicted_plan(tmp_path, tiny_problem,
                                                   monkeypatch):
    from repro.formats import select as fsel
    cache, predictor = _trained_cache(tmp_path / "train")
    fresh = PlanCache(cache.directory)
    monkeypatch.setattr(fsel, "_measure_formats", _boom)
    plan = fsel.choose_format(tiny_problem.phi, tiny_problem.dictionary,
                              cache=fresh, predictor=predictor)
    assert plan.reason == "predicted"
    assert len(refine.QUEUE) >= 1
    monkeypatch.undo()
    assert run_pending() >= 1
    # the cached entry is now the measured/heuristic decision
    upgraded = fsel.choose_format(tiny_problem.phi, tiny_problem.dictionary,
                                  cache=fresh, predictor=predictor)
    assert upgraded.reason in ("heuristic", "autotune")


def test_format_refinement_probes_at_the_enqueued_size(tmp_path,
                                                      tiny_problem,
                                                      monkeypatch):
    """A predicted plan's refinement measures at the ``probe_nc`` of the
    request it refines, not at the subject's exact Nc."""
    from repro import obs
    from repro.formats import select as fsel
    cache, predictor = _trained_cache(tmp_path / "train")
    fresh = PlanCache(cache.directory)
    nc_probe = tiny_problem.phi.n_coeffs + 40
    monkeypatch.setattr(fsel, "_measure_formats", _boom)
    plan = fsel.choose_format(tiny_problem.phi, tiny_problem.dictionary,
                              allowed=("coo", "alto"), cache=fresh,
                              predictor=predictor, probe_nc=nc_probe)
    assert plan.reason == "predicted"
    monkeypatch.undo()
    obs.enable()
    assert run_pending() >= 1
    probes = [e["args"] for e in obs.TRACER.export_chrome()
              if e["name"] == "select.measure"]
    assert {p["format"] for p in probes} == {"coo", "alto"}
    assert all(p["nc_probe"] == nc_probe for p in probes)
    upgraded = fsel.choose_format(tiny_problem.phi, tiny_problem.dictionary,
                                  allowed=("coo", "alto"), cache=fresh,
                                  predictor=predictor)
    assert upgraded.reason == "autotune"


def test_cache_hit_on_predicted_plan_reenqueues_refinement(tmp_path,
                                                           tiny_problem,
                                                           monkeypatch):
    """A process restart drops the in-memory queue; a predicted plan still
    serving hits must re-enqueue its refinement."""
    from repro.formats import select as fsel
    cache, predictor = _trained_cache(tmp_path / "train")
    fresh = PlanCache(cache.directory)
    monkeypatch.setattr(fsel, "_measure_formats", _boom)
    plan = fsel.choose_format(tiny_problem.phi, tiny_problem.dictionary,
                              cache=fresh, predictor=predictor)
    assert plan.reason == "predicted"
    refine.QUEUE.clear()          # simulate the restart
    hit = fsel.choose_format(tiny_problem.phi, tiny_problem.dictionary,
                             cache=fresh, predictor=predictor)
    assert hit.reason == "predicted"
    assert len(refine.QUEUE) == 1


def test_frontend_idle_tick_drains_refine_queue(tiny_problem):
    """The serve driver spends idle ticks on refinement tasks — without a
    single job ever being submitted."""
    from repro.serve.frontend import LifeFrontend
    ran = []
    refine.QUEUE.push("format", "idle-test", lambda: ran.append(1))
    with LifeFrontend(LifeConfig(n_iters=1, plan_cache_dir=""),
                      idle_wait=0.001) as fe:
        deadline = time.monotonic() + 5.0
        while not ran and time.monotonic() < deadline:
            time.sleep(0.01)
        assert fe.service is not None
    assert ran == [1]
    assert len(refine.QUEUE) == 0


def test_frontend_refine_disabled_leaves_queue(tiny_problem):
    from repro.serve.frontend import LifeFrontend
    ran = []
    refine.QUEUE.push("format", "disabled-test", lambda: ran.append(1))
    with LifeFrontend(LifeConfig(n_iters=1, plan_cache_dir=""),
                      idle_wait=0.001, refine=False):
        time.sleep(0.1)
    assert ran == [] and len(refine.QUEUE) == 1


# ----------------------------------------------------------------------------
# config surface
# ----------------------------------------------------------------------------

def test_predict_off_disables_the_rung(tmp_path, tiny_problem):
    cache, predictor = _trained_cache(tmp_path / "train")
    assert predictor is not None          # a trained model exists...
    cfg = LifeConfig(executor="opt", format="auto", n_iters=1, tune="cached",
                     predict="off", plan_cache_dir=cache.directory)
    eng = LifeEngine(tiny_problem, cfg)
    # ...but predict="off" skips the rung: heuristic/measured only
    assert eng.format_plan.reason in ("heuristic", "autotune")
    assert eng.tune_plan.reason != "predicted"


def test_predict_validation():
    from repro.tune.tuner import validate_config
    with pytest.raises(ValueError, match="predict"):
        validate_config(LifeConfig(predict="sometimes"))


def test_predictor_file_location(tmp_path):
    assert predictor_path(str(tmp_path)).endswith("predictor.json")
