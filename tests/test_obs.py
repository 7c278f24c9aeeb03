"""Observability layer: overhead contract, quantiles, invariants, traces.

Four contracts from DESIGN.md §12:

* disabled instruments are allocation-free no-ops (tracemalloc-pinned);
* the shared :func:`repro.obs.quantile` — and the histogram reservoir
  below its cap — match ``np.percentile`` exactly (hypothesis);
* the scheduler's counter algebra holds at every tick of a randomized
  trace: ``admitted == completed + queued + running``;
* span nesting round-trips through the flat Chrome-trace export by
  interval containment.
"""
import os
import tracemalloc

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import obs
from repro.obs.metrics import MetricsRegistry, quantile
from repro.obs.trace import _NOOP_SPAN, Tracer

TEST_SEED = int(os.environ.get("REPRO_TEST_SEED", "0"))


# ----------------------------------------------------------------------------
# overhead contract
# ----------------------------------------------------------------------------

def _hot_loop(c, g, h, t, n=500):
    for _ in range(n):
        c.inc()
        g.set(3.0)
        h.observe(1.5)
        with t.span("hot"):
            pass


def test_disabled_instruments_allocate_nothing():
    """With the switch off, held instruments and span() must not allocate:
    tracemalloc attributes zero new bytes to the obs module sources.

    A genuine disabled-path allocation reproduces on every attempt; a
    full-suite process carries background allocation noise (jax worker
    threads, arena reuse), so the check retries a few times and passes on
    the first clean measurement."""
    import gc

    from repro.obs import metrics as metrics_mod
    from repro.obs import trace as trace_mod

    reg = MetricsRegistry()
    t = Tracer()
    c = reg.counter("x.count")
    g = reg.gauge("x.gauge")
    h = reg.histogram("x.hist")
    assert not obs.enabled()

    filters = [tracemalloc.Filter(True, metrics_mod.__file__),
               tracemalloc.Filter(True, trace_mod.__file__)]
    grew = None
    for _ in range(3):
        gc.collect()
        tracemalloc.start()
        try:
            _hot_loop(c, g, h, t)             # warm any lazy caches
            before = tracemalloc.take_snapshot()
            _hot_loop(c, g, h, t)
            after = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        diff = after.filter_traces(filters).compare_to(
            before.filter_traces(filters), "lineno")
        grew = [s for s in diff if s.size_diff > 0]
        if not grew:
            break
    assert not grew, f"disabled path allocated: {grew}"
    # and nothing was recorded
    assert c.value == 0.0 and g.value == 0.0 and h.count == 0
    assert t.roots == []


def test_disabled_span_is_shared_noop():
    t = Tracer()
    s = t.span("anything", {"ignored": 1})
    assert s is _NOOP_SPAN
    with s as inner:
        inner.set_attr("k", "v")              # must be inert, not raise
    assert t.roots == []


# ----------------------------------------------------------------------------
# quantiles vs numpy
# ----------------------------------------------------------------------------

@st.composite
def float_samples(draw):
    n = draw(st.integers(1, 200))
    lo = draw(st.floats(-1e6, 1e6))
    spread = draw(st.floats(0.0, 1e6))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    return (lo + spread * rng.random(n)).tolist()


@settings(max_examples=40, deadline=None)
@given(float_samples(), st.floats(0.0, 100.0))
def test_quantile_matches_numpy(xs, q):
    assert quantile(xs, q) == pytest.approx(
        float(np.percentile(xs, q)), rel=1e-9, abs=1e-6)


@settings(max_examples=15, deadline=None)
@given(float_samples())
def test_histogram_exact_below_reservoir_cap(xs):
    obs.enable()
    reg = MetricsRegistry()
    h = reg.histogram("h", max_samples=4096)
    for x in xs:
        h.observe(x)
    assert h.count == len(xs)
    assert h.sum == pytest.approx(sum(xs))
    assert h.min == min(xs) and h.max == max(xs)
    for q in (0.0, 25.0, 50.0, 95.0, 100.0):
        assert h.quantile(q) == pytest.approx(
            float(np.percentile(xs, q)), rel=1e-9, abs=1e-6)


def test_histogram_reservoir_is_deterministic_and_bounded():
    obs.enable()
    xs = np.random.default_rng(TEST_SEED).random(5000).tolist()

    def fill():
        h = MetricsRegistry().histogram("h.bounded", max_samples=256)
        for x in xs:
            h.observe(x)
        return h

    h1, h2 = fill(), fill()
    assert len(h1._samples) == 256 and h1.count == 5000
    # same name + same stream -> identical reservoir (repeatable quantiles)
    assert h1._samples == h2._samples
    # the estimate still lands near the true distribution
    assert h1.quantile(50.0) == pytest.approx(
        float(np.percentile(xs, 50.0)), abs=0.1)


def test_quantile_rejects_out_of_range():
    with pytest.raises(ValueError):
        quantile([1.0], 101.0)


# ----------------------------------------------------------------------------
# registry semantics
# ----------------------------------------------------------------------------

def test_reset_preserves_instrument_identity():
    """reset() zeroes in place: a handle cached before the reset keeps
    recording into the same instrument afterwards (what lets the serving
    stack survive table13's per-rate resets)."""
    obs.enable()
    reg = MetricsRegistry()
    c = reg.counter("kept", role="x")
    c.inc(5.0)
    reg.reset()
    assert c.value == 0.0
    c.inc(2.0)
    assert reg.counter("kept", role="x") is c
    assert reg.value("kept", role="x") == 2.0


def test_total_sums_matching_labels():
    obs.enable()
    reg = MetricsRegistry()
    reg.counter("lk", kind="tile", outcome="hit").inc(3.0)
    reg.counter("lk", kind="tune", outcome="hit").inc(2.0)
    reg.counter("lk", kind="tile", outcome="miss").inc(7.0)
    assert reg.total("lk", outcome="hit") == 5.0
    assert reg.total("lk") == 12.0
    assert reg.total("other") == 0.0


def test_snapshot_shape_and_reader():
    obs.enable()
    reg = MetricsRegistry()
    reg.counter("c", a="1").inc(4.0)
    reg.gauge("g").set(2.5)
    h = reg.histogram("h")
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["schema"] == "obs-1"
    from repro.obs import snapshot_value
    assert snapshot_value(snap, "counters", "c", {"a": 1}) == 4.0
    assert snapshot_value(snap, "gauges", "g") == 2.5
    assert snapshot_value(snap, "gauges", "missing") is None
    (he,) = snap["histograms"]
    assert he["count"] == 3 and he["quantiles"]["p50"] == 2.0
    # JSON-serializable end to end (no NaN/Inf for non-empty histograms)
    import json
    json.dumps(snap, allow_nan=False)


# ----------------------------------------------------------------------------
# span tracing
# ----------------------------------------------------------------------------

def _rebuild_by_containment(events):
    """Reconstruct the span tree from flat Chrome complete events."""
    nodes = [dict(e, children=[]) for e in
             sorted(events, key=lambda e: (e["ts"], -e["dur"]))]
    roots, stack = [], []
    for n in nodes:
        while stack and not (stack[-1]["ts"] <= n["ts"] and
                             n["ts"] + n["dur"] <= stack[-1]["ts"]
                             + stack[-1]["dur"]):
            stack.pop()
        (stack[-1]["children"] if stack else roots).append(n)
        stack.append(n)
    return roots


def _names(tree):
    return [(n["name"], _names(n["children"])) for n in tree]


def test_span_nesting_roundtrips_through_chrome_export():
    obs.enable()
    t = Tracer()
    with t.span("root", {"k": 1}):
        with t.span("child-a"):
            with t.span("leaf"):
                pass
        with t.span("child-b"):
            pass
    with t.span("root2"):
        pass

    tree = t.export()
    assert _names_from_dicts(tree) == [
        ("root", [("child-a", [("leaf", [])]), ("child-b", [])]),
        ("root2", []),
    ]
    assert tree[0]["attrs"] == {"k": 1}
    assert all(n["dur_us"] >= 0 for n in tree)

    rebuilt = _rebuild_by_containment(t.export_chrome())
    assert _names(rebuilt) == _names_from_dicts(tree)

    import json
    payload = json.loads(t.to_chrome_json())
    assert {e["ph"] for e in payload["traceEvents"]} == {"X"}


def _names_from_dicts(tree):
    return [(n["name"], _names_from_dicts(n["children"])) for n in tree]


def test_span_attrs_and_monotonic_durations():
    obs.enable()
    t = Tracer()
    with t.span("op") as sp:
        sp.set_attr("bytes", 128)
    (root,) = t.export()
    assert root["attrs"]["bytes"] == 128
    assert root["dur_us"] >= 0.0


def test_spans_nest_per_thread():
    """A span opened on another thread while one is open here is a root of
    its own, never a child of this thread's span."""
    import threading

    obs.enable()
    t = Tracer()
    inside = threading.Event()
    release = threading.Event()

    def client():
        with t.span("client"):
            with t.span("client.inner"):
                inside.set()
                release.wait(5.0)

    with t.span("server"):
        th = threading.Thread(target=client)
        th.start()
        assert inside.wait(5.0)
        with t.span("server.inner"):
            pass
        release.set()
        th.join()
    assert sorted(_names_from_dicts(t.export())) == [
        ("client", [("client.inner", [])]),
        ("server", [("server.inner", [])])]
    tids = {e["name"]: e["tid"] for e in t.export_chrome()}
    assert tids["client"] == tids["client.inner"] != tids["server"]


def test_spans_reach_the_profiler_trace(tiny_cohort, tmp_path):
    """With obs on, the service's, the scheduler's and the engine's spans
    are host events of a jax.profiler trace, their attributes stats."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from repro.core.batched import ladder_size
    from repro.core.life import LifeConfig
    from repro.serve import LifeService

    obs.enable()
    svc = LifeService(LifeConfig(executor="opt", n_iters=4,
                                 plan_cache_dir=""), slice_iters=4)
    jax.profiler.start_trace(str(tmp_path))
    try:
        svc.submit(tiny_cohort[0], job_id="a1", n_iters=4, format="coo")
        svc.run()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    stats = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    stats.setdefault(e.name, dict(e.stats))
    assert stats["service.submit"]["job"] == "a1"
    assert stats["engine.build"]["jobs"] == "a1"
    assert stats["engine.build"]["nc"] == tiny_cohort[0].phi.n_coeffs
    assert stats["engine.build"]["nc_padded"] == ladder_size(
        tiny_cohort[0].phi.n_coeffs)
    assert stats["engine.step"]["jobs"] == "a1"
    assert stats["engine.step"]["k"] == 4
    assert stats["scheduler.slice"]["jobs"] == "a1"
    assert "scheduler.tick" in stats


def test_batched_runner_lookups_and_padded_build(tiny_cohort, monkeypatch):
    """Two engines on subjects of one ladder size: the first misses the
    runner memo, the second hits it; each build span carries nc_padded."""
    import dataclasses

    import jax

    import repro.core.batched as batched
    from repro.core.life import LifeConfig

    monkeypatch.setattr(batched, "_RUNNERS", {})
    p = tiny_cohort[0]
    n = p.phi.n_coeffs - 2
    other = dataclasses.replace(
        p, phi=jax.tree_util.tree_map(lambda a: a[:n], p.phi))
    assert batched.ladder_size(n) == batched.ladder_size(p.phi.n_coeffs)
    cfg = LifeConfig(executor="opt", n_iters=4, plan_cache_dir="")
    obs.enable()
    batched.BatchedLifeEngine([p], cfg)
    assert obs.value("engine.runner.lookups", outcome="miss") == 1.0
    assert obs.value("engine.runner.lookups", outcome="hit") == 0.0
    batched.BatchedLifeEngine([other], cfg)
    assert obs.value("engine.runner.lookups", outcome="miss") == 1.0
    assert obs.value("engine.runner.lookups", outcome="hit") == 1.0
    builds = [s["attrs"] for s in obs.TRACER.export()
              if s["name"] == "engine.build"]
    assert [b["nc"] for b in builds] == [p.phi.n_coeffs, n]
    assert [b["nc_padded"] for b in builds] == [
        batched.ladder_size(p.phi.n_coeffs)] * 2


def test_solver_ops_carry_their_scope(tiny_problem):
    """The compiled solver's HLO names DSC, WC and the BB update in its op
    metadata, which a profiler trace reports as each op's tf_op."""
    from repro.core.life import LifeConfig, LifeEngine
    from repro.core.sbbnnls import _as_partial, _steps

    eng = LifeEngine(tiny_problem, LifeConfig(executor="opt",
                                              plan_cache_dir=""))
    hlo = _steps.lower(_as_partial(eng.matvec), _as_partial(eng.rmatvec),
                       tiny_problem.b, eng.init_state(),
                       n_iters=2).compile().as_text()
    for scope in ("sbbnnls.dsc", "sbbnnls.wc", "sbbnnls.bb"):
        assert f"/{scope}/" in hlo, scope


def test_tracer_bounds_recorded_spans():
    obs.enable()
    t = Tracer(max_spans=3)
    for _ in range(5):
        with t.span("s"):
            pass
    assert len(t.roots) == 3 and t.dropped == 2
    t.reset()
    assert t.roots == [] and t.dropped == 0


# ----------------------------------------------------------------------------
# scheduler counter invariant over randomized traces
# ----------------------------------------------------------------------------

def test_scheduler_counters_hold_over_random_traces(tiny_cohort):
    """At every observable point of a randomized submit/tick interleaving:
    admitted == completed + queued + running (DESIGN.md §12.2)."""
    from repro.core.life import LifeConfig
    from repro.serve import LifeService

    obs.enable()
    rng = np.random.default_rng(100 + TEST_SEED)
    for trial in range(3):
        obs.reset()
        svc = LifeService(LifeConfig(executor="opt", n_iters=8,
                                     plan_cache_dir=""), slice_iters=3)
        pending = [(p, ["coo", "auto", "sell", "fcoo"][rng.integers(4)],
                    int(rng.integers(0, 3)), int(rng.integers(4, 12)))
                   for p in tiny_cohort]

        def check():
            admitted = obs.value("serve.jobs.admitted")
            completed = obs.value("serve.jobs.completed")
            queued = obs.value("serve.queue.depth")
            running = obs.value("serve.jobs.running")
            assert admitted == completed + queued + running, (
                f"trial {trial}: admitted={admitted} != "
                f"completed={completed} + queued={queued} + "
                f"running={running}")

        i = 0
        while pending or svc.scheduler.active():
            if pending and (not svc.scheduler.active()
                            or rng.random() < 0.5):
                p, fmt, pri, n = pending.pop()
                svc.submit(p, job_id=f"t{trial}-j{i}", n_iters=n,
                           format=fmt, priority=pri)
                i += 1
            else:
                svc.step()
            check()
        assert obs.value("serve.jobs.admitted") == len(tiny_cohort)
        assert obs.value("serve.jobs.completed") == len(tiny_cohort)
        assert obs.histogram("serve.queue.depth").count > 0
        assert obs.histogram("serve.slice.seconds").count > 0


def test_extended_counter_algebra_with_failures_and_cancels(tiny_cohort):
    """The §13 extension of the invariant above, under randomized
    submit/tick interleavings with poisoned tenants and a cancellation:
    admitted == completed + failed + cancelled + queued + running."""
    import dataclasses

    from repro.core.life import LifeConfig
    from repro.serve import LifeService

    obs.enable()
    rng = np.random.default_rng(300 + TEST_SEED)
    svc = LifeService(LifeConfig(executor="opt", n_iters=8,
                                 plan_cache_dir=""), slice_iters=3)
    pending = [(tiny_cohort[0], "h0", 40), (tiny_cohort[1], "h1", 6),
               (tiny_cohort[2], "h2", 6),
               (dataclasses.replace(tiny_cohort[0],
                                    b=np.asarray(tiny_cohort[0].b)[:-3]),
                "p0", 6),
               (dataclasses.replace(tiny_cohort[1],
                                    b=np.asarray(tiny_cohort[1].b)[:-3]),
                "p1", 6)]
    rng.shuffle(pending)

    def check():
        admitted = obs.value("serve.jobs.admitted")
        completed = obs.value("serve.jobs.completed")
        failed = obs.value("serve.jobs.failed")
        cancelled = obs.value("serve.jobs.cancelled")
        queued = obs.value("serve.queue.depth")
        running = obs.value("serve.jobs.running")
        assert admitted == (completed + failed + cancelled
                            + queued + running), (
            f"admitted={admitted} != completed={completed} + "
            f"failed={failed} + cancelled={cancelled} + "
            f"queued={queued} + running={running}")

    submitted = set()
    cancelled_h0 = False
    tried_cancel = False
    steps = 0
    while pending or svc.scheduler.active():
        if pending and (not svc.scheduler.active() or rng.random() < 0.5):
            p, jid, n = pending.pop()
            svc.submit(p, job_id=jid, n_iters=n, format="coo")
            submitted.add(jid)
        else:
            svc.step()
            steps += 1
            if not tried_cancel and steps >= 3 and "h0" in submitted:
                tried_cancel = True             # mid-flight cancellation
                cancelled_h0 = svc.cancel("h0")
                check()
        check()
    assert obs.value("serve.jobs.admitted") == 5.0
    assert obs.value("serve.jobs.failed") == 2.0
    assert obs.value("serve.jobs.cancelled") == float(cancelled_h0)
    assert svc.failed_jobs == ("p0", "p1")


def test_service_latency_and_snapshot_surface(tiny_cohort):
    """submit->finish latency lands in the histogram and
    metrics_snapshot() mirrors the plan-cache stats into gauges."""
    from repro.core.life import LifeConfig
    from repro.serve import LifeService

    obs.enable()
    svc = LifeService(LifeConfig(executor="opt", n_iters=6,
                                 plan_cache_dir=""), slice_iters=3)
    for i, p in enumerate(tiny_cohort):
        svc.submit(p, job_id=f"j{i}", n_iters=6, format="coo")
    svc.run()
    lat = obs.histogram("serve.job.latency.seconds")
    assert lat.count == len(tiny_cohort)
    assert lat.min >= 0.0
    snap = svc.metrics_snapshot()
    from repro.obs import snapshot_value
    assert snapshot_value(snap, "gauges", "plan_cache.hit_rate") is not None
    assert snap["spans"]["recorded"] > 0


# ----------------------------------------------------------------------------
# plan cache + engine surfacing
# ----------------------------------------------------------------------------

def test_plan_cache_lookup_counters_by_kind(tiny_problem, tmp_path):
    """Engine builds drive the labeled lookup counters: a cold kernel build
    misses tile plans, a warm rebuild hits every one."""
    from repro.core.life import LifeConfig, LifeEngine
    from repro.core.plan_cache import PlanCache

    obs.enable()
    cfg = LifeConfig(executor="kernel", plan_cache_dir=str(tmp_path))
    LifeEngine(tiny_problem, cfg)
    misses = obs.total("plan_cache.lookups", kind="tile", outcome="miss")
    assert misses > 0
    obs.reset()
    warm = PlanCache(str(tmp_path))
    eng = LifeEngine(tiny_problem, cfg, warm)
    assert obs.total("plan_cache.lookups", outcome="miss") == 0.0
    assert obs.total("plan_cache.lookups", kind="tile",
                     outcome="hit") == misses
    assert eng.cache_stats.hit_rate == 1.0
    obs.record_cache_stats(eng.cache_stats)
    assert obs.value("plan_cache.hit_rate") == 1.0


def test_cache_stats_hit_rate_property():
    from repro.core.plan_cache import CacheStats
    s = CacheStats()
    assert s.hit_rate == 0.0 and s.lookups == 0
    s.record(True, kind="tile")
    s.record(False, kind="tile")
    assert s.lookups == 2 and s.hit_rate == 0.5


def test_engine_step_populates_histogram_and_roofline(tiny_problem):
    """The step histogram is recorded, and an obs-on step compiles no more
    programs than an obs-off one (the roofline gauges that used to compile
    the SpMV pair again are gone; the benchmark's trace reads rooflines)."""
    import jax.monitoring as mon

    from repro.core.life import LifeConfig, LifeEngine

    compiles = []

    def count(event, *_, **__):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    def fresh_step_compiles(k):
        eng = LifeEngine(tiny_problem, LifeConfig(executor="opt", n_iters=4,
                                                  plan_cache_dir=""))
        state = eng.init_state()
        del compiles[:]
        eng.step(state, k)          # a new k: the solver compiles afresh
        return len(compiles)

    mon.register_event_duration_secs_listener(count)
    try:
        off = fresh_step_compiles(3)
        obs.enable()
        on = fresh_step_compiles(5)
    finally:
        mon.unregister_event_duration_listener(count)
    assert off >= 1 and on == off
    h = obs.histogram("engine.step.seconds", executor="opt")
    assert h.count == 1
    (root,) = [s for s in obs.TRACER.export() if s["name"] == "engine.step"]
    assert root["attrs"]["k"] == 5
    assert not [g for g in obs.snapshot()["gauges"]
                if g["name"].startswith("engine.")]


def test_disabled_stack_records_nothing(tiny_problem):
    """The instrumented production stack writes nothing while disabled."""
    from repro.core.life import LifeConfig, LifeEngine

    assert not obs.enabled()
    eng = LifeEngine(tiny_problem, LifeConfig(executor="opt", n_iters=4,
                                              plan_cache_dir=""))
    state = eng.init_state()
    eng.step(state, 4)
    snap = obs.snapshot()
    assert all(c["value"] == 0.0 for c in snap["counters"])
    assert all(h["count"] == 0 for h in snap["histograms"])
    assert snap["spans"]["recorded"] == 0
