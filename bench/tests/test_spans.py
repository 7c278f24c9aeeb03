"""The span and scope reductions of bench/spans.py.

Synthetic intervals check the attribution's arithmetic: its precedence,
nesting, and parts that sum to the idle time.  ``data/small.xplane.pb.gz``
(bench/tests/test_trace.py) checks the wire-format reader against
``jax.profiler.ProfileData``; ``data/spans.xplane.pb.gz``, recorded on one
TPU v5e chip by ``record_spans.py`` (two small solves and a virtual-lesion
query through the front end with the program's tracing on), checks that
the reductions find the program's spans and the solver's scopes in a real
trace."""
from pathlib import Path

import pytest

from bench import harness, spans, trace

DATA = Path(__file__).parent / "data"
SMALL = DATA / "small.xplane.pb.gz"
RECORDED = DATA / "spans.xplane.pb.gz"
DEVICE = "/device:TPU:0"


def _xspace(ops, *threads):
    return spans.Xspace(ops={DEVICE: trace.Events.of(list(ops))},
                        threads=[trace.Events.of(list(t)) for t in threads])


def test_interval_arithmetic():
    a = spans.union([(5, 6), (0, 2), (1, 3), (4, 4)])
    assert a == [(0, 3), (5, 6)]
    b = [(1, 2), (2.5, 5.5)]
    assert spans.minus(a, b) == [(0, 1), (2, 2.5), (5.5, 6)]
    assert spans.meet(a, b) == [(1, 2), (2.5, 3), (5, 5.5)]
    assert spans.length(spans.minus(a, [])) == pytest.approx(4.0)
    assert spans.minus(a, [(-1, 9)]) == []


def test_idle_is_attributed_by_precedence():
    """Compile beats build beats intake; a PjitFunction counts as compile
    only around a compile event of its own thread; the parts sum to the
    idle time."""
    server = [("bench.window", 0.0, 10.0),
              ("engine.build", 0.5, 2.5),
              ("engine.step", 1.0, 6.0),
              ("PjitFunction(run_batch)", 1.5, 4.0),
              ("lower_sharding_computation", 2.0, 3.0),
              ("PjitFunction(add)", 7.2, 7.3)]
    client = [("service.submit", 7.0, 8.0), ("lesion.edit", 8.5, 9.0),
              ("PjitFunction(take)", 1.0, 5.0)]
    xs = _xspace([("fusion", 0.0, 1.0), ("fusion", 6.0, 7.0)],
                 server, client)
    out = spans.reduce(xs)
    assert out["window_s"] == pytest.approx(10.0)
    assert out["busy_s"] == pytest.approx(2.0)
    assert out["idle_s"] == pytest.approx(8.0)
    parts = out["idle"]
    assert parts["compile"] == pytest.approx(2.5)     # [1.5, 4]
    assert parts["build"] == pytest.approx(0.5)       # [1, 1.5]
    assert parts["intake"] == pytest.approx(1.5)      # [7, 8], [8.5, 9]
    assert parts["other"] == pytest.approx(3.5)
    assert sum(parts.values()) == pytest.approx(out["idle_s"])
    assert out["spans"]
    # what no layer explains ([4, 6], [8, 8.5], [9, 10]), by the shortest
    # span or call around it on any thread
    assert dict(out["other_by_span"]) == pytest.approx(
        {"PjitFunction(take)": 1.0, "engine.step": 1.0, "bench.window": 1.5})


def test_nothing_outside_the_window_counts():
    server = [("backend_compile", 0.0, 3.0), ("bench.window", 2.0, 6.0),
              ("engine.build", 5.0, 9.0)]
    out = spans.reduce(_xspace([("fusion", 2.0, 4.0)], server))
    assert out["idle_s"] == pytest.approx(2.0)
    assert out["idle"] == pytest.approx(
        {"compile": 0.0, "build": 1.0, "intake": 0.0, "other": 1.0})


def test_a_program_without_spans_reads_nothing():
    xs = _xspace([("jit(f)/mul:", 0.0, 1.0)],
                 [("bench.window", 0.0, 2.0), ("backend_compile", 1.0, 2.0)])
    out = spans.reduce(xs)
    assert not out["spans"]
    assert not any(out["scopes"][k] for k in spans.SCOPES)
    spans.LAST = out

    class Done:
        def done(self):
            return [object()]

    try:
        assert spans.idle_per_answer(Done(), "compile") is None
        assert spans.scope_per_answer(Done(), "dsc") is None
        spans.LAST = None
        assert spans.idle_per_answer(Done(), "compile") is None
    finally:
        spans.LAST = None
    assert spans.reduce(spans.Xspace(ops={}, threads=[])) is None


def test_scope_seconds_take_the_innermost_scope():
    ops = [("jit(run_batch)/while", 0.0, 10.0),
           ("jit(run_batch)/while/body/closed_call/vmap(sbbnnls.dsc)/mul:",
            1.0, 2.0),
           ("jit(run_batch)/while/body/sbbnnls.dsc/jit(dsc)/scatter-add:",
            1.5, 3.0),
           ("jit(f)/sbbnnls.bb/cond/branch_1_fun/sbbnnls.wc/dot_general:",
            4.0, 5.0),
           ("jit(f)/sbbnnls.bb/max:", 5.0, 5.5),
           ("jit(f)/sbbnnls.dscx/mul:", 6.0, 7.0),
           ("jit(f)/vmap(sbbnnls.wc)/mul:", 9.0, 12.0),
           ("(custom fusion)", 7.0, 8.0), ("(conditional)", 4.0, 5.5)]
    xs = _xspace(ops, [("bench.window", 0.0, 10.0)])
    assert spans.scope_seconds(xs, 0.0, 10.0) == pytest.approx(
        {"dsc": 2.0, "wc": 2.0, "bb": 0.5, "unnamed": 1.0})


def test_wire_reader_agrees_with_profile_data():
    xs = spans.read(SMALL)
    tr = trace.load(SMALL)
    assert spans.window(xs) == pytest.approx(trace.span(tr, "bench.window"),
                                             abs=1e-9)
    mine, theirs = xs.ops[DEVICE], tr.ops[DEVICE]
    assert len(mine) == len(theirs)
    assert max(abs(mine.start - theirs.start)) < 1e-8
    assert max(abs(mine.end - theirs.end)) < 1e-8
    # the tf_op stat is each op's scope path
    assert any(n.startswith("jit(bench_dsc)/jit(dsc)/") for n in mine.names)
    out = spans.reduce(xs)
    assert out["busy_s"] == pytest.approx(trace.summary(tr)["busy_s"],
                                          abs=1e-7)


@pytest.mark.skipif(not RECORDED.exists(), reason="recorded trace absent")
def test_recorded_front_end_trace():
    xs = spans.read(RECORDED)
    names = {n for ev in xs.threads for n in ev.names}
    assert {"engine.build", "engine.step", "service.submit", "lesion.edit",
            "scheduler.tick", "scheduler.slice"} <= names
    out = spans.reduce(xs)
    assert out["scopes"]["dsc"] > 0 and out["scopes"]["wc"] > 0
    assert out["scopes"]["bb"] > 0
    assert sum(out["scopes"].values()) <= out["busy_s"] + 1e-9
    # the batched WC's fiber scatter carries no tf_op on the v5e
    assert out["scopes"]["unnamed"] > 0
    parts = out["idle"]
    assert parts["compile"] > 0 and parts["build"] > 0
    assert parts["intake"] > 0
    assert sum(parts.values()) == pytest.approx(out["idle_s"])
    assert out["busy_s"] + out["idle_s"] == pytest.approx(out["window_s"])


@pytest.mark.skipif(not RECORDED.exists(), reason="recorded trace absent")
def test_recorded_spans_carry_their_jobs():
    import gzip

    from jax.profiler import ProfileData
    data = ProfileData.from_serialized_xspace(gzip.open(RECORDED).read())
    stats = {}
    for plane in data.planes:
        if plane.name.startswith(trace.HOST_PREFIX):
            for line in plane.lines:
                for e in line.events:
                    if e.name in spans.PROGRAM_SPANS:
                        stats.setdefault(e.name, []).append(dict(e.stats))
    jobs = {s["job"] for s in stats["service.submit"]}
    assert len(jobs) == 3
    for name in ("engine.build", "engine.step"):
        assert {s["jobs"] for s in stats[name]} <= jobs
    assert {s["fibers"] for s in stats["lesion.edit"]} == {50}


def test_install_turns_obs_on_for_the_window_only(monkeypatch):
    """The harness turns the program's tracing on around a traced window
    of either loop (and leaves it alone otherwise); install() only
    analyses the trace the harness loads."""
    from repro import obs
    seen = []
    monkeypatch.setattr(spans, "_INSTALLED", False)
    monkeypatch.setattr(spans, "LAST", None)
    monkeypatch.setattr(trace, "load", lambda path: path)
    windows = dict(harness.WINDOWS)
    spans.install()
    spans.install()                        # idempotent: wraps once
    assert harness.WINDOWS == windows      # no window is wrapped
    with harness.program_tracing(True):
        seen.append(obs.enabled())
    with harness.program_tracing(False):
        seen.append(obs.enabled())
    assert seen == [True, False] and not obs.enabled()
    assert trace.load(SMALL) == SMALL      # the harness's load still runs
    assert spans.LAST is not None and not spans.LAST["spans"]


@pytest.mark.skipif(not RECORDED.exists(), reason="recorded trace absent")
def test_wire_reader_keeps_each_steps_jobs():
    """The steps' job ids as the wire reader reads them are those
    ``jax.profiler.ProfileData`` gives, at the same times."""
    import gzip

    from jax.profiler import ProfileData
    data = ProfileData.from_serialized_xspace(gzip.open(RECORDED).read())
    theirs = sorted(
        (e.start_ns * 1e-9, tuple(str(dict(e.stats)["jobs"]).split()))
        for plane in data.planes if plane.name.startswith(trace.HOST_PREFIX)
        for line in plane.lines for e in line.events
        if e.name == spans.STEP)
    mine = spans.read(RECORDED).steps
    assert len(mine) == len(theirs) > 0
    for (s, _, jobs), (t, want) in zip(mine, theirs):
        assert s == pytest.approx(t, abs=1e-6) and jobs == want
    lo, _ = spans.window(spans.read(RECORDED))
    out = spans.reduce(spans.read(RECORDED))
    assert [jobs for _, jobs in out["steps"]] == [j for _, j in theirs]
    assert out["steps"][0][0] == pytest.approx(theirs[0][0] - lo, abs=1e-6)


def test_queue_and_step_jobs_from_steps(monkeypatch):
    """queue_s: median over jobs of (first step naming the job - due);
    step_jobs: mean jobs a step; jobs never stepped and closed-loop jobs
    are left out, and nothing is read without steps."""
    from bench import traffic
    xs = spans.Xspace(
        ops={DEVICE: trace.Events.of([("fusion", 10.0, 11.0)])},
        threads=[trace.Events.of([("bench.window", 10.0, 20.0)])],
        steps=[(9.0, 9.5, ("0",)), (10.5, 11.0, ("0",)),
               (11.0, 12.0, ("0", "1")), (12.5, 13.0, ("1", "2", "3")),
               (21.0, 22.0, ("4",))])
    out = spans.reduce(xs)
    assert [s for s, _ in out["steps"]] == pytest.approx([0.5, 1.0, 2.5])
    monkeypatch.setattr(spans, "LAST", out)

    def job(jid, due):
        req = traffic.Request(subject=0, n_iters=1, at=due)
        return harness.Job(req=req, sent=due or 0.0, due=due, job_id=jid)

    class Run:
        jobs = [job("0", 0.25), job("1", 0.5), job("2", 1.5), job("4", 2.0),
                job(None, None)]

    # waits 0.25, 0.5, 1.0 (job 4 never stepped in the window)
    assert spans.queue_seconds(Run()) == pytest.approx(0.5)
    assert spans.step_jobs() == pytest.approx((1 + 2 + 3) / 3)
    monkeypatch.setattr(spans, "LAST", dict(out, steps=[]))
    assert spans.queue_seconds(Run()) is None and spans.step_jobs() is None
