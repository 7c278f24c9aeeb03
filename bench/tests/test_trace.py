"""The trace reduction and the compulsory-work functions.

Synthetic intervals check the arithmetic; ``data/small.xplane.pb.gz``, a
trace recorded on one TPU v5e chip (a window holding three small jitted
calls, a 20 ms annotated wait and two isolated ``bench_dsc`` calls on a
5,000-fiber subject), checks that the reduction reads a real trace."""
from pathlib import Path

import pytest

from bench import peaks, trace, work

DATA = Path(__file__).parent / "data" / "small.xplane.pb.gz"


def _trace(ops, host=()):
    return trace.Trace(ops={"/device:TPU:0": trace.Events.of(list(ops))},
                       modules={}, host=trace.Events.of(list(host)))


def test_union_clips_and_merges():
    ev = trace.Events.of([("a", 0.0, 2.0), ("b", 1.0, 3.0), ("c", 5.0, 6.0),
                          ("d", 9.0, 12.0)])
    assert trace.merged(ev, 1.5, 10.0) == [(1.5, 3.0), (5.0, 6.0),
                                           (9.0, 10.0)]
    t = _trace([("a", 0.0, 2.0), ("b", 1.0, 3.0), ("c", 5.0, 6.0)])
    assert trace.busy_s(t, 0.0, 10.0) == pytest.approx(4.0)
    assert trace.idle_share(t, 0.0, 10.0) == pytest.approx(0.6)


def test_busy_is_averaged_over_devices():
    t = trace.Trace(ops={"/device:TPU:0": trace.Events.of([("a", 0, 4)]),
                         "/device:TPU:1": trace.Events.of([("a", 0, 2)])},
                    modules={}, host=trace.Events.of([]))
    assert trace.busy_s(t, 0.0, 10.0) == pytest.approx(3.0)


def test_top_ops_and_gaps_are_named():
    t = _trace([("fusion", 0.0, 1.0), ("scatter", 1.0, 4.0),
                ("fusion", 6.0, 7.0), ("fusion", 9.5, 10.0)],
               host=[("bench.window", 0.0, 10.0), ("bench.wait", 3.0, 9.0),
                     ("backend_compile", 4.2, 5.9)])
    assert trace.top_ops(t, 0.0, 10.0) == [("scatter", 3.0),
                                           ("fusion", 2.5)]
    gaps = trace.idle_gaps(t, 0.0, 10.0)
    assert gaps[0] == ("bench.wait", pytest.approx(2.5))
    assert gaps[1] == ("bench.wait / backend_compile", pytest.approx(2.0))
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps),
                                          reverse=True)


def test_module_seconds_are_those_started_in_the_span():
    """Whatever their names: a program compiled alike before the isolated
    call lends it its name on the device."""
    t = trace.Trace(ops={}, modules={
        "/device:TPU:0": trace.Events.of([("jit_dsc(1)", 0.5, 0.75),
                                          ("jit_dsc(1)", 2.0, 2.5),
                                          ("jit_other(2)", 3.0, 3.25),
                                          ("jit_dsc(1)", 3.9, 4.5)]),
        "/device:TPU:1": trace.Events.of([("jit_dsc(1)", 2.0, 2.25)])},
        host=trace.Events.of([]))
    assert sorted(trace.module_seconds(t, 1.0, 4.0)) == pytest.approx(
        [0.25, 0.25, 0.5, 0.6])


def test_work_and_roofline():
    dsc = work.dsc(1_000_000, 140_608, 50_000, 96, 96)
    assert dsc["bytes"] == 16_000_000 + 4 * (96 * 96 + 50_000 + 140_608 * 96)
    assert dsc["flops"] == 1_000_000 * 193
    v5e = peaks.peaks("TPU v5 lite")
    # memory-bound by far: bytes / 819 GB/s
    assert work.roofline_seconds(dsc, v5e) == pytest.approx(
        dsc["bytes"] / 819e9)
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


@pytest.mark.skipif(not DATA.exists(), reason="recorded trace not present")
def test_recorded_trace():
    t = trace.load(DATA)
    assert list(t.ops) == ["/device:TPU:0"] and len(t.ops["/device:TPU:0"])
    s = trace.summary(t)
    assert 0.0 < s["busy_s"] < s["window_s"]
    assert 0.0 < s["idle_share"] < 1.0
    # the two bench_dsc calls start inside the window, after two of the
    # three small calls (the first starts just before it)
    calls = trace.module_seconds(t, s["lo"], s["hi"])
    assert len(calls) == 4 and all(0 < c < 0.1 for c in calls)
    assert sorted(calls)[-2:] == pytest.approx([0.00144, 0.00144], rel=0.01)
    bd = trace.breakdown(t, s["lo"], s["hi"])
    assert bd["device_ops"] and len(bd["device_ops"]) <= 10
    assert bd["idle_gaps"] and len(bd["idle_gaps"]) <= 10
    # the annotated 20 ms wait is among the longest gaps
    assert any(n.startswith("bench.wait") and d >= 0.019
               for n, d in bd["idle_gaps"])
