"""The program's spans and the solver's named scopes, read from a profiler
trace.

``bench/trace.py`` reads ops and host events through
``jax.profiler.ProfileData``, which gives an event's name and interval but
not its metadata.  This module reads the ``.xplane.pb`` protobuf wire
format itself (no dependency) for

* each XLA op of a TPU device with its ``tf_op`` stat, the op's scope
  path, e.g. ``jit(run_batch)/while/body/closed_call/vmap(sbbnnls.dsc)/
  mul:`` (the solver's scopes are ``sbbnnls.dsc``, ``sbbnnls.wc`` and
  ``sbbnnls.bb``, ``core/sbbnnls.py:sbbnnls_step``);
* each host event with the thread that wrote it,

and reduces them over the benchmark's window (host event
``bench.window``) to

* :func:`attribute_idle`: each idle moment of the first device named by
  what the host was doing, by precedence: compiling (:data:`COMPILE`),
  then an engine build (``engine.build``), then intake
  (``service.submit``, ``lesion.edit``), else other;
* :func:`scope_seconds`: the device seconds of the ops of each solver
  scope, an op counted under the innermost ``sbbnnls.*`` scope of its
  path.

Which host events count as compiling.  JAX 0.9 annotates tracing,
lowering and compiling (``profiler.annotate_function`` in
``jax/_src/interpreters/partial_eval.py``, ``interpreters/pxla.py`` and
``compiler.py``): ``trace_to_jaxpr_dynamic`` (a function traced to a
jaxpr), ``lower_sharding_computation`` (a jit lowered to HLO),
``backend_compile`` / ``backend_compile_and_load`` (XLA), and for pmap
``lower_parallel_callable`` and ``PmapComputation.compile``.  The rest of
a first call (argument handling between those steps) has no event of its
own, so the whole ``PjitFunction(<name>)`` event of a call counts as
compiling where one of those events lies inside it on the same thread;
the dispatch of a call already compiled holds none and does not count.
In the recorded chip trace of the front end
(``tests/data/spans.xplane.pb.gz``) a job's first ``engine.step`` holds
``PjitFunction(run_batch)`` > ``trace_to_jaxpr_dynamic``,
``lower_sharding_computation``, ``backend_compile_and_load``.

It also keeps each ``engine.step`` in the window with the job ids of its
``jobs`` stat (:func:`steps`), for the scheduler's readers ``queue_s`` and
``step_jobs``.

The harness turns the program's tracing (``repro.obs``) on for the window
of a traced run.  The metric readers ``idle_compile_s``, ``idle_build_s``,
``idle_intake_s``, ``dsc_device_s``, ``wc_device_s``, ``queue_s`` and
``step_jobs`` call :func:`install` when they are loaded, which the harness
does at the start of a traced run only: it analyses the trace file the
harness loads.  A program without these spans (or scopes) gives no
reading, not zero.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import re
import statistics
import sys
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from bench import trace

Intervals = List[Tuple[float, float]]

COMPILE = ("trace_to_jaxpr_dynamic", "lower_sharding_computation",
           "backend_compile", "backend_compile_and_load",
           "lower_parallel_callable", "PmapComputation.compile")
PJIT_PREFIX = "PjitFunction("
BUILD = ("engine.build",)
INTAKE = ("service.submit", "lesion.edit")
#: the program's spans (DESIGN.md §12.4); one of them in the window shows
#: that the program writes its spans to the profiler
PROGRAM_SPANS = ("scheduler.tick", "scheduler.slice", "scheduler.quarantine",
                 "engine.build", "engine.step", "service.submit",
                 "service.checkpoint", "lesion.edit", "tune.search",
                 "select.predicted")
SCOPES = ("dsc", "wc", "bb")
TF_OP, CATEGORY = "tf_op", "hlo_category"
#: ops that hold others (their interval covers their body's ops)
CONTAINERS = ("(while)", "(conditional)")
_SCOPE = re.compile(r"(?:^|[/(])sbbnnls\.(dsc|wc|bb)(?=[/):]|$)")
WINDOW = "bench.window"
#: host spans whose stats :func:`read` keeps, and the stat kept
STEP, JOBS = "engine.step", "jobs"


# -- the wire format ---------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    ``memoryview`` of the bytes otherwise."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire} at byte {i}")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _stat_value(stat: dict, stat_names: dict):
    """An XStat's value: its string, a reference to an interned string,
    or its number."""
    if 5 in stat:
        return _text(stat[5])
    if 7 in stat:
        return stat_names.get(stat[7], "")
    return next((stat[k] for k in (4, 3) if k in stat), None)


def _plane(buf, by_stat: bool, with_stats=()):
    """Name, lines and event names of one XPlane.  Lines are ``(line
    name, [(metadata id, start s, end s, stats)])``; an event's name is its
    metadata's, or ``by_stat``, its metadata's ``tf_op`` stat, else its
    ``hlo_category`` in parentheses (``(while)``, ``(custom fusion)``).
    ``stats`` is the dict of an event's own stats where its name is in
    ``with_stats``, else None."""
    name, lines, events, stat_names = "", [], {}, {}
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            entry = dict(_fields(v))
            events[entry[1]] = entry.get(2, b"")
        elif f == 5:
            entry = dict(_fields(v))
            meta = dict(_fields(entry.get(2, b"")))
            stat_names[entry[1]] = _text(meta.get(2, b""))
    names = {}
    for key, raw in events.items():
        meta = list(_fields(raw))
        if not by_stat:
            names[key] = next((_text(v) for f, v in meta if f == 2), "")
            continue
        stats = {}
        for f, v in meta:
            stat = dict(_fields(v)) if f == 5 else {}
            if stat_names.get(stat.get(1)) in (TF_OP, CATEGORY):
                stats[stat_names[stat[1]]] = _stat_value(stat, stat_names)
        names[key] = stats.get(TF_OP) or f"({stats.get(CATEGORY, '')})"
    out = []
    for raw in lines:
        line_name, stamp_ns, rows = "", 0, []
        for f, v in _fields(raw):
            if f == 2:
                line_name = _text(v)
            elif f == 3:
                stamp_ns = v
            elif f == 4:
                fields = list(_fields(v))
                ev = dict(fields)
                start = stamp_ns * 1e-9 + ev.get(2, 0) * 1e-12
                stats = None
                if names.get(ev.get(1, 0)) in with_stats:
                    stats = {}
                    for g, raw_stat in fields:
                        if g == 4:
                            stat = dict(_fields(raw_stat))
                            stats[stat_names.get(stat.get(1), "")] = \
                                _stat_value(stat, stat_names)
                rows.append((ev.get(1, 0), start,
                             start + ev.get(3, 0) * 1e-12, stats))
        out.append((line_name, rows))
    return name, out, names


@dataclasses.dataclass
class Xspace:
    """What the reductions need of one trace."""

    ops: Dict[str, trace.Events]    # TPU plane -> XLA ops, by tf_op
    threads: List[trace.Events]     # one per host line, events by name
    #: (start, end, job ids) of each engine.step on the host
    steps: List[Tuple[float, float, Tuple[str, ...]]] = \
        dataclasses.field(default_factory=list)


def read(path) -> Xspace:
    """Read an ``.xplane.pb`` (or a gzipped copy) written by
    ``jax.profiler``."""
    path = Path(path)
    raw = (gzip.open(path).read() if path.suffix == ".gz"
           else path.read_bytes())
    ops, threads, steps = {}, [], []
    for f, plane in _fields(memoryview(raw)):
        if f != 1:
            continue
        name = _text(dict(_fields(plane)).get(2, b""))
        if name.startswith(trace.DEVICE_PREFIX):
            _, lines, tf_op = _plane(plane, True)
            for line_name, rows in lines:
                if line_name == trace.OPS_LINE:
                    ops[name] = trace.Events.of(
                        [(tf_op.get(m, ""), s, e) for m, s, e, _ in rows])
        elif name.startswith(trace.HOST_PREFIX):
            _, lines, names = _plane(plane, False, (STEP,))
            threads.extend(trace.Events.of(
                [(names.get(m, ""), s, e) for m, s, e, _ in rows if e > s])
                for _, rows in lines if rows)
            steps += [(s, e, tuple(str(st.get(JOBS) or "").split()))
                      for _, rows in lines for _, s, e, st in rows
                      if st is not None]
    return Xspace(ops=ops, threads=threads, steps=sorted(steps))


# -- interval arithmetic on sorted disjoint lists --------------------------------

def union(rows) -> Intervals:
    out: List[List[float]] = []
    for s, e in sorted(rows):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(a, b) for a, b in out]


def minus(a: Intervals, b: Intervals) -> Intervals:
    """``a`` without ``b``."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def meet(a: Intervals, b: Intervals) -> Intervals:
    return minus(a, minus(a, b))


def length(a: Intervals) -> float:
    return float(sum(e - s for s, e in a))


# -- the reductions ------------------------------------------------------------

def window(xs: Xspace) -> Optional[Tuple[float, float]]:
    """The first ``bench.window`` host event, or None."""
    found = [(float(s), float(e)) for ev in xs.threads
             for n, s, e in zip(ev.names, ev.start, ev.end) if n == WINDOW]
    return min(found) if found else None


def compile_intervals(threads: List[trace.Events]) -> Intervals:
    """Where a thread lowered or compiled, or ran a ``PjitFunction`` call
    that did."""
    rows = []
    for ev in threads:
        comp = [(s, e) for n, s, e in zip(ev.names, ev.start, ev.end)
                if n in COMPILE]
        rows += comp
        for n, s, e in zip(ev.names, ev.start, ev.end):
            if n.startswith(PJIT_PREFIX) and any(
                    s <= cs and ce <= e for cs, ce in comp):
                rows.append((s, e))
    return union(rows)


def named(threads: List[trace.Events], names) -> Intervals:
    return union((s, e) for ev in threads
                 for n, s, e in zip(ev.names, ev.start, ev.end)
                 if n in names)


def idle(xs: Xspace, lo: float, hi: float) -> Intervals:
    """Where the first TPU device ran no op in ``[lo, hi]``."""
    busy = trace.merged(xs.ops[sorted(xs.ops)[0]], lo, hi)
    return minus([(lo, hi)], busy)


def attribute_idle(idle_at: Intervals, layers
                   ) -> Tuple[Dict[str, float], Intervals]:
    """Seconds of ``idle_at`` under each ``(name, intervals)`` of
    ``layers``, the first that covers a moment taking it, and ``other``,
    what none covers (the parts sum to the idle time); and where that is.
    """
    left, out = idle_at, {}
    for name, cover in layers:
        out[name] = length(meet(left, cover))
        left = minus(left, cover)
    out["other"] = length(left)
    return out, left


def innermost(left: Intervals, threads: List[trace.Events],
              k: int = 8) -> List[Tuple[str, float]]:
    """``left`` named by the shortest program span, ``bench.*``
    annotation or JAX call (``PjitFunction``, ``np.asarray``) around
    each moment: where the idle time that no layer explains sits."""
    around = sorted(((e - s, n, s, e) for ev in threads
                    for n, s, e in zip(ev.names, ev.start, ev.end)
                    if n in PROGRAM_SPANS
                    or n.startswith((trace.BENCH_PREFIX, PJIT_PREFIX,
                                     "np.asarray"))))
    seconds: Dict[str, float] = defaultdict(float)
    for _, n, s, e in around:
        if not left:
            break
        part = meet(left, [(s, e)])
        if part:
            seconds[n] += length(part)
            left = minus(left, part)
    if left:
        seconds["(none)"] += length(left)
    return sorted(seconds.items(), key=lambda kv: -kv[1])[:k]


def scope_seconds(xs: Xspace, lo: float, hi: float) -> Dict[str, float]:
    """Device seconds of the first TPU device's ops in ``[lo, hi]`` under
    each solver scope (innermost ``sbbnnls.*`` of the op's ``tf_op``), and
    ``unnamed``: ops with no ``tf_op`` at all, containers aside (the v5e
    compiler leaves the batched WC's fiber scatter without one)."""
    ev = xs.ops[sorted(xs.ops)[0]]
    rows: Dict[str, list] = {s: [] for s in SCOPES + ("unnamed",)}
    for name, s, e in zip(ev.names, ev.start, ev.end):
        found = _SCOPE.findall(name)
        if found:
            rows[found[-1]].append((max(s, lo), min(e, hi)))
        elif name.startswith("(") and name not in CONTAINERS:
            rows["unnamed"].append((max(s, lo), min(e, hi)))
    return {k: length(union(v)) for k, v in rows.items()}


def analyse(path) -> Optional[dict]:
    """:func:`reduce` of the trace file at ``path``."""
    return reduce(read(path))


def reduce(xs: Xspace) -> Optional[dict]:
    """The window's idle attribution and scope seconds (totals, not per
    answer), or None where the trace has no window or no device."""
    span = window(xs)
    if span is None or not xs.ops:
        return None
    lo, hi = span
    threads = [trace.Events.of([(n, max(s, lo), min(e, hi))
                                for n, s, e in zip(ev.names, ev.start,
                                                   ev.end)
                                if e > lo and s < hi])
               for ev in xs.threads]
    gaps = idle(xs, lo, hi)
    layers = (("compile", compile_intervals(threads)),
              ("build", named(threads, BUILD)),
              ("intake", named(threads, INTAKE)))
    parts, left = attribute_idle(gaps, layers)
    return {"window_s": hi - lo, "busy_s": (hi - lo) - length(gaps),
            "idle_s": length(gaps), "idle": parts,
            "spans": any(n in PROGRAM_SPANS for ev in threads
                         for n in ev.names),
            "scopes": scope_seconds(xs, lo, hi),
            "other_by_span": innermost(left, threads),
            "steps": steps(xs, lo, hi)}


def steps(xs: Xspace, lo: float, hi: float
          ) -> List[Tuple[float, Tuple[str, ...]]]:
    """``(start, job ids)`` of each ``engine.step`` that starts in
    ``[lo, hi]``, start in seconds from ``lo``, in order."""
    return [(s - lo, jobs) for s, _, jobs in xs.steps if lo <= s <= hi]


# -- the harness's side (traced runs) ---------------------------------------------

#: the analysis of the trace the harness loaded last
LAST: Optional[dict] = None
_INSTALLED = False


def install() -> None:
    """Analyse the trace file when the harness loads it (the analysis's
    totals go to standard error as ``bench.spans {...}``, the steps
    aside).  Idempotent."""
    global _INSTALLED
    if _INSTALLED:
        return
    _INSTALLED = True
    load = trace.load

    def load_and_analyse(path):
        global LAST
        t0 = time.perf_counter()
        LAST = analyse(path)
        if LAST is not None:
            shown = {k: v for k, v in LAST.items() if k != "steps"}
            shown["n_steps"] = len(LAST["steps"])
        print("bench.spans " + json.dumps(
            {"analyse_s": time.perf_counter() - t0,
             "window": shown if LAST is not None else None}),
            file=sys.stderr, flush=True)
        return load(path)

    trace.load = load_and_analyse


def idle_per_answer(run, layer: str) -> Optional[float]:
    """Idle seconds under ``layer`` per answer of the window, where the
    program wrote its spans."""
    done = len(run.done())
    if LAST is None or not LAST["spans"] or not done:
        return None
    return LAST["idle"][layer] / done


def scope_per_answer(run, scope: str) -> Optional[float]:
    """Device seconds under solver scope ``scope`` per answer, where the
    program names its scopes."""
    done = len(run.done())
    if LAST is None or not done or not any(LAST["scopes"][k]
                                           for k in SCOPES):
        return None
    return LAST["scopes"][scope] / done


def step_jobs() -> Optional[float]:
    """Mean number of jobs an ``engine.step`` of the window advanced."""
    if LAST is None or not LAST["steps"]:
        return None
    return sum(len(jobs) for _, jobs in LAST["steps"]) / len(LAST["steps"])


def queue_seconds(run) -> Optional[float]:
    """Median over the window's open-loop jobs of the time from the job's
    due time to the start of the first ``engine.step`` that names it; jobs
    never stepped in the window are left out."""
    if LAST is None or not LAST["steps"]:
        return None
    first: Dict[str, float] = {}
    for start, jobs in LAST["steps"]:
        for job in jobs:
            first.setdefault(job, start)
    waits = [first[j.job_id] - j.due for j in run.jobs
             if j.due is not None and j.job_id in first]
    return statistics.median(waits) if waits else None
