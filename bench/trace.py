"""Reduction of a profiler trace to the benchmark's device metrics.

A trace is read once into plain arrays (:func:`load`): per TPU device the
intervals of its XLA ops and XLA modules, and the host's events.  The
functions below then give

* the busy time, the union of a device's op intervals inside a window;
* the idle share, one less the busy time over the window's length;
* the device time of the programs run inside a host annotation (their
  module events);
* the ops that took most device time, and the longest idle gaps, each
  gap named by what the host was doing: the benchmark's own annotation
  (``bench.*``) around its middle, and the host event that overlaps it
  most.

All times are seconds on the trace's clock, which host and device share.
"""
from __future__ import annotations

import dataclasses
import gzip
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "/host:"
BENCH_PREFIX = "bench."


@dataclasses.dataclass
class Events:
    """Named intervals: ``names[i]`` ran from ``start[i]`` to ``end[i]``."""

    names: List[str]
    start: np.ndarray
    end: np.ndarray

    @classmethod
    def of(cls, rows: List[Tuple[str, float, float]]) -> "Events":
        rows = sorted(rows, key=lambda r: r[1])
        return cls([r[0] for r in rows],
                   np.array([r[1] for r in rows], np.float64),
                   np.array([r[2] for r in rows], np.float64))

    def __len__(self) -> int:
        return len(self.names)


@dataclasses.dataclass
class Trace:
    ops: Dict[str, Events]        # device plane name -> its XLA ops
    modules: Dict[str, Events]    # device plane name -> its XLA modules
    host: Events


def load(path) -> Trace:
    """Read an ``.xplane.pb`` (or a gzipped copy) written by
    ``jax.profiler``."""
    from jax.profiler import ProfileData
    path = Path(path)
    if path.suffix == ".gz":
        data = ProfileData.from_serialized_xspace(gzip.open(path).read())
    else:
        data = ProfileData.from_file(str(path))
    ops, modules, host = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    rows = [(_short(e.name), e.start_ns * 1e-9,
                             e.end_ns * 1e-9) for e in line.events]
                    (ops if line.name == OPS_LINE
                     else modules)[plane.name] = Events.of(rows)
        elif plane.name.startswith(HOST_PREFIX):
            for line in plane.lines:
                host.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                            for e in line.events if e.duration_ns > 0)
    return Trace(ops=ops, modules=modules, host=Events.of(host))


def _short(name: str) -> str:
    """An op's HLO name without its text (``%fusion.3 = f32[..] ...`` ->
    ``fusion.3``); module names pass through."""
    return name.split(" = ", 1)[0].lstrip("%")


def span(trace: Trace, name: str) -> Tuple[float, float]:
    """Start and end of the first host event called ``name``."""
    for n, s, e in zip(trace.host.names, trace.host.start, trace.host.end):
        if n == name:
            return float(s), float(e)
    raise KeyError(f"no host event {name!r} in the trace")


def merged(ev: Events, lo: float, hi: float) -> List[Tuple[float, float]]:
    """The union of ``ev``'s intervals clipped to ``[lo, hi]``, as
    disjoint sorted intervals."""
    out: List[List[float]] = []
    for s, e in zip(np.maximum(ev.start, lo), np.minimum(ev.end, hi)):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(a, b) for a, b in out]


def busy_s(trace: Trace, lo: float, hi: float) -> float:
    """Seconds in which an op ran, averaged over the devices."""
    if not trace.ops:
        raise ValueError("the trace holds no TPU device ops")
    return float(np.mean([sum(b - a for a, b in merged(ev, lo, hi))
                          for ev in trace.ops.values()]))


def idle_share(trace: Trace, lo: float, hi: float) -> float:
    return 1.0 - busy_s(trace, lo, hi) / (hi - lo)


def module_seconds(trace: Trace, lo: float, hi: float) -> List[float]:
    """Device seconds of each run of a compiled program (an XLA module)
    that started inside ``[lo, hi]``, on any device.  The benchmark's
    isolated calls are found by their host annotation and not by the
    module's name: the runtime shows a program under the name of one
    compiled alike before it (a format selection's probe of the same DSC
    on the same subject), so a name alone can miss them."""
    return [float(e - s) for ev in trace.modules.values()
            for s, e in zip(ev.start, ev.end) if lo <= s <= hi]


def top_ops(trace: Trace, lo: float, hi: float,
            k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` op names that took most device seconds in the window."""
    total: Dict[str, float] = {}
    for ev in trace.ops.values():
        dur = np.minimum(ev.end, hi) - np.maximum(ev.start, lo)
        for n, d in zip(ev.names, dur):
            if d > 0:
                total[n] = total.get(n, 0.0) + float(d)
    return sorted(total.items(), key=lambda kv: -kv[1])[:k]


def idle_gaps(trace: Trace, lo: float, hi: float,
              k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` longest stretches of the window in which the first device
    ran no op, each named by what the host was doing."""
    plane = sorted(trace.ops)[0]
    busy = merged(trace.ops[plane], lo, hi)
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return [(_host_label(trace.host, a, b), float(b - a))
            for a, b in gaps[:k]]


def _host_label(host: Events, a: float, b: float) -> str:
    """``<bench annotation around the middle> / <host event overlapping
    most>`` for the interval ``[a, b]``."""
    if not len(host):
        return "unknown"
    names = np.array(host.names, dtype=object)
    bench = np.array([n.startswith(BENCH_PREFIX) for n in host.names])
    mid = 0.5 * (a + b)
    around = bench & (host.start <= mid) & (host.end >= mid)
    parts = []
    if around.any():
        # the innermost: the shortest annotation around the middle
        i = np.flatnonzero(around)[np.argmin(
            (host.end - host.start)[around])]
        parts.append(names[i])
    overlap = np.minimum(host.end, b) - np.maximum(host.start, a)
    overlap[bench] = 0.0
    if overlap.max() > 0:
        parts.append(names[int(np.argmax(overlap))])
    return " / ".join(parts) or "idle"


def breakdown(trace: Trace, lo: float, hi: float) -> dict:
    return {"device_ops": [[n, s] for n, s in top_ops(trace, lo, hi)],
            "idle_gaps": [[n, s] for n, s in idle_gaps(trace, lo, hi)]}


def summary(trace: Trace, window: str = "bench.window") -> dict:
    """Window, busy seconds and idle share of the annotated window."""
    lo, hi = span(trace, window)
    return {"lo": lo, "hi": hi, "window_s": hi - lo,
            "busy_s": busy_s(trace, lo, hi),
            "idle_share": idle_share(trace, lo, hi)}
