"""The one traffic generator: reads a mix's parameters, makes its requests.

A mix is a JSON file under ``bench/traffic/`` (see ``bench/README.md``).
Every seed of a mix gets the same work.  In a closed loop one client
cycles through the mix's subjects, or through the bundles of a lesion mix.
In an open loop requests arrive on a schedule that never waits for
answers: exponential gaps at ``rate_per_s``, each seed the same set of
gaps in its own order, and subjects drawn by popularity (Zipf).  So runs
with different seeds differ in which subjects they see and when, not in
how much they ask.

A mix may also name each job's ``format`` and its device ``mesh`` (both
passed to every job of the run, warm-up included); without them a job
runs the front end's default layout on one device.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Iterator, List, Optional

import numpy as np

LOOPS = ("closed", "open")
#: an open mix's parameters that must be positive, and those that may be 0
OPEN_POSITIVE = ("rate_per_s", "warmup_s", "warmup_subjects", "drain_s")
OPEN_NONNEGATIVE = ("tenants_zipf",)
#: numbered streams of the seed: the window's arrival order and subjects,
#: then the warm-up's
WINDOW_STREAMS = (5, 6)
WARMUP_STREAMS = (7, 8)
#: the formats a job may name (the serving scheduler's), and those with a
#: sharded executor, which alone may run on a mesh
FORMATS = ("auto", "coo", "alto", "sell", "fcoo")
MESH_FORMATS = ("coo", "sell")


@dataclasses.dataclass
class Request:
    """One job: which subject, how many iterations, for a virtual lesion
    which bundle of the subject it removes, and in an open loop when it is
    due (seconds from the window's start)."""

    subject: int
    n_iters: int
    bundle: Optional[int] = None
    at: Optional[float] = None


def load(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    if mix.get("loop") not in LOOPS:
        raise ValueError(f"{path}: loop must be one of {LOOPS}")
    for key in ("subjects", "n_iters", "checked"):
        if int(mix.get(key, 1)) < 1:
            raise ValueError(f"{path}: {key} must be a positive integer")
    if mix["loop"] == "open":
        if "lesion" in mix:
            raise ValueError(f"{path}: an open mix has no lesion block")
        for key in OPEN_POSITIVE + OPEN_NONNEGATIVE:
            least = "above" if key in OPEN_POSITIVE else "at least"
            value = float(mix.get(key, -1.0))
            if value < 0 or (value == 0 and key in OPEN_POSITIVE):
                raise ValueError(f"{path}: an open mix needs {key}, "
                                 f"{least} 0")
    if mix.get("format", "coo") not in FORMATS:
        raise ValueError(f"{path}: format must be one of {FORMATS}")
    if "mesh" in mix:
        mesh = mix["mesh"]
        if not (isinstance(mesh, list) and len(mesh) == 2 and all(
                isinstance(n, int) and not isinstance(n, bool) and n >= 1
                for n in mesh)):
            raise ValueError(f"{path}: mesh must be [rows, cols], two "
                             f"positive integers")
        if mix.get("format", "coo") not in MESH_FORMATS:
            raise ValueError(f"{path}: a mesh needs a format with a sharded "
                             f"executor, one of {MESH_FORMATS}")
    return mix


def chips(mix: dict) -> int:
    """The devices a mix's jobs run on: its mesh's rows times columns."""
    rows, cols = mix.get("mesh", (1, 1))
    return rows * cols


def job_options(mix: dict) -> dict:
    """What every job of the mix passes to the front end besides its
    problem and iterations: the mix's ``format`` and ``mesh``, where set."""
    out = {}
    if "format" in mix:
        out["format"] = mix["format"]
    if "mesh" in mix:
        out["mesh"] = tuple(mix["mesh"])
    return out


def rng(seed: int, stream: int) -> np.random.Generator:
    """The generator of one numbered stream of the seed (2: bundles,
    3: inputs of the isolated calls, 4: the answers checked, 5 and 6: an
    open window's arrival order and subjects, 7 and 8: its warm-up's);
    subjects' data has streams of its own in ``gen``."""
    return np.random.default_rng([stream, seed % 2 ** 64])


def arrivals(rate: float, seconds: float, r: np.random.Generator
             ) -> np.ndarray:
    """Send times in ``[0, seconds)`` of an open loop at ``rate`` a second.

    The gaps are the ``n = round(rate * seconds)`` midpoint quantiles of
    the exponential law, scaled to the mean gap ``seconds / (n + 1/2)``
    (about ``1 / rate``, and the last arrival inside the window), in the
    order ``r`` draws: Poisson-like arrivals, with the same number of them
    and the same total load for every seed, where independent draws would
    change the load by about ``1 / sqrt(n)`` from seed to seed."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds * n / (n + 0.5) / gaps.sum()
    return np.cumsum(r.permutation(gaps))


def zipf_subjects(count: int, n_subjects: int, exponent: float,
                  r: np.random.Generator) -> np.ndarray:
    """``count`` independent draws among ``n_subjects`` tenants, tenant
    ``k`` with probability proportional to ``(k + 1) ** -exponent``."""
    p = (np.arange(n_subjects) + 1.0) ** -float(exponent)
    return r.choice(n_subjects, size=count, p=p / p.sum())


def open_requests(mix: dict, seed: int, seconds: float, *,
                  warm_up: bool = False) -> List[Request]:
    """The open loop's schedule over ``seconds``: the window's requests on
    subjects ``0 .. subjects - 1``, or the warm-up's, on the
    ``warmup_subjects`` after them, from streams of their own."""
    arrive, pick = WARMUP_STREAMS if warm_up else WINDOW_STREAMS
    times = arrivals(float(mix["rate_per_s"]), seconds, rng(seed, arrive))
    n, first = int(mix["subjects"]), 0
    if warm_up:
        n, first = int(mix["warmup_subjects"]), n
    subjects = zipf_subjects(times.size, n, mix["tenants_zipf"],
                             rng(seed, pick))
    return [Request(subject=first + int(s), n_iters=int(mix["n_iters"]),
                    at=float(t)) for s, t in zip(subjects, times)]


def closed_requests(mix: dict, seed: int) -> Iterator[Request]:
    """One client's requests, endless: subjects in turn, or for a lesion
    mix the bundles of subject 0 in turn."""
    n_iters = int(mix["n_iters"])
    if "lesion" in mix:
        i = 0
        while True:
            yield Request(subject=0, n_iters=n_iters,
                          bundle=i % int(mix["lesion"]["bundles"]))
            i += 1
    k = int(mix["subjects"])
    i = 0
    while True:
        yield Request(subject=i % k, n_iters=n_iters)
        i += 1


def bundles(subject, *, size: int, count: int, seed: int) -> list:
    """``count`` disjoint, spatially coherent bundles of ``size`` fibers
    (lesion candidates): an anchor fiber and its nearest neighbours by the
    centroid of the voxels it crosses.  Only fibers with coefficients are
    eligible."""
    r = rng(seed, 2)
    gx, gy, gz = subject.grid
    vox = subject.voxels.astype(np.int64)
    pos = np.stack([vox // (gy * gz), (vox // gz) % gy, vox % gz], axis=1)
    counts = np.bincount(subject.fibers, minlength=subject.n_fibers)
    sums = np.stack([np.bincount(subject.fibers, weights=pos[:, i],
                                 minlength=subject.n_fibers)
                     for i in range(3)], axis=1)
    structural = np.nonzero(counts > 0)[0]
    if structural.size < size * count:
        raise ValueError(f"need {size * count} fibers with coefficients, "
                         f"have {structural.size}")
    centroids = sums[structural] / counts[structural, None]
    available = np.ones(structural.size, bool)
    out = []
    for _ in range(count):
        anchor = r.choice(np.nonzero(available)[0])
        dist = np.linalg.norm(centroids - centroids[anchor], axis=1)
        dist[~available] = np.inf
        members = np.argsort(dist, kind="stable")[:size]
        available[members] = False
        out.append(np.sort(structural[members]).astype(np.int32))
    return out
