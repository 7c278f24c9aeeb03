"""The one traffic generator: reads a mix's parameters, makes its requests.

A mix is a JSON file under ``bench/traffic/`` (see ``bench/README.md``).
Every seed of a mix gets the same work: one client cycles through the
mix's subjects, or through the bundles of a lesion mix.  So runs with
different seeds differ in which subjects and bundles they see, not in how
much they ask.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Iterator, Optional

import numpy as np

LOOPS = ("closed",)


@dataclasses.dataclass
class Request:
    """One job: which subject, how many iterations and, for a virtual
    lesion, which bundle of the subject it removes."""

    subject: int
    n_iters: int
    bundle: Optional[int] = None


def load(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    if mix.get("loop") not in LOOPS:
        raise ValueError(f"{path}: loop must be one of {LOOPS}")
    for key in ("subjects", "n_iters", "checked"):
        if int(mix.get(key, 1)) < 1:
            raise ValueError(f"{path}: {key} must be a positive integer")
    return mix


def rng(seed: int, stream: int) -> np.random.Generator:
    """The generator of one numbered stream of the seed (2: bundles,
    3: inputs of the isolated calls, 4: the answers checked); subjects'
    data has streams of its own in ``gen``."""
    return np.random.default_rng([stream, seed % 2 ** 64])


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
