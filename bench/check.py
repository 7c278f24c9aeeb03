"""The comparison that decides ``correct``.

Each compared number is a gap between what the timed path produced and
the plain reference (``bench/reference.py``), taken over every answer
the run compares:

* ``loss_gap``: the largest gap between a job's per-iteration losses and
  the reference's, relative to the reference's largest loss;
* ``obj_gap``: the gap between the objective ``1/2 |M w - b|^2`` of a
  job's final weights and that of the reference's, both taken by the
  reference in float64 (``reference.objective``), relative to the
  reference's.  The weights themselves are not compared: many fibers
  cross the same voxels, so different weight vectors fit the signal
  equally well, and two float32 runs that round differently drift apart
  along them.  Nor is the predicted signal ``M w``: once a solve reaches
  float32's floor of the loss (on some subjects within 100 iterations),
  its Barzilai-Borwein steps follow rounding, and ``M w`` wanders within
  that floor by up to about 4e-5 of its norm, while the bf16 control
  lies only about twice as far (PERF.md).  The objective is flat along
  that wander and rises with the control's error;
* ``fit_gap`` (shown, not compared): ``|M w - M w_ref| / |M w_ref|``;
* ``dsc_gap`` / ``wc_gap`` (traced runs): the largest gap of the isolated
  DSC and WC calls' outputs, relative to the reference output's largest
  magnitude;
* ``missing``: jobs due in the window whose answer never came, or came
  as a failure.

The limits of a cell are in ``bench/limits/<cell>.json``; ``PERF.md``
gives the readings each was set from.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np


def rel_max(got, ref) -> float:
    """Largest absolute gap over the reference's largest magnitude."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        return float("inf")
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


def fit_gap(got, ref) -> float:
    """2-norm of the gap over the reference's 2-norm."""
    got = np.asarray(got, np.float64).reshape(-1)
    ref = np.asarray(ref, np.float64).reshape(-1)
    if got.shape != ref.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-30))


def obj_gap(got: float, ref: float) -> float:
    """Gap between two objective values over the reference's."""
    if not np.isfinite(got):
        return float("inf")
    return abs(got - ref) / max(abs(ref), 1e-30)


def loss_gap(got, ref) -> float:
    """Largest gap between two loss traces, relative to the reference's
    largest loss.  (Near convergence the loss is a difference of nearly
    equal float32 numbers, so a gap relative to each loss would measure
    that cancellation rather than the solve.)"""
    got = np.asarray(got, np.float64).reshape(-1)
    if not np.all(np.isfinite(got)):
        return float("inf")
    return rel_max(got, np.asarray(ref, np.float64).reshape(-1))


def load_limits(path: Path) -> Dict[str, float]:
    return {k: float(v) for k, v in json.loads(Path(path).read_text()).items()}


def verdict(readings: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, List[str]]:
    """``correct`` and one line per number: name, reading, limit.  A
    number with no limit in the cell's file fails: every number compared
    needs one."""
    ok, lines = True, []
    for name, value in readings.items():
        limit = limits.get(name)
        good = limit is not None and np.isfinite(value) and value <= limit
        ok &= bool(good)
        lines.append(f"{name} {value!r} limit {limit!r} "
                     f"{'ok' if good else 'FAIL'}")
    return ok, lines
