"""The measurement loop every runtime search in the repo shares.

The paper selects its restructuring at runtime from "the average execution
time for three runs"; this module is that loop factored out once, so the
three searches that exist today — restructuring choice
(``core/restructure.autotune_plan``), format choice (``formats/select``),
and kernel launch parameters (``tune/tuner``) —
measure with identical warmup/blocking/repeat semantics and their outcomes
stay comparable.

Deliberately dependency-light: jax only, so it can be imported from the
bottom of the stack (``core/restructure``) without cycles.
"""
from __future__ import annotations

import time
import warnings
from typing import Callable, Sequence, Tuple

import jax

#: measurement defaults, mirroring the paper's "three runs" protocol
DEFAULT_WARMUP = 1
DEFAULT_REPEATS = 3

#: process-lifetime count of :func:`time_call` invocations.  Every runtime
#: search in the repo times through this one function, so the counter is a
#: complete audit of measurement work — the zero-measurement contract of
#: the predicted cold-start path is asserted against it (tests and the
#: table16 benchmark snapshot it before/after a build).
_N_MEASURED = 0


def measurement_count() -> int:
    """Total ``time_call`` invocations in this process."""
    return _N_MEASURED


def block(out):
    """Block until every array leaf of ``out`` is ready (timing barrier)."""
    for leaf in jax.tree.leaves(out):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()
    return out


def time_call(fn: Callable, *args, warmup: int = DEFAULT_WARMUP,
              repeats: int = DEFAULT_REPEATS) -> float:
    """Mean seconds per blocking call after ``warmup`` compile/warm calls."""
    global _N_MEASURED
    _N_MEASURED += 1
    for _ in range(warmup):
        block(fn(*args))
    t0 = time.perf_counter()
    for _ in range(repeats):
        block(fn(*args))
    return (time.perf_counter() - t0) / max(1, repeats)


def measure_candidates(candidates: Sequence, run: Callable[[object], float],
                       ) -> Tuple[int, dict]:
    """Run ``run(candidate) -> cost_seconds`` for every candidate.

    Returns (index of the cheapest candidate, {str(candidate): cost}).
    ``run`` owns preparation *and* timing (usually via :func:`time_call`)
    so callers decide what "cost" means — a single op, a weighted pair,
    a whole iteration.

    Duplicate labels are disambiguated with a ``#<index>`` suffix instead
    of silently overwriting: persisted measurement dicts must account for
    every candidate actually timed, or audits under-count the search.
    """
    if not candidates:
        raise ValueError("need at least one candidate")
    costs = {}
    best_i, best_cost = 0, None
    for i, cand in enumerate(candidates):
        cost = float(run(cand))
        label = _label(cand)
        if label in costs:
            warnings.warn(f"duplicate search candidate label {label!r}; "
                          f"keying repeat as {label}#{i}", stacklevel=2)
            label = f"{label}#{i}"
        costs[label] = cost
        if best_cost is None or cost < best_cost:
            best_i, best_cost = i, cost
    return best_i, costs


def _label(cand) -> str:
    if isinstance(cand, dict):
        parts = []
        for k in sorted(cand):
            v = cand[k]
            parts.append(f"{k}={_label(v) if isinstance(v, dict) else v}")
        return ",".join(parts)
    return str(cand)
