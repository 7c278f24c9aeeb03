"""Per-dataset format selection: heuristic first, measurement when unsure.

Chen et al. (arXiv:1805.11938) show no single SpMV format wins across
matrices on many-core hardware; this module is that observation applied to
Phi.  The decision pipeline:

1. **Cache** — the choice is a :class:`~repro.formats.base.FormatPlan`
   keyed by ``plan_cache.format_plan_key`` (full index content + geometry +
   candidate set, format-versioned); a warm engine rebuild loads it and
   never re-runs selection.
2. **Predict** — a trained :class:`~repro.learn.model.Predictor` beside
   the cache directory answers the miss from ``phi_stats`` features alone
   (``reason="predicted"``, zero measurements); the measured pipeline is
   enqueued on :data:`repro.learn.refine.QUEUE` so background refinement
   upgrades the cached entry in place (DESIGN.md §14).
3. **Heuristic** — from ``core/inspector.py:phi_stats`` run-length
   statistics: SELL's padding overhead is computable in O(Nc) without
   encoding anything.  Overhead at most ``sell_accept`` extra slots per
   coefficient -> take SELL outright (dense uniform rows: the direct
   row-block kernels win and the padding is cheap); at least
   ``sell_reject`` -> SELL is struck from the candidate set (skewed row
   degrees: padding would dominate bytes moved).
4. **Autotune fallback** — whenever more than one candidate survives the
   heuristic (SELL in its ambiguous zone, or COO vs ALTO with no static
   signal between them), measure: the paper's three runs per candidate of
   the DSC executor (the dominant op, ~2 calls/iteration vs WC's ~1.5),
   timed through :mod:`repro.tune.search` — the same measurement loop
   ``restructure.autotune_plan`` and the kernel autotuner use — so format
   choice and tile choice share one cost currency (DESIGN.md §10.2).
   A caller that runs at a padded size passes it as ``probe_nc``: the
   batched engine's candidates are then prepared on the host as the engine
   prepares them, padded to that size and timed there, so a new subject
   whose size was probed before compiles nothing.  Without ``probe_nc``
   the probes run at the subject's exact Nc.

``resolve_format`` is the engine entry point: it also handles explicit
``LifeConfig(format="sell"/"alto"/"coo")`` requests (no selection, plan
records ``reason="explicit"``) and maps the chosen format to the executor
registry name.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import batched, spmv
from repro.core.inspector import phi_stats
from repro.core.restructure import sort_by_host
from repro.core.std import PhiTensor
from repro.formats import fcoo as fcoo_mod
from repro.formats import sell as sell_mod
from repro.formats.fcoo import FcooPhi
from repro.formats.base import FormatPlan, format_names
from repro.formats.sell import DEFAULT_ROW_TILE, DEFAULT_SLOT_TILE, SellPhi

#: SELL padding-overhead thresholds (extra slots per real coefficient)
DEFAULT_SELL_ACCEPT = 1.0
DEFAULT_SELL_REJECT = 4.0

#: format name -> executor registry name; None = defer to config.executor
#: (COO is what every pre-existing executor already consumes)
_FORMAT_EXECUTORS = {"coo": None, "sell": "kernel-sell", "alto": "alto",
                     "fcoo": "kernel-fcoo"}

#: default "auto" candidate set (every leaf format)
DEFAULT_CANDIDATES = ("coo", "sell", "alto", "fcoo")


def _mesh_cells(config) -> int:
    return (getattr(config, "shard_rows", 1)
            * getattr(config, "shard_cols", 1))


def executor_for(format_name: str, config) -> str:
    """Registry name that runs a format.

    Resolution order: (1) a multi-cell mesh request
    (``shard_rows * shard_cols > 1``) maps to the format's mesh executor
    from the registry's ``mesh=`` metadata — asking for a partition is the
    strongest signal, so it wins even over an explicit single-device
    executor; (2) an explicitly configured executor that itself consumes
    the format (so ``executor="shard-sell", format="sell"`` runs the
    sharded path on a 1x1 mesh, not ``kernel-sell``); (3) the static
    single-device mapping above (COO defers to config.executor)."""
    if format_name not in _FORMAT_EXECUTORS:
        raise ValueError(
            f"format must be one of {format_names()}, got {format_name!r}")
    from repro.core.registry import REGISTRY
    requested = getattr(config, "executor", "opt")
    if _mesh_cells(config) > 1:
        sharded = REGISTRY.mesh_executor_for(format_name)
        if sharded is not None:
            return sharded
    if requested in REGISTRY and REGISTRY.consumes(requested) == format_name:
        return requested
    mapped = _FORMAT_EXECUTORS[format_name]
    return requested if mapped is None else mapped


def _geometry(config) -> Tuple[int, int]:
    return (getattr(config, "row_tile", DEFAULT_ROW_TILE),
            getattr(config, "slot_tile", DEFAULT_SLOT_TILE))


def choose_format(
    phi: PhiTensor,
    dictionary,
    *,
    row_tile: int = DEFAULT_ROW_TILE,
    slot_tile: int = DEFAULT_SLOT_TILE,
    allowed: Tuple[str, ...] = DEFAULT_CANDIDATES,
    sell_accept: float = DEFAULT_SELL_ACCEPT,
    sell_reject: float = DEFAULT_SELL_REJECT,
    cache=None,
    predictor=None,
    probe_nc: Optional[int] = None,
) -> FormatPlan:
    """Pick a Phi format for one dataset (see module docstring pipeline).

    ``probe_nc`` is the padded coefficient count the caller runs at; the
    measure rung then probes at that size (None: at ``phi``'s own)."""
    if not allowed:
        raise ValueError("allowed must name at least one format")
    key = None
    if cache is not None and cache.enabled:
        from repro.core.plan_cache import format_plan_key
        key = format_plan_key(
            np.asarray(phi.atoms), np.asarray(phi.voxels),
            np.asarray(phi.fibers),
            sizes=(phi.n_atoms, phi.n_voxels, phi.n_fibers),
            row_tile=row_tile, slot_tile=slot_tile, allowed=allowed,
            sell_accept=sell_accept, sell_reject=sell_reject)
        plan = cache.get_format_plan(key)
        if plan is not None:
            if plan.reason == "predicted":
                # a predicted entry that is still serving hits was never
                # refined (process restart dropped the queue) — re-enqueue
                _enqueue_refinement(key, cache, phi, dictionary, allowed,
                                    row_tile, slot_tile, sell_accept,
                                    sell_reject, probe_nc)
            return plan

    stats = phi_stats(phi, row_tile=row_tile, slot_tile=slot_tile)
    params = dict(row_tile=row_tile, slot_tile=slot_tile)

    if predictor is not None:
        with obs.span("select.predicted") as sp:
            fmt = predictor.predict_format(stats, allowed=allowed)
            sp.set_attr("format", fmt)
        if fmt is not None:
            obs.counter("learn.predict", kind="format", outcome="hit").inc()
            plan = FormatPlan(fmt, "predicted", params, stats)
            if key is not None:
                cache.put_format_plan(key, plan)
                _enqueue_refinement(key, cache, phi, dictionary, allowed,
                                    row_tile, slot_tile, sell_accept,
                                    sell_reject, probe_nc)
            return plan
        obs.counter("learn.predict", kind="format", outcome="fallback").inc()

    plan = _decide_format(phi, dictionary, stats, params, allowed,
                          row_tile=row_tile, slot_tile=slot_tile,
                          sell_accept=sell_accept, sell_reject=sell_reject,
                          probe_nc=probe_nc)
    if key is not None:
        cache.put_format_plan(key, plan)
    return plan


def _decide_format(phi, dictionary, stats, params, allowed, *, row_tile,
                   slot_tile, sell_accept, sell_reject,
                   probe_nc=None) -> FormatPlan:
    """Heuristic + measured rungs of the ladder (no cache, no predictor).

    Factored out so background refinement can re-run exactly this under
    the same thresholds and overwrite a predicted plan in place."""
    overhead = max(stats["dsc.sell_overhead"], stats["wc.sell_overhead"])
    candidates = tuple(allowed)
    # strike SELL on heavy skew — unless it is the only candidate the
    # caller permits, in which case the caller's constraint wins
    if "sell" in candidates and overhead >= sell_reject and len(candidates) > 1:
        candidates = tuple(f for f in candidates if f != "sell")
    if "sell" in candidates and overhead <= sell_accept:
        return FormatPlan("sell", "heuristic", params, stats)
    if len(candidates) == 1:
        return FormatPlan(candidates[0], "heuristic", params, stats)
    return FormatPlan(_measure_formats(phi, dictionary, candidates,
                                       row_tile, slot_tile, probe_nc),
                      "autotune", params, stats)


def _enqueue_refinement(key, cache, phi, dictionary, allowed, row_tile,
                        slot_tile, sell_accept, sell_reject,
                        probe_nc=None) -> None:
    """Queue the measured pipeline to upgrade a predicted plan in place,
    probing at the size the predicted request runs at."""
    from repro.learn import refine

    def _task() -> None:
        stats = phi_stats(phi, row_tile=row_tile, slot_tile=slot_tile)
        params = dict(row_tile=row_tile, slot_tile=slot_tile)
        plan = _decide_format(phi, dictionary, stats, params, allowed,
                              row_tile=row_tile, slot_tile=slot_tile,
                              sell_accept=sell_accept,
                              sell_reject=sell_reject, probe_nc=probe_nc)
        cache.put_format_plan(key, plan)

    refine.QUEUE.push("format", key, _task)


#: probe shapes this process has timed — (format, Nc probed, Nv, Nf,
#: dictionary shape, dtype) — so ``select.probe`` can say whether a probe's
#: programs were compiled before
_PROBED = set()


def _padded_operand(phi: PhiTensor, fmt: str, nc: int):
    """A coo or alto probe as the batched engine builds its operand: the
    subject ordered on the host (voxel sort; ALTO's order), padded to
    ``nc`` inert coefficients and placed on the device once.  Returns it
    with the DSC function the engine's recipe runs on it."""
    if fmt == "alto":
        phi, dim, dsc = batched.alto_ordered(phi), None, spmv.dsc_naive
    else:
        dim, dsc = "voxel", spmv.dsc
    return jax.device_put(batched.prepare_host(phi, nc, dim, dsc)), dsc


def _measure_formats(phi: PhiTensor, dictionary, allowed: Tuple[str, ...],
                     row_tile: int, slot_tile: int,
                     probe_nc: Optional[int] = None) -> str:
    """Autotune fallback: time the DSC executor of each candidate format,
    one warm-up and three timed calls each (:mod:`repro.tune.search`).

    With ``probe_nc`` the coo and alto candidates are built on the host as
    the batched engine builds its operands (``core/batched.py``), padded to
    ``probe_nc`` inert coefficients, placed on the device once and timed
    on the engine's own DSC functions: every subject that runs at one
    padded size shares the probes' compiled programs.  Sell and fcoo, whose
    encoded shapes are the subject's own, probe at its exact Nc."""
    from repro.tune import search as tsearch
    if probe_nc is not None and probe_nc < phi.n_coeffs:
        raise ValueError(f"probe_nc={probe_nc} is below the subject's "
                         f"{phi.n_coeffs} coefficients")
    w_probe = jnp.ones((phi.n_fibers,), dictionary.dtype)

    def probe(fmt: str, padded: bool):
        """The candidate's prepared DSC call."""
        if padded:
            op, dsc = _padded_operand(phi, fmt, probe_nc)
            return lambda: dsc(op, dictionary, w_probe)
        if fmt == "sell":
            enc = SellPhi.encode(phi, op="dsc", row_tile=row_tile,
                                 slot_tile=slot_tile)
            return lambda: sell_mod.dsc_reference(enc, dictionary, w_probe)
        if fmt == "alto":
            # prepare the actual registry executor so arbitration charges
            # ALTO whatever its real DSC path costs — timing dsc_naive over
            # a decoded COO tensor instead would keep "winning" for alto
            # even after its executor changes (untuned, untracked build:
            # selection must not recurse into the kernel autotuner)
            from types import SimpleNamespace
            from repro.core.registry import REGISTRY
            ex = REGISTRY.create(
                "alto", phi, SimpleNamespace(dictionary=dictionary),
                SimpleNamespace(tune="off"))
            return lambda: ex.matvec(w_probe)
        if fmt == "fcoo":
            enc = FcooPhi.encode(phi)
            return lambda: fcoo_mod.dsc_reference(enc, dictionary, w_probe)
        op, _ = sort_by_host(phi, "voxel")              # coo
        return lambda: spmv.dsc(op, dictionary, w_probe)

    def measure(fmt: str) -> float:
        padded = probe_nc is not None and fmt in ("coo", "alto")
        nc_probe = probe_nc if padded else phi.n_coeffs
        shape = (fmt, nc_probe, phi.n_voxels, phi.n_fibers,
                 tuple(dictionary.shape), str(dictionary.dtype))
        obs.counter("select.probe", format=fmt,
                    shape="seen" if shape in _PROBED else "new").inc()
        with obs.span("select.measure", {"format": fmt, "nc": phi.n_coeffs,
                                         "nc_probe": nc_probe}):
            cost = tsearch.time_call(probe(fmt, padded), warmup=1, repeats=3)
        _PROBED.add(shape)
        return cost

    best_i, _ = tsearch.measure_candidates(tuple(allowed), measure)
    return tuple(allowed)[best_i]


def resolve_format(phi: PhiTensor, problem, config, cache=None,
                   allowed: Optional[Tuple[str, ...]] = None,
                   mesh_aware: bool = True,
                   probe_nc: Optional[int] = None) -> FormatPlan:
    """Engine entry point: honor an explicit ``config.format`` or select.

    ``allowed`` restricts the candidate set (the batched engine passes the
    vmappable subset — SELL widths are per-subject static shapes).  Under a
    multi-cell mesh request (``shard_rows * shard_cols > 1``) the "auto"
    candidate set is further restricted to formats with a registered mesh
    executor — alto has no sharded path, so selecting it would silently
    drop the requested partitioning.  Callers for whom the mesh is
    placement-only (the batched engine: ``shard_rows/cols`` just
    device_put the stacked operands, no mesh executor runs) pass
    ``mesh_aware=False`` to keep the full candidate set.  ``probe_nc``
    is the padded coefficient count the caller runs at (the batched
    engine's ladder size); the measure rung probes there.
    """
    fmt = getattr(config, "format", "coo")
    row_tile, slot_tile = _geometry(config)
    params = dict(row_tile=row_tile, slot_tile=slot_tile)
    if fmt != "auto":
        if fmt not in _FORMAT_EXECUTORS:
            raise ValueError(
                f"format must be one of {format_names() + ('auto',)}, "
                f"got {fmt!r}")
        if allowed is not None and fmt not in allowed:
            raise ValueError(
                f"format {fmt!r} is not supported here (allowed: {allowed})")
        return FormatPlan(fmt, "explicit", params)
    candidates = (tuple(allowed) if allowed is not None
                  else DEFAULT_CANDIDATES)
    if mesh_aware and _mesh_cells(config) > 1:
        from repro.core.registry import REGISTRY
        mesh_ok = tuple(f for f in candidates
                        if REGISTRY.mesh_executor_for(f) is not None)
        if not mesh_ok:
            raise ValueError(
                f"no candidate format in {candidates} has a mesh executor "
                f"(shard_rows x shard_cols = {_mesh_cells(config)})")
        candidates = mesh_ok
    predictor = None
    if (getattr(config, "predict", "auto") != "off"
            and cache is not None and cache.enabled):
        from repro.learn import load_predictor
        predictor = load_predictor(cache.directory)
    return choose_format(
        phi, problem.dictionary, row_tile=row_tile, slot_tile=slot_tile,
        allowed=candidates,
        sell_accept=getattr(config, "sell_accept", DEFAULT_SELL_ACCEPT),
        sell_reject=getattr(config, "sell_reject", DEFAULT_SELL_REJECT),
        cache=cache, predictor=predictor, probe_nc=probe_nc)
