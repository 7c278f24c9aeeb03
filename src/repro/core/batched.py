"""Batched multi-subject LiFE: one vmapped SBBNNLS over a subject cohort.

Production LiFE serves many subjects against one shared diffusion dictionary
(the canonical atoms depend on the gradient scheme, not the subject).  Per
subject the workload is identical in *structure* — same Nv voxel grid, same
Nf candidate fibers, same Ntheta directions — but each Phi tensor has its own
coefficient count Nc_s.  This engine:

  1. restructures every subject's Phi per the chosen executor (the same
     per-op sorts :mod:`repro.core.registry` applies for one subject),
  2. pads each subject's coefficient arrays to the cohort max Nc with inert
     dummy slots — value 0 so padding contributes nothing through either
     SpMV, and sort-key index = (dim size - 1) so the padded tail preserves
     the sortedness the segment-sum executors rely on (the same dummy-slot
     idiom as ``kernels/ops.py:_padded_operands``),
  3. stacks the cohort into (S, Nc_max) operands and runs SBBNNLS for all
     subjects at once: one ``lax.scan`` whose body is the vmapped solver
     step, so the per-iteration Barzilai-Borwein step size stays
     *per-subject* while every SpMV becomes one batched device computation.

Batching composes with the plan cache: the "auto" path autotunes once (on
the first subject, through the persistent cache) and applies the measured
sort choice cohort-wide.  Executors whose operands are per-subject static
shapes (``kernel`` tile plans, ``shard`` mesh layouts) are rejected —
:class:`~repro.core.registry.Executor.vmappable` records which factories
admit stacking.  See DESIGN.md §6.2.

Mesh placement (DESIGN.md §9): with ``shard_rows * shard_cols > 1`` the
stacked cohort is laid out over the same (``data``, ``model``) mesh the
sharded executors use — *subjects* shard over the batch (``data``) axis and
the stacked Phi coefficient slots over ``model`` — by ``device_put``-ing
the operands under NamedShardings and letting GSPMD partition the vmapped
solve.  An axis whose size does not divide its mesh axis stays replicated
(jax requires even chunks for explicit placement); results are unchanged
either way, only the partitioning differs.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import spmv
from repro.core.plan_cache import PlanCache
from repro.core.registry import _DSC_FNS, _WC_FNS, REGISTRY
from repro.core.restructure import sort_by_host
from repro.core.sbbnnls import SbbnnlsState, sbbnnls_step
from repro.core.std import PhiTensor
from repro.data.dmri import LifeProblem

# executor name -> (dsc sort dim or None, wc sort dim or None, dsc fn, wc fn)
_BATCH_RECIPES = {
    "naive": (None, None, spmv.dsc_naive, spmv.wc_naive),
    "opt": ("voxel", "fiber", spmv.dsc, spmv.wc),
    "opt-paper": ("voxel", "atom", spmv.dsc, spmv.wc_atom_sorted),
}

# dims whose executor consumes a *sorted* segment reduction; padding must
# extend the sort key monotonically for these
_SEGMENT_SORTED = {(spmv.dsc, "voxel"), (spmv.wc, "fiber")}


def _pad_sorted(phi: PhiTensor, nc_max: int, sort_dim: Optional[str],
                keep_sorted: bool) -> PhiTensor:
    """Pad a (possibly sorted) PhiTensor to nc_max inert dummy coefficients."""
    pad = nc_max - phi.n_coeffs
    if pad == 0:
        return phi
    dim_last = {"atom": phi.n_atoms - 1, "voxel": phi.n_voxels - 1,
                "fiber": phi.n_fibers - 1}

    def pad_idx(arr, dim):
        fill = dim_last[dim] if (keep_sorted and dim == sort_dim) else 0
        return jnp.concatenate(
            [arr, jnp.full((pad,), fill, arr.dtype)])

    return dataclasses.replace(
        phi,
        atoms=pad_idx(phi.atoms, "atom"),
        voxels=pad_idx(phi.voxels, "voxel"),
        fibers=pad_idx(phi.fibers, "fiber"),
        values=jnp.concatenate(
            [phi.values, jnp.zeros((pad,), phi.values.dtype)]))


def _stack_phis(phis: Sequence[PhiTensor]) -> PhiTensor:
    return dataclasses.replace(
        phis[0],
        atoms=jnp.stack([p.atoms for p in phis]),
        voxels=jnp.stack([p.voxels for p in phis]),
        fibers=jnp.stack([p.fibers for p in phis]),
        values=jnp.stack([p.values for p in phis]))


class BatchedLifeEngine:
    """Runs SBBNNLS for a cohort of subjects in one vmapped computation.

    All subjects must share the dictionary shape and the (Nv, Nf) problem
    geometry; coefficient counts may differ (padded to the cohort max).
    """

    def __init__(self, problems: Sequence[LifeProblem], config,
                 cache: Optional[PlanCache] = None):
        if not problems:
            raise ValueError("need at least one subject")
        self.problems = list(problems)
        self.config = config
        self.cache = cache if cache is not None else PlanCache(
            getattr(config, "plan_cache_dir", None),
            getattr(config, "plan_cache_max_bytes", None))
        self.format_plan = None       # set when config.format != "coo"
        self.tune_plan = None         # set when config.tune != "off"
        from repro.tune.tuner import validate_config as _validate_tune
        _validate_tune(config)
        if getattr(config, "compact_every", 0) > 0:
            raise ValueError(
                "weight compaction is per-subject (changes Nc mid-run) and "
                "is not supported by the batched engine; use LifeEngine")
        p0 = self.problems[0]
        for p in self.problems[1:]:
            if (p.phi.n_voxels, p.phi.n_fibers) != (p0.phi.n_voxels,
                                                    p0.phi.n_fibers):
                raise ValueError("subjects must share (Nv, Nf) geometry")
            if not np.array_equal(np.asarray(p.dictionary),
                                  np.asarray(p0.dictionary)):
                raise ValueError("subjects must share the dictionary "
                                 "(same gradient scheme and atoms)")
        self.dictionary = p0.dictionary
        self.n_subjects = len(self.problems)
        self.inspector_seconds = 0.0
        self.mesh = self._make_mesh()
        self._build()

    def _make_mesh(self):
        """(data, model) mesh when the config asks for a multi-cell layout."""
        R = getattr(self.config, "shard_rows", 1)
        C = getattr(self.config, "shard_cols", 1)
        if R * C <= 1:
            return None
        if R * C > len(jax.devices()):
            raise ValueError(
                f"batched mesh needs {R * C} devices, "
                f"have {len(jax.devices())}")
        from jax.sharding import AxisType
        return jax.make_mesh((R, C), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)

    def _place_on_mesh(self) -> None:
        """Subjects over the batch (`data`) axis, Phi slots over `model`.

        Axes that don't divide their mesh axis stay replicated (jax needs
        even chunks for device_put); GSPMD keeps results identical."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        subj = ("data" if self.n_subjects % self.mesh.shape["data"] == 0
                else None)
        slot = ("model" if self.nc_padded % self.mesh.shape["model"] == 0
                else None)
        phi_sh = NamedSharding(self.mesh, P(subj, slot))
        b_sh = NamedSharding(self.mesh, P(subj, None, None))
        self.phi_dsc = jax.device_put(self.phi_dsc, phi_sh)
        self.phi_wc = jax.device_put(self.phi_wc, phi_sh)
        self.b = jax.device_put(self.b, b_sh)

    # -- inspector ----------------------------------------------------------
    def _resolve_recipe(self):
        name = self.config.executor
        fmt = getattr(self.config, "format", "coo")
        self._alto_order = False
        if fmt != "coo":
            # Format selection across the vmappable subset: SELL widths are
            # per-subject static shapes, so only COO and ALTO stack; "auto"
            # picks between them on the first subject (FormatPlan-cached),
            # and an explicit format="sell" is rejected by resolve_format.
            from repro.formats import select as fsel
            # mesh_aware=False: shard_rows/cols are placement-only here
            # (device_put of the stacked operands), so alto stays eligible
            self.format_plan = fsel.resolve_format(
                self.problems[0].phi, self.problems[0], self.config,
                self.cache, allowed=("coo", "alto"), mesh_aware=False)
            if self.format_plan.format == "alto":
                self._alto_order = True
                return None, None, spmv.dsc_naive, spmv.wc_naive
        if name in _BATCH_RECIPES:
            return _BATCH_RECIPES[name]
        if name == "auto":
            # tune once on the first subject (persistent-cache-backed),
            # apply the measured choice cohort-wide
            ex = REGISTRY.create("auto", self.problems[0].phi,
                                 self.problems[0], self.config, self.cache)
            dsc_dim = ex.plans["dsc"].restructure
            wc_dim = ex.plans["wc"].restructure
            return dsc_dim, wc_dim, _DSC_FNS[dsc_dim], _WC_FNS[wc_dim]
        raise ValueError(
            f"executor {name!r} is not vmappable across subjects "
            f"(supported: {sorted(_BATCH_RECIPES) + ['auto']})")

    def _resolve_tuning(self) -> str:
        """Resolve the tune plan on the first subject (persistent-cached);
        returns the storage dtype the stacked operands are built under.

        The batched recipes are pure-jnp (no Pallas tile axes), so the
        searched axis that reaches this engine is the compute dtype; tile
        winners in the plan simply don't apply.  Routing through the same
        resolver keeps the plan-cache entry shared with single-subject
        engines on the same dataset/backend."""
        cfg = self.config
        if getattr(cfg, "tune", "off") == "off":
            cd = getattr(cfg, "compute_dtype", "fp32")
            return "fp32" if cd == "auto" else cd
        from repro.tune.tuner import resolve_plan
        self.tune_plan = resolve_plan(cfg.executor, self.problems[0].phi,
                                      self.problems[0], cfg, self.cache)
        return self.tune_plan.compute_dtype

    def _build(self) -> None:
        t0 = time.perf_counter()
        self._compute_dtype = self._resolve_tuning()
        dsc_dim, wc_dim, self._dsc_fn, self._wc_fn = self._resolve_recipe()
        nc_max = max(p.phi.n_coeffs for p in self.problems)
        self.nc_padded = nc_max

        def prep(phi: PhiTensor, dim: Optional[str], fn) -> PhiTensor:
            sorted_phi = sort_by_host(phi, dim)[0] if dim else phi
            keep_sorted = (fn, dim) in _SEGMENT_SORTED
            return _pad_sorted(sorted_phi, nc_max, dim, keep_sorted)

        phis = [p.phi for p in self.problems]
        if self._alto_order:
            # one ALTO-linearized ordering per subject serves both ops
            # (locality in every mode at once; scatter executors above)
            from repro.formats.alto import AltoPhi
            phis = [AltoPhi.encode(phi).sort()[0].decode() for phi in phis]

        self.phi_dsc = _stack_phis(
            [prep(phi, dsc_dim, self._dsc_fn) for phi in phis])
        self.phi_wc = _stack_phis(
            [prep(phi, wc_dim, self._wc_fn) for phi in phis])
        self.b = jnp.stack([p.b for p in self.problems])
        self._d_op = self.dictionary
        if self._compute_dtype == "bf16":
            # bf16 storage of the static operands (stacked Phi values + the
            # shared dictionary); w/Y/b stay fp32 so every product promotes
            # to fp32 before the segment reductions (DESIGN.md §10.3)
            store = jnp.bfloat16
            self.phi_dsc = dataclasses.replace(
                self.phi_dsc, values=self.phi_dsc.values.astype(store))
            self.phi_wc = dataclasses.replace(
                self.phi_wc, values=self.phi_wc.values.astype(store))
            self._d_op = jnp.asarray(self.dictionary).astype(store)
        if self.mesh is not None:
            self._place_on_mesh()
        self._runner = jax.jit(self._make_runner(),
                               static_argnames=("n_iters",))
        self.inspector_seconds += time.perf_counter() - t0

    @property
    def resolved_compute_dtype(self) -> str:
        """Storage dtype the stacked operands were built under (the tune
        plan's winner when ``compute_dtype="auto"`` was searched)."""
        return self._compute_dtype

    def _make_runner(self):
        d = self._d_op
        dsc_fn, wc_fn = self._dsc_fn, self._wc_fn

        def run_batch(phi_dsc, phi_wc, b, states, *, n_iters: int):
            def one_step(phi_v, phi_w, b_s, state):
                return sbbnnls_step(lambda w: dsc_fn(phi_v, d, w),
                                    lambda y: wc_fn(phi_w, d, y), b_s, state)

            def body(ss, _):
                new = jax.vmap(one_step)(phi_dsc, phi_wc, b, ss)
                return new, new.loss

            final, losses = jax.lax.scan(body, states, xs=None,
                                         length=n_iters)
            return final, losses.T            # states, (S, n_iters)

        return run_batch

    # -- driver --------------------------------------------------------------
    def init_states(self, w0: Optional[jax.Array] = None) -> SbbnnlsState:
        """Fresh per-subject solver states stacked along axis 0 (S, ...)."""
        nf = self.problems[0].phi.n_fibers
        if w0 is None:
            w0 = jnp.ones((self.n_subjects, nf), self.dictionary.dtype)
        s = w0.shape[0]
        return SbbnnlsState(w=w0, it=jnp.zeros((s,), jnp.int32),
                            loss=jnp.zeros((s,), w0.dtype))

    def step(self, states: SbbnnlsState, k: int
             ) -> Tuple[SbbnnlsState, np.ndarray]:
        """Advance every subject's state by ``k`` iterations (stepped API).

        Per-subject iteration counters ride in the stacked state, so subjects
        admitted mid-flight (continuous batching) or restored from a
        checkpoint keep their own Barzilai-Borwein parity — chained calls
        match one uninterrupted run exactly.  Returns (states, (S, k) loss
        trace)."""
        if not obs.SWITCH.on:
            new, losses = self._runner(self.phi_dsc, self.phi_wc, self.b,
                                       states, n_iters=k)
            return new, np.asarray(losses)
        with obs.span("engine.step", {"executor": self.config.executor,
                                      "batched": self.n_subjects, "k": k}):
            t0 = time.perf_counter()
            new, losses = self._runner(self.phi_dsc, self.phi_wc, self.b,
                                       states, n_iters=k)
            losses = np.asarray(losses)   # host transfer blocks on the scan
            obs.histogram("engine.step.seconds",
                          executor=self.config.executor).observe(
                time.perf_counter() - t0)
        return new, losses

    def run(self, n_iters: Optional[int] = None,
            w0: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, np.ndarray]:
        """Solve all subjects; returns (W (S, Nf), losses (S, n_iters))."""
        n_iters = self.config.n_iters if n_iters is None else n_iters
        final, losses = self._runner(self.phi_dsc, self.phi_wc, self.b,
                                     self.init_states(w0), n_iters=n_iters)
        return final.w, np.asarray(losses)

    def prune_stats(self, w_batch: jax.Array,
                    threshold: float = 1e-6) -> List[dict]:
        out = []
        for p, w in zip(self.problems, np.asarray(w_batch)):
            true = np.asarray(p.w_true) > 0
            kept = w > threshold
            tp = float(np.sum(kept & true))
            out.append(dict(
                kept=float(kept.sum()), total=float(kept.size),
                precision=tp / max(1.0, float(kept.sum())),
                recall=tp / max(1.0, float(true.sum()))))
        return out
