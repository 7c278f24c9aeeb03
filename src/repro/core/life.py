"""LiFE end-to-end engine: connectome pruning with pluggable SpMV executors.

Executor dispatch goes through :mod:`repro.core.registry` — the code-version
ladder (paper §6.3.1/§6.4.1), selectable via ``executor=``:

  naive        CPU-naive        : Figure-3 translation, scatter/gather adds
  opt-paper    CPU/GPU-opt      : per-op restructuring as the paper ships it
                                  (DSC voxel-sorted, WC atom-sorted)
  opt          TPU-opt (ours)   : output-side sorts for both ops
                                  (DSC voxel-sorted, WC fiber-sorted)
  kernel       TPU Pallas       : inspector-planned tiled kernels
                                  (compiled on TPU, interpreted elsewhere)
  auto         runtime autotune : measured selection (paper's hybrid/runtime
                                  choice, §4.1.2)
  shard        mesh partition   : 2-D shard_map SpMVs over inner sorted-COO
                                  cells (distributed/life_shard, DESIGN.md §9)
  shard-sell   mesh + SELL      : per-cell SELL tiles feeding the Pallas SELL
                                  kernels under shard_map

Inspector products (tile plans, autotune choices) are memoized through the
persistent :class:`~repro.core.plan_cache.PlanCache`, so a second engine
construction on the same dataset pays ~zero ``inspector_seconds``
(amortization across runs, DESIGN.md §6.3).

Weight compaction (``compact_every > 0``) periodically drops coefficients
whose fiber weight reached zero — the paper's "evaded BLAS call" effect,
realized as an inspector re-run whose cost is amortized over the following
iterations.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.plan_cache import PlanCache
from repro.core.registry import REGISTRY, Executor, create_for_format
from repro.core.restructure import compact_by_weight
from repro.core.sbbnnls import (SbbnnlsState, nnls_loss, sbbnnls_init,
                                sbbnnls_steps)
from repro.core.std import PhiTensor
from repro.data.dmri import LifeProblem

EXECUTORS = REGISTRY.names()          # public alias; registry is the truth


@dataclasses.dataclass
class LifeConfig:
    """Engine configuration: executor choice plus every tuning knob.

    The fields form four groups — code version (``executor``, ``format``,
    mesh geometry), kernel launch parameters (``c_tile``, ``row_tile``,
    ``slot_tile``, ``seg_tile``), plan-selection policy (``tune``,
    ``predict``, the SELL thresholds, ``compute_dtype``), and the solver
    driver (``n_iters``, compaction).  Instances are plain data: hashable
    config digests and serving batch-compatibility classes are derived
    from them, so two equal configs must mean identical execution.
    """

    executor: str = "opt"
    n_iters: int = 100
    compact_every: int = 0          # 0 disables weight compaction
    compact_threshold: float = 0.0
    c_tile: int = 256               # kernel coefficient-tile size
    row_tile: int = 8               # kernel output row-block size
    # mesh geometry (R, C) for the sharded executors; with R*C > 1 the
    # format="auto" candidate set and executor mapping become mesh-aware
    # (formats/select.py picks among formats with a registered mesh executor)
    shard_rows: int = 1
    shard_cols: int = 1
    # Phi layout: "coo" (canonical; executor= picks the code version),
    # "sell" / "alto" (force that format's executor), or "auto" (pick per
    # dataset via formats/select.py, FormatPlan-cached).  DESIGN.md §7.
    format: str = "coo"
    slot_tile: int = 32             # SELL slots consumed per kernel grid step
    seg_tile: int = 16              # F-COO segments-per-chunk rounding (the
                                    # one-hot K dim of kernels/fcoo.py)
    # Kernel autotuning (DESIGN.md §10): "off" runs the frozen constants
    # above; "cached" replays a persisted TunePlan when one exists (never
    # measures); "full" searches the launch-parameter space on a cache miss
    # and persists the winner per (dataset, executor, backend, devices).
    tune: str = "off"
    # Learned cold-start selection (DESIGN.md §14): "auto" lets a trained
    # predictor beside the plan cache answer format/tune cache misses with
    # zero-measurement reason="predicted" plans (measured refinement runs
    # in the background); "off" disables the predict rung of the ladder.
    predict: str = "auto"
    # Storage dtype of the static operands (dictionary + Phi values):
    # "fp32", "bf16" (bf16 storage / fp32 accumulate — halves resident
    # bytes, accuracy contract repro.tune.plan.BF16_RTOL), or "auto" (a
    # searched axis; requires tune != "off").
    compute_dtype: str = "fp32"
    # cap on measured candidates per search (the default-config candidate
    # is never truncated away, so "tuned" can't regress the frozen config
    # on the tuner's own objective)
    tune_budget: int = 12
    # format="auto" SELL thresholds: padding overhead (extra slots/coeff)
    # below sell_accept takes SELL outright, above sell_reject strikes it
    sell_accept: float = 1.0
    sell_reject: float = 4.0
    # None -> default cache dir ($REPRO_PLAN_CACHE or ~/.cache/repro-life);
    # "" -> plan caching disabled.
    plan_cache_dir: Optional[str] = None
    # cap on the on-disk plan cache (oldest entries pruned past it);
    # None -> $REPRO_PLAN_CACHE_MAX_BYTES or unbounded.
    plan_cache_max_bytes: Optional[int] = None


class LifeEngine:
    """Binds a LifeProblem to an executor; runs SBBNNLS; reports pruning."""

    def __init__(self, problem: LifeProblem, config: LifeConfig,
                 cache: Optional[PlanCache] = None):
        if config.executor not in REGISTRY:
            raise ValueError(f"executor must be one of {REGISTRY.names()}")
        from repro.tune.tuner import validate_config as _validate_tune
        _validate_tune(config)
        self.problem = problem
        self.config = config
        self.cache = cache if cache is not None else PlanCache(
            config.plan_cache_dir, config.plan_cache_max_bytes)
        self.inspector_seconds = 0.0
        self._build(problem.phi)

    # -- inspector ----------------------------------------------------------
    def _build(self, phi: PhiTensor) -> None:
        t0 = time.perf_counter()
        self.phi = phi
        if self.config.format == "coo":
            name = self.config.executor
            if self.config.shard_rows * self.config.shard_cols > 1:
                # a multi-cell mesh request is the strongest signal: route
                # through the mesh-aware mapping (-> "shard") instead of
                # silently running the configured executor on one device
                from repro.formats import select as fsel
                name = fsel.executor_for("coo", self.config)
            self.executor: Executor = REGISTRY.create(
                name, phi, self.problem, self.config, self.cache)
        else:
            # format-parameterized path: "sell"/"alto" force that layout's
            # executor; "auto" selects per dataset (FormatPlan-cached)
            self.executor = create_for_format(
                phi, self.problem, self.config, self.cache)
        self.matvec = self.executor.matvec
        self.rmatvec = self.executor.rmatvec
        dt = time.perf_counter() - t0
        self.inspector_seconds += dt
        obs.histogram("engine.build.seconds").observe(dt)
        # held instruments for the hot step loop (no-ops while disabled);
        # HLO byte counts are invalidated here because compaction rebinds
        # the SpMV closures over a smaller Phi
        self._op_bytes: Optional[float] = None
        self._h_step = obs.histogram("engine.step.seconds",
                                     executor=self.executor.name)
        self._g_frac = obs.gauge("engine.roofline.fraction",
                                 executor=self.executor.name,
                                 format=self.config.format)
        self._g_bw = obs.gauge("engine.achieved_bandwidth.gbps",
                               executor=self.executor.name,
                               format=self.config.format)

    @property
    def dsc_plan(self):
        """Autotuned DSC SpmvPlan (auto executor only)."""
        return self.executor.plans.get("dsc")

    @property
    def format_plan(self):
        """Chosen FormatPlan (format != "coo" only)."""
        return self.executor.plans.get("format")

    @property
    def tune_plan(self):
        """Resolved TunePlan (tune != "off" only; DESIGN.md §10)."""
        return self.executor.plans.get("tune")

    @property
    def resolved_compute_dtype(self) -> str:
        """The storage dtype this engine actually runs under — the tune
        plan's winner when a search resolved ``compute_dtype="auto"``,
        the config value otherwise.  Serving pins checkpoints (and bucket
        rebuilds) to this, never to the unresolved request."""
        plan = self.tune_plan
        if plan is not None:
            return plan.compute_dtype
        cd = getattr(self.config, "compute_dtype", "fp32")
        return "fp32" if cd == "auto" else cd

    @property
    def wc_plan(self):
        """Autotuned WC SpmvPlan (auto executor only; None otherwise)."""
        return self.executor.plans.get("wc")

    @property
    def cache_stats(self):
        """Hit/miss counters of the bound plan cache (CacheStats)."""
        return self.cache.stats

    # -- driver --------------------------------------------------------------
    def init_state(self, w0: Optional[jax.Array] = None) -> SbbnnlsState:
        """Fresh solver state (all-ones start unless ``w0`` is given)."""
        nf = self.problem.phi.n_fibers
        w = jnp.ones((nf,), self.problem.dictionary.dtype) if w0 is None else w0
        return sbbnnls_init(w)

    def step(self, state: SbbnnlsState, k: int
             ) -> Tuple[SbbnnlsState, np.ndarray]:
        """Advance ``state`` by ``k`` SBBNNLS iterations (stepped API).

        State in -> k iters -> state out; the iteration counter rides in the
        state, so chained calls reproduce one uninterrupted run exactly.
        The serving scheduler time-slices long solves through this."""
        if not obs.SWITCH.on:
            new, ls = sbbnnls_steps(self.matvec, self.rmatvec,
                                    self.problem.b, state, k)
            return new, np.asarray(ls)
        with obs.span("engine.step", {"executor": self.executor.name,
                                      "format": self.config.format,
                                      "k": k}) as sp:
            t0 = time.perf_counter()
            new, ls = sbbnnls_steps(self.matvec, self.rmatvec,
                                    self.problem.b, state, k)
            ls = np.asarray(ls)     # host transfer blocks on the computation
            dt = time.perf_counter() - t0
            self._h_step.observe(dt)
            self._annotate_roofline(sp, k, dt)
        return new, ls

    def _annotate_roofline(self, sp, k: int, dt: float) -> None:
        """Set achieved-bandwidth gauges from HLO byte counts (obs-on only).

        Bytes per SBBNNLS iteration follow the tuner's dominant-op mix
        (DSC every iteration + line-search probe, WC on alternation):
        ``DSC_WEIGHT * dsc_bytes + WC_WEIGHT * wc_bytes``.  Fraction is
        against the running chip's HBM bandwidth (``analysis.PEAKS``); on a
        device with no published peaks (a CPU) nothing is written."""
        from repro.roofline.analysis import PEAKS
        kind = jax.devices()[0].device_kind
        if kind not in PEAKS or dt <= 0.0:
            return
        bytes_per_iter = self._op_bytes_per_iter()
        achieved = bytes_per_iter * k / dt
        frac = achieved / PEAKS[kind]["hbm_bw"]
        self._g_bw.set(achieved / 1e9)
        self._g_frac.set(frac)
        sp.set_attr("bytes_accessed", bytes_per_iter * k)
        sp.set_attr("achieved_gbps", achieved / 1e9)
        sp.set_attr("roofline_fraction", frac)

    def _op_bytes_per_iter(self) -> float:
        """Weighted HBM bytes of one SBBNNLS iteration, from the compiled
        HLO of the bound SpMV pair (lazy, memoized until the next _build)."""
        if self._op_bytes is None:
            from repro.roofline import hlo_cost
            from repro.tune.tuner import DSC_WEIGHT, WC_WEIGHT
            d = self.problem.dictionary
            probes = ((self.matvec, jnp.ones((self.phi.n_fibers,), d.dtype)),
                      (self.rmatvec,
                       jnp.ones((self.phi.n_voxels, d.shape[1]), d.dtype)))
            apply = jax.jit(lambda fn, x: fn(x))
            dsc_b, wc_b = (
                hlo_cost.analyze(
                    apply.lower(fn, probe).compile().as_text(),
                    n_chips=1).bytes_accessed
                for fn, probe in probes)
            self._op_bytes = DSC_WEIGHT * dsc_b + WC_WEIGHT * wc_b
        return self._op_bytes

    def run(self, n_iters: Optional[int] = None,
            w0: Optional[jax.Array] = None) -> Tuple[jax.Array, np.ndarray]:
        """Run SBBNNLS with optional periodic weight compaction."""
        cfg = self.config
        n_iters = cfg.n_iters if n_iters is None else n_iters
        state = self.init_state(w0)
        losses: List[np.ndarray] = []
        chunk = cfg.compact_every if cfg.compact_every > 0 else n_iters
        done = 0
        while done < n_iters:
            k = min(chunk, n_iters - done)
            state, ls = self.step(state, k)
            losses.append(ls)
            done += k
            if cfg.compact_every > 0 and done < n_iters:
                t0 = time.perf_counter()
                compacted = compact_by_weight(self.phi, state.w,
                                              cfg.compact_threshold)
                if compacted.n_coeffs < self.phi.n_coeffs:
                    self._build(compacted)
                self.inspector_seconds += time.perf_counter() - t0
        return state.w, np.concatenate(losses)

    def loss(self, w: jax.Array) -> float:
        """NNLS objective ``0.5 * ||Phi w - b||^2`` under this engine's
        bound SpMV (so a compacted engine scores against its own Phi)."""
        return float(nnls_loss(self.matvec, self.problem.b, w))

    def prune_stats(self, w: jax.Array, threshold: float = 1e-6) -> dict:
        """Support recovery vs the synthetic ground truth.

        Args:
            w: converged fiber weights.
            threshold: weights at or below this count as pruned.

        Returns:
            dict with ``kept``/``total`` counts and ``precision``/
            ``recall`` of the recovered support against ``w_true > 0``.
            Only meaningful on synthetic problems that carry ``w_true``;
            for ground-truth-free pruning use
            :func:`repro.science.prune_connectome`.
        """
        w_np = np.asarray(w)
        true = np.asarray(self.problem.w_true) > 0
        kept = w_np > threshold
        tp = float(np.sum(kept & true))
        return dict(
            kept=float(kept.sum()),
            total=float(kept.size),
            precision=tp / max(1.0, float(kept.sum())),
            recall=tp / max(1.0, float(true.sum())),
        )
