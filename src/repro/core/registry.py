"""Executor registry: named factories behind one matvec/rmatvec protocol.

Replaces the if/elif executor ladder that used to live in ``core/life.py``.
Every way of running the two LiFE SpMV ops — naive scatter, restructured
segment-sum (paper and TPU sort choices), inspector-planned Pallas kernels,
runtime autotuning, and the shard_map mesh partition — registers a factory
under a name; ``LifeEngine``, ``BatchedLifeEngine``, benchmarks and tests
all resolve executors through the registry, so adding a code version is one
``@REGISTRY.register(...)`` function, not an engine edit.

Protocol: a factory takes ``(phi, problem, config, cache)`` and returns an
:class:`Executor` whose ``matvec(w) -> (Nv, Ntheta)`` and
``rmatvec(y) -> (Nf,)`` run DSC / WC for that code version.  ``cache`` is a
:class:`~repro.core.plan_cache.PlanCache`; factories that do inspector work
(tile planning, autotune measurement) route it through the cache so the cost
is paid once per dataset, not once per construction (DESIGN.md §6).

The ladder (paper §6.3.1/§6.4.1):

  naive        CPU-naive        : Figure-3 translation, scatter/gather adds
  opt-paper    CPU/GPU-opt      : per-op restructuring as the paper ships it
  opt          TPU-opt (ours)   : output-side sorts for both ops
  kernel       TPU Pallas       : inspector-planned tiled kernels
  kernel-sell  TPU Pallas/SELL  : blocked-ELL layout, direct row-block
                                  accumulation (no prefetch map, DESIGN.md §7)
  alto         linearized COO   : ALTO single-index sort order, one Phi copy
                                  serves both ops
  kernel-fcoo  TPU Pallas/F-COO : segment-flagged linearization; ONE resident
                                  copy feeds both ops via segment-scan
                                  kernels (DESIGN.md §11)
  auto         runtime autotune : measured selection (paper §4.1.2)
  shard        mesh partition   : 2-D shard_map SpMVs over inner sorted-COO
                                  cells behind the same protocol
  shard-sell   mesh + SELL      : per-cell SELL tiles feeding the Pallas
                                  SELL kernels under shard_map (DESIGN.md §9)

Format-parameterized construction: ``create_for_format`` resolves a
``LifeConfig.format`` choice ("coo"/"sell"/"alto"/"auto", the latter via
``formats.select``) to the executor that consumes the chosen layout, and
records the :class:`~repro.formats.base.FormatPlan` in the executor's
``plans`` dict so engines can report what was picked and why.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.tree_util import Partial

from repro.core import spmv
from repro.core.inspector import plan_tiles
from repro.core.plan_cache import PlanCache, spmv_plan_key, tile_plan_key
from repro.core.restructure import SpmvPlan, autotune_plan, sort_by_host
from repro.core.std import PhiTensor

Array = jax.Array
MatVec = Callable[[Array], Array]


@dataclasses.dataclass
class Executor:
    """A bound pair of SpMV closures plus inspector diagnostics.

    Built-in factories return ``jax.tree_util.Partial`` closures whose bound
    arrays (Phi layouts, plans, the dictionary) are pytree leaves, so the
    solver passes them to its compiled program as arguments
    (``core/sbbnnls.py``)."""

    name: str
    matvec: MatVec                        # w (Nf,) -> y (Nv, Ntheta)
    rmatvec: MatVec                       # y (Nv, Ntheta) -> w (Nf,)
    plans: Dict[str, object] = dataclasses.field(default_factory=dict)

    # Set by factories that can run under vmap with stacked operands; the
    # batched engine refuses executors that cannot (kernel plans and mesh
    # layouts are per-subject static shapes).
    vmappable: bool = False


ExecutorFactory = Callable[..., Executor]


class ExecutorRegistry:
    """Name -> factory mapping with decorator registration.

    ``consumes`` records which registered Phi layout a factory materializes
    and runs (every factory *takes* the canonical COO tensor; this names the
    layout it executes over).  The serving scheduler buckets jobs by it, and
    the conformance matrix (tests/test_conformance.py) derives the full set
    of executor x format pairs it must hold to the oracle from it — so a new
    executor is covered by the contract the moment it registers.
    """

    def __init__(self):
        self._factories: Dict[str, ExecutorFactory] = {}
        self._consumes: Dict[str, str] = {}
        self._mesh: Dict[str, bool] = {}

    def register(self, name: str, *, consumes: str = "coo",
                 mesh: bool = False
                 ) -> Callable[[ExecutorFactory], ExecutorFactory]:
        """Decorator registering an executor factory.

        Args:
            name: executor name (``LifeConfig.executor`` value).
            consumes: registered Phi layout the factory runs over.
            mesh: True for the mesh-partitioned path of ``consumes``
                (at most one per format; see :meth:`mesh_executor_for`).

        Raises:
            ValueError: when ``name`` is already registered.
        """
        def deco(factory: ExecutorFactory) -> ExecutorFactory:
            if name in self._factories:
                raise ValueError(f"executor {name!r} already registered")
            self._factories[name] = factory
            self._consumes[name] = consumes
            self._mesh[name] = mesh
            return factory
        return deco

    def names(self) -> Tuple[str, ...]:
        """All registered executor names, sorted."""
        return tuple(sorted(self._factories))

    def consumes(self, name: str) -> str:
        """Phi layout executor ``name`` runs over ("coo"/"sell"/"alto")."""
        if name not in self._consumes:
            raise ValueError(
                f"executor must be one of {self.names()}, got {name!r}")
        return self._consumes[name]

    def executors_for_format(self, format_name: str) -> Tuple[str, ...]:
        """All registered executors that run over ``format_name``."""
        return tuple(sorted(n for n, f in self._consumes.items()
                            if f == format_name))

    def mesh_executor_for(self, format_name: str) -> Optional[str]:
        """The mesh-partitioned executor consuming ``format_name`` (the
        factory registered with ``mesh=True``), or None when the format has
        no sharded path (e.g. alto).  Drives the selector's mesh-aware
        candidate set and the serving scheduler's mesh-slice buckets."""
        for n in self.executors_for_format(format_name):
            if self._mesh.get(n):
                return n
        return None

    def __contains__(self, name: str) -> bool:
        return name in self._factories

    def create(self, name: str, phi: PhiTensor, problem, config,
               cache: Optional[PlanCache] = None,
               tune_plan=None) -> Executor:
        """Instantiate executor ``name`` for ``phi`` (which may be a
        compacted descendant of ``problem.phi``).

        Tuning hook (DESIGN.md §10): with ``config.tune != "off"`` (and no
        explicit ``tune_plan``) the kernel autotuner resolves a
        :class:`~repro.tune.plan.TunePlan` for this (dataset, executor,
        backend) through the plan cache; the plan's launch parameters are
        substituted into the config the factory sees, and the plan itself
        lands in ``executor.plans["tune"]`` so engines can report what ran.
        An explicit ``tune_plan`` is applied verbatim (no search).
        """
        if name not in self._factories:
            raise ValueError(
                f"executor must be one of {self.names()}, got {name!r}")
        if cache is None:
            cache = PlanCache("")        # disabled cache
        if tune_plan is None and getattr(config, "tune", "off") != "off":
            from repro.tune.tuner import resolve_plan
            tune_plan = resolve_plan(name, phi, problem, config, cache)
        if tune_plan is not None:
            config = tune_plan.apply(config)
        executor = self._factories[name](phi, problem, config, cache)
        if tune_plan is not None:
            executor.plans["tune"] = tune_plan
        return executor


REGISTRY = ExecutorRegistry()


# ----------------------------------------------------------------------------
# Built-in factories
# ----------------------------------------------------------------------------

def _compute_dtype(config) -> str:
    """Resolved storage dtype a factory should build under ("auto" only
    reaches a factory when the tuner was bypassed — treat it as fp32)."""
    cd = getattr(config, "compute_dtype", "fp32")
    return "fp32" if cd == "auto" else cd


def _with_storage_dtype(phi: PhiTensor, dictionary, config):
    """bf16 storage of the static operands for the jnp executors.

    Dynamic operands (w, Y) stay fp32, so every product promotes to fp32
    before the segment/scatter reductions — bf16 storage, fp32 accumulate,
    uniformly with the Pallas paths (kernels/ops.py, DESIGN.md §10.3)."""
    if _compute_dtype(config) != "bf16":
        return phi, dictionary
    return (dataclasses.replace(
                phi, values=jnp.asarray(phi.values).astype(jnp.bfloat16)),
            jnp.asarray(dictionary).astype(jnp.bfloat16))


@REGISTRY.register("naive")
def _make_naive(phi, problem, config, cache) -> Executor:
    phi, d = _with_storage_dtype(phi, problem.dictionary, config)
    return Executor(
        name="naive",
        matvec=Partial(spmv.dsc_naive, phi, d),
        rmatvec=Partial(spmv.wc_naive, phi, d),
        vmappable=True)


def _sorted_pair(phi: PhiTensor, wc_dim: str):
    phi_v, order_v = sort_by_host(phi, "voxel")
    phi_w, order_w = sort_by_host(phi, wc_dim)
    return phi_v, phi_w, order_v, order_w


@REGISTRY.register("opt")
def _make_opt(phi, problem, config, cache) -> Executor:
    phi, d = _with_storage_dtype(phi, problem.dictionary, config)
    phi_v, phi_w, _, _ = _sorted_pair(phi, "fiber")
    return Executor(
        name="opt",
        matvec=Partial(spmv.dsc, phi_v, d),
        rmatvec=Partial(spmv.wc, phi_w, d),
        vmappable=True)


@REGISTRY.register("opt-paper")
def _make_opt_paper(phi, problem, config, cache) -> Executor:
    phi, d = _with_storage_dtype(phi, problem.dictionary, config)
    phi_v, phi_w, _, _ = _sorted_pair(phi, "atom")
    return Executor(
        name="opt-paper",
        matvec=Partial(spmv.dsc, phi_v, d),
        rmatvec=Partial(spmv.wc_atom_sorted, phi_w, d),
        vmappable=True)


def planned_tiles(sorted_ids: np.ndarray, n_rows: int, *, c_tile: int,
                  row_tile: int, cache: PlanCache):
    """plan_tiles through the persistent cache (content-addressed)."""
    key = tile_plan_key(sorted_ids, n_rows, c_tile=c_tile, row_tile=row_tile)
    plan = cache.get_tile_plan(key)
    if plan is None:
        plan = plan_tiles(sorted_ids, n_rows, c_tile=c_tile, row_tile=row_tile)
        cache.put_tile_plan(key, plan)
    return plan


@REGISTRY.register("kernel")
def _make_kernel(phi, problem, config, cache) -> Executor:
    from repro.kernels import ops as kops
    d = problem.dictionary
    phi_v, phi_w, _, _ = _sorted_pair(phi, "fiber")
    dsc_plan = planned_tiles(np.asarray(phi_v.voxels), phi.n_voxels,
                             c_tile=config.c_tile, row_tile=config.row_tile,
                             cache=cache)
    wc_plan = planned_tiles(np.asarray(phi_w.fibers), phi.n_fibers,
                            c_tile=config.c_tile, row_tile=config.row_tile,
                            cache=cache)
    cd = _compute_dtype(config)
    return Executor(
        name="kernel",
        matvec=kops.make_dsc(phi_v, d, dsc_plan, compute_dtype=cd),
        rmatvec=kops.make_wc(phi_w, d, wc_plan, compute_dtype=cd),
        plans=dict(dsc_tiles=dsc_plan, wc_tiles=wc_plan))


@REGISTRY.register("kernel-sell", consumes="sell")
def _make_kernel_sell(phi, problem, config, cache) -> Executor:
    """Pallas executors over the blocked-ELL layout (formats/sell.py).

    The SELL encode replaces the TilePlan inspector entirely: the layout's
    static slot arrays ARE the plan, so there is nothing to tile, no scalar
    prefetch, and no one-hot scatter in the kernels (DESIGN.md §7)."""
    from repro.formats.sell import SellPhi
    from repro.kernels import ops as kops
    d = problem.dictionary
    row_tile = getattr(config, "row_tile", 8)
    slot_tile = getattr(config, "slot_tile", 32)
    sell_dsc = SellPhi.encode(phi, op="dsc", row_tile=row_tile,
                              slot_tile=slot_tile)
    sell_wc = SellPhi.encode(phi, op="wc", row_tile=row_tile,
                             slot_tile=slot_tile)
    cd = _compute_dtype(config)
    return Executor(
        name="kernel-sell",
        matvec=kops.make_dsc_sell(sell_dsc, d, compute_dtype=cd),
        rmatvec=kops.make_wc_sell(sell_wc, d, compute_dtype=cd),
        plans=dict(sell_dsc=sell_dsc, sell_wc=sell_wc))


@REGISTRY.register("kernel-fcoo", consumes="fcoo")
def _make_kernel_fcoo(phi, problem, config, cache) -> Executor:
    """Pallas segment-scan executors over ONE F-COO copy (formats/fcoo.py).

    Unlike kernel-sell there is no per-op encode: the single linearized
    stream plus its segment metadata serves matvec AND rmatvec (the WC view
    is a permutation gather, not a copy) — the one-copy residency DESIGN.md
    §11 accounts for and table12 gates at 0.6x of SELL(DSC)+SELL(WC)."""
    from repro.formats.fcoo import FcooPhi
    from repro.kernels import ops as kops
    fc = FcooPhi.encode(phi, c_tile=config.c_tile,
                        seg_tile=getattr(config, "seg_tile", 16))
    matvec, rmatvec = kops.make_fcoo_ops(
        fc, problem.dictionary, compute_dtype=_compute_dtype(config))
    return Executor(name="kernel-fcoo", matvec=matvec, rmatvec=rmatvec,
                    plans=dict(fcoo=fc))


@REGISTRY.register("alto", consumes="alto")
def _make_alto(phi, problem, config, cache) -> Executor:
    """Both ops over one ALTO-ordered Phi copy (formats/alto.py).

    The linearized sort gives locality in every mode at once, so the same
    coefficient order feeds DSC and WC — halving resident index memory
    versus the two per-op sorted copies the other executors keep."""
    from repro.formats.alto import AltoPhi
    enc, _ = AltoPhi.encode(phi).sort()
    phi_lin, d = _with_storage_dtype(enc.decode(), problem.dictionary,
                                     config)
    # keep accounting only — retaining `enc` would hold a second
    # (lin, values) copy alive for the executor's lifetime
    meta = dict(n_coeffs=enc.n_coeffs, nbytes=enc.nbytes)
    return Executor(
        name="alto",
        matvec=Partial(spmv.dsc_naive, phi_lin, d),
        rmatvec=Partial(spmv.wc_naive, phi_lin, d),
        plans=dict(alto=meta),
        vmappable=True)


def create_for_format(phi, problem, config,
                      cache: Optional[PlanCache] = None,
                      allowed: Optional[Tuple[str, ...]] = None) -> Executor:
    """Resolve ``config.format`` (possibly "auto") to a bound executor.

    The chosen/loaded FormatPlan lands in ``executor.plans["format"]``.
    ``format="coo"`` (the default) preserves the pre-format behaviour:
    the executor named by ``config.executor`` over the canonical layout.
    """
    from repro.formats import select as fsel
    if cache is None:
        cache = PlanCache("")
    plan = fsel.resolve_format(phi, problem, config, cache, allowed=allowed)
    name = fsel.executor_for(plan.format, config)
    cells = (getattr(config, "shard_rows", 1)
             * getattr(config, "shard_cols", 1))
    if cells > 1 and name != REGISTRY.mesh_executor_for(plan.format):
        # never silently drop a requested partition: a format with no
        # sharded path (alto) cannot honor shard_rows x shard_cols > 1
        from repro.formats import format_names
        meshable = [f for f in format_names()
                    if REGISTRY.mesh_executor_for(f)]
        raise ValueError(
            f"format {plan.format!r} has no mesh executor; cannot honor "
            f"shard_rows x shard_cols = {cells} "
            f"(mesh-capable formats: {meshable})")
    executor = REGISTRY.create(name, phi, problem, config, cache)
    executor.plans["format"] = plan
    return executor


# per sort-dim executors: output-side sorts get segment-sum paths,
# input-side sorts keep the scatter (paper Table 2/3 combinations)
_DSC_FNS = {"atom": spmv.dsc_atom_sorted, "voxel": spmv.dsc,
            "fiber": spmv.dsc_atom_sorted}   # fiber-sort: unsorted Y path
_WC_FNS = {"atom": spmv.wc_atom_sorted, "voxel": spmv.wc_atom_sorted,
           "fiber": spmv.wc}


@REGISTRY.register("auto")
def _make_auto(phi, problem, config, cache) -> Executor:
    phi, d = _with_storage_dtype(phi, problem.dictionary, config)
    probe_dtype = problem.dictionary.dtype     # probes mimic solver operands
    atoms = np.asarray(phi.atoms)
    voxels = np.asarray(phi.voxels)
    fibers = np.asarray(phi.fibers)

    def tuned(op: str, run) -> SpmvPlan:
        key = spmv_plan_key(op, atoms, voxels, fibers)
        plan = cache.get_spmv_plan(key)
        if plan is None:
            plan = autotune_plan(op, phi, run)
            cache.put_spmv_plan(key, plan)
        if plan.order is None:      # cached choice without the permutation
            _, plan.order = sort_by_host(phi, plan.restructure)
        return plan

    w_probe = jnp.ones((phi.n_fibers,), probe_dtype)
    y_probe = jnp.ones((phi.n_voxels, d.shape[1]), probe_dtype)
    dsc_plan = tuned("dsc", lambda p, dim: _DSC_FNS[dim](p, d, w_probe))
    wc_plan = tuned("wc", lambda p, dim: _WC_FNS[dim](p, d, y_probe))

    phi_v = phi.take(jnp.asarray(dsc_plan.order))
    phi_w = phi.take(jnp.asarray(wc_plan.order))
    dsc_fn = _DSC_FNS[dsc_plan.restructure]
    wc_fn = _WC_FNS[wc_plan.restructure]
    return Executor(
        name="auto",
        matvec=Partial(dsc_fn, phi_v, d),
        rmatvec=Partial(wc_fn, phi_w, d),
        plans=dict(dsc=dsc_plan, wc=wc_plan),
        vmappable=True)


def _layout_positions(plan, n_voxels: int, n_fibers: int
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """global id -> padded (range-stacked) position maps, host-computed once."""
    w_pos = np.zeros(n_fibers, np.int64)
    for c in range(plan.C):
        lo, hi = plan.fiber_cuts[c], plan.fiber_cuts[c + 1]
        w_pos[lo:hi] = c * plan.nf_local + np.arange(hi - lo)
    y_pos = np.zeros(n_voxels, np.int64)
    for r in range(plan.R):
        lo, hi = plan.voxel_cuts[r], plan.voxel_cuts[r + 1]
        y_pos[lo:hi] = r * plan.nv_local + np.arange(hi - lo)
    return w_pos, y_pos


def _make_shard_executor(phi, problem, config, cache,
                         cell_format: str) -> Executor:
    """Shared factory for the mesh executors (`shard` / `shard-sell`).

    Builds an (R, C) = (shard_rows, shard_cols) mesh over the available
    devices, materializes each (voxel-range x fiber-range) cell through the
    PhiFormat protocol (``formats/shard.py:ShardPhi`` composing the inner
    ``cell_format``), and wraps the shard_map'd per-op functions with the
    global<->padded layout maps so callers see plain (Nf,) -> (Nv, Ntheta)
    closures.  The partition plan is persistent-cache-backed under a key
    that includes the mesh shape, the inner format, and the device count.
    Each cell array is placed with a ``NamedSharding`` over the mesh, so
    every device holds only its own cells.
    """
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.distributed import life_shard as LS
    from repro.formats.shard import encode_pair, partition_cuts
    from repro.kernels.ops import bind, pad_lanes, sell_lanes, storage_cast

    R = getattr(config, "shard_rows", 1)
    C = getattr(config, "shard_cols", 1)
    name = "shard" if cell_format == "coo" else "shard-sell"
    if R * C > len(jax.devices()):
        raise ValueError(
            f"{name} executor needs {R * C} devices, "
            f"have {len(jax.devices())}")
    mesh = jax.make_mesh((R, C), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    d = problem.dictionary
    n_theta = d.shape[1]
    cd = _compute_dtype(config)
    plan = partition_cuts(phi, R, C, cell_format=cell_format, cache=cache)
    row_tile = getattr(config, "row_tile", 8)
    slot_tile = getattr(config, "slot_tile", 32)
    sp_dsc, sp_wc = encode_pair(phi, cell_format=cell_format, plan=plan,
                                row_tile=row_tile, slot_tile=slot_tile)
    meta = dict(nv_local=plan.nv_local, nf_local=plan.nf_local,
                n_theta=n_theta)

    nf_pad = C * plan.nf_local
    nv_pad = R * plan.nv_local

    if cell_format == "coo":
        dsc_sm, wc_sm = LS.make_sharded_ops(mesh, meta)
        keys = ("atoms", "voxels", "fibers", "values")
        layout, lanes = np.asarray, (lambda y: y)
        d_op = storage_cast(d, cd)
    else:
        dsc_sm, wc_sm = LS.make_sharded_sell_ops(
            mesh, meta, slot_tile=slot_tile, out_dtype=d.dtype)
        keys = ("atoms", "others", "values")
        layout, lanes = sell_lanes, pad_lanes
        d_op = pad_lanes(storage_cast(d, cd))

    def place(arrays):
        """Cell arrays onto the mesh, one (data, model) cell per device."""
        out = []
        for k in keys:
            x = layout(arrays[k])
            x = storage_cast(x, cd) if k == "values" else jnp.asarray(x)
            spec = P("data", "model", *([None] * (x.ndim - 2)))
            out.append(jax.device_put(x, NamedSharding(mesh, spec)))
        return tuple(out)
    w_pos, y_pos = _layout_positions(plan, phi.n_voxels, phi.n_fibers)
    replicated = NamedSharding(mesh, P())
    shared = dict(d=jax.device_put(d_op, replicated),
                  w_pos=jax.device_put(w_pos, replicated),
                  y_pos=jax.device_put(y_pos, replicated))

    def matvec(op, w: Array) -> Array:
        w_padded = jnp.zeros((nf_pad,), w.dtype).at[op["w_pos"]].set(w)
        y_padded = dsc_sm(*op["cell"], op["d"], w_padded)[:, :n_theta]
        return jnp.take(y_padded, op["y_pos"], axis=0)

    def rmatvec(op, y: Array) -> Array:
        y_padded = jnp.zeros((nv_pad, y.shape[1]), y.dtype
                             ).at[op["y_pos"]].set(y)
        w_padded = wc_sm(*op["cell"], op["d"], lanes(y_padded))
        return jnp.take(w_padded, op["w_pos"])

    matvec = bind(matvec, dict(shared, cell=place(sp_dsc.arrays)))
    rmatvec = bind(rmatvec, dict(shared, cell=place(sp_wc.arrays)))
    return Executor(name=name, matvec=matvec, rmatvec=rmatvec,
                    plans=dict(mesh=mesh, partition=plan,
                               shard_dsc=sp_dsc, shard_wc=sp_wc))


@REGISTRY.register("shard", mesh=True)
def _make_shard(phi, problem, config, cache) -> Executor:
    """2-D mesh-partitioned SpMVs over inner sorted-COO cells."""
    return _make_shard_executor(phi, problem, config, cache, "coo")


@REGISTRY.register("shard-sell", consumes="sell", mesh=True)
def _make_shard_sell(phi, problem, config, cache) -> Executor:
    """2-D mesh-partitioned SpMVs over per-cell SELL tiles: each device's
    (voxel-range x fiber-range) cell is a blocked-ELL slot array feeding the
    Pallas SELL kernels under shard_map (DESIGN.md §9)."""
    return _make_shard_executor(phi, problem, config, cache, "sell")
