"""Search-space enumeration for the kernel autotuner (DESIGN.md §10.1).

One table, :data:`TUNABLE_TILES`, names the launch-parameter axes each Pallas
executor exposes — the analogue of the paper's per-platform sweep columns.
Executors without an entry (the pure-jnp scatter/segment paths) have no tile
axes; their search space degenerates to the compute-dtype axis.

Candidate enumeration always includes the *current* config values, so the
measured winner can never be worse than the frozen defaults on the tuner's
own objective — the invariant ``benchmarks/table15_tuning.py`` reports on.
"""
from __future__ import annotations

import itertools
from typing import Dict, List, Tuple

from repro.tune.plan import COMPUTE_DTYPES

#: executor registry name -> the launch-parameter axes its kernels take.
#: The COO Pallas pair tiles coefficients (c_tile) into row blocks
#: (row_tile); the SELL kernels and their per-cell shard variants walk
#: (slot_tile, 128-row) blocks of the slot-major layout, so their row block
#: is the lane width and not an axis; the F-COO pair chunks the linearized
#: stream (c_tile) with seg_tile-quantized segment blocks.
TUNABLE_TILES: Dict[str, Tuple[str, ...]] = {
    "kernel": ("c_tile", "row_tile"),
    "kernel-sell": ("slot_tile",),
    "kernel-fcoo": ("c_tile", "seg_tile"),
    "shard-sell": ("slot_tile",),
}

#: per-axis candidate values (the current config value is always added).
#: Every value compiles for the TPU: row_tile (the COO output row-block)
#: and slot_tile (SELL slots, which the kernels take on the sublane axis)
#: stay multiples of the fp32 sublane (8); c_tile and seg_tile size lane
#: blocks that span the whole array axis.  slot_tile and c_tile sweep the
#: padding-vs-occupancy trade-off the paper's Table 9 measures.
AXIS_CANDIDATES: Dict[str, Tuple[int, ...]] = {
    "c_tile": (128, 256, 512),
    "row_tile": (8, 16),
    "slot_tile": (8, 16, 32),
    "seg_tile": (8, 16, 32),
}


def tile_axes(executor: str) -> Tuple[str, ...]:
    """Launch-parameter axes executor ``executor`` exposes (may be empty)."""
    return TUNABLE_TILES.get(executor, ())


def current_params(executor: str, config) -> Dict[str, int]:
    """The config's own values for the executor's tile axes."""
    return {ax: int(getattr(config, ax)) for ax in tile_axes(executor)}


def search_space(executor: str, config, *,
                 budget: int | None = None) -> List[dict]:
    """Candidate list: ``{"params": {axis: value}, "compute_dtype": str}``.

    The first candidate is always the current config under its requested (or
    fp32-first, when "auto") dtype — truncating to ``budget`` can therefore
    never drop the default configuration, only exotic corners of the grid.
    """
    axes = tile_axes(executor)
    cur = current_params(executor, config)
    requested = getattr(config, "compute_dtype", "fp32")
    dtypes = COMPUTE_DTYPES if requested == "auto" else (requested,)

    per_axis = [sorted(set(AXIS_CANDIDATES[ax]) | {cur[ax]}) for ax in axes]
    tiles = [dict(zip(axes, combo))
             for combo in itertools.product(*per_axis)] if axes else [{}]
    # current-config-first ordering so budget truncation keeps the default
    tiles.sort(key=lambda t: (t != cur, tuple(sorted(t.items()))))

    out: List[dict] = []
    for dt in dtypes:              # default tiles under every dtype first
        out.append(dict(params=dict(cur), compute_dtype=dt))
    for t in tiles:
        for dt in dtypes:
            cand = dict(params=dict(t), compute_dtype=dt)
            if cand not in out:
                out.append(cand)
    if budget is not None and budget > 0:
        out = out[:max(budget, len(dtypes))]
    return out
