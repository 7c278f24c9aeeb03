"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), from the peaks of the chip the
program is compiled for (:data:`PEAKS`, keyed by ``device_kind``):

  compute    = HLO_FLOPs_global / (chips * 197e12 bf16 FLOP/s)
  memory     = HLO_bytes_global / (chips * 819e9 B/s HBM)
  collective = collective_bytes_per_chip / 50e9 B/s per ICI link

`compiled.cost_analysis()` reports per-partition (per-chip) flops/bytes under
SPMD, so global = per_chip * chips.  Collective bytes are NOT in
cost_analysis: we parse the post-SPMD `compiled.as_text()` and sum data moved
per collective with ring-algorithm factors:

  all-gather:          result_bytes * (g-1)/g
  reduce-scatter:      result_bytes * (g-1)        (operand = result * g)
  all-reduce:          2 * size_bytes * (g-1)/g
  all-to-all:          size_bytes * (g-1)/g
  collective-permute:  size_bytes

where g is the replica-group size parsed from the instruction.  This is the
standard ring/bidirectional model; absolute numbers are approximations, the
*relative* movement across perf iterations is what the hillclimb optimizes.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Any, Dict, Optional

#: ``jax.Device.device_kind`` of a TPU v5e chip.
V5E = "TPU v5 lite"

#: Published per-chip peaks keyed by ``device_kind``.  TPU v5e: Google Cloud
#: documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
#: 1,600 Gbit/s of interchip interconnect over 4 links (50 GB/s per link).
PEAKS: Dict[str, Dict[str, float]] = {
    V5E: dict(peak_flops=197e12, hbm_bw=819e9, link_bw=50e9),
}


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of ``device_kind``; a kind not in :data:`PEAKS` has
    no roofline, so looking it up raises (never a default chip)."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|((?:[a-z0-9_]+)\[[0-9,]*\][^ ]*))\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.IGNORECASE)
_SHAPE_RE = re.compile(r"([a-z0-9_]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^}]*\})")
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        return max(1, m.group(1).count(",") + 1)
    return default


def collective_bytes(hlo_text: str, n_chips: int) -> Dict[str, float]:
    """Per-chip bytes moved over ICI, by collective kind."""
    out: Dict[str, float] = {"all-gather": 0.0, "all-reduce": 0.0,
                             "reduce-scatter": 0.0, "all-to-all": 0.0,
                             "collective-permute": 0.0}
    counts: Dict[str, int] = {k: 0 for k in out}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        type_str = m.group(1) or m.group(2)
        kind = m.group(3).lower()
        size = _shape_bytes(type_str)
        g = _group_size(line, n_chips)
        if g <= 1:
            continue
        if kind == "all-gather":
            moved = size * (g - 1) / g
        elif kind == "reduce-scatter":
            moved = size * (g - 1)
        elif kind == "all-reduce":
            moved = 2 * size * (g - 1) / g
        elif kind == "all-to-all":
            moved = size * (g - 1) / g
        else:  # collective-permute
            moved = size
        out[kind] += moved
        counts[kind] += 1
    out["total"] = sum(out.values())
    out["counts"] = counts  # type: ignore[assignment]
    return out


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    flops_per_chip: float
    bytes_per_chip: float
    coll_bytes_per_chip: float
    model_flops: float
    useful_ratio: float
    dominant: str
    bound_s: float

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def roofline(flops_per_chip: float, bytes_per_chip: float,
             coll_bytes_per_chip: float, n_chips: int,
             model_flops_global: float, *, device_kind: str) -> Roofline:
    hw = peaks(device_kind)
    compute_s = flops_per_chip / hw["peak_flops"]
    memory_s = bytes_per_chip / hw["hbm_bw"]
    collective_s = coll_bytes_per_chip / hw["link_bw"]
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    hlo_global = flops_per_chip * n_chips
    useful = model_flops_global / hlo_global if hlo_global else 0.0
    return Roofline(
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        flops_per_chip=flops_per_chip, bytes_per_chip=bytes_per_chip,
        coll_bytes_per_chip=coll_bytes_per_chip,
        model_flops=model_flops_global, useful_ratio=useful,
        dominant=dominant, bound_s=max(terms.values()))


def model_flops(cfg, shape_name: str, seq: int, batch: int, kind: str) -> float:
    """MODEL_FLOPS: 6*N*D train (fwd+bwd), 2*N_active*D inference."""
    n_active = cfg.active_param_count()
    if kind == "train":
        tokens = seq * batch
        return 6.0 * n_active * tokens
    if kind == "prefill":
        tokens = seq * batch
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * batch


def mfu_fraction(r: Roofline, n_chips: int, kind: str) -> float:
    """Achievable model-FLOPs utilization upper bound implied by the terms
    on a v5e: useful model flops / (chips * peak * bound-time)."""
    denom = n_chips * PEAKS[V5E]["peak_flops"] * max(r.bound_s, 1e-30)
    return r.model_flops / denom
