"""Roofline extraction from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), all in seconds (TPU v5e constants):

  compute    = HLO_FLOPs_per_device / 197e12          (bf16 MXU peak)
  memory     = HLO_bytes_per_device / 819e9           (HBM bandwidth)
  collective = Σ_ops bytes·factor / 50e9              (per-link ICI)

FLOPs/bytes come from compiled.cost_analysis() of the *partitioned*
module (i.e. per-device numbers). Collective bytes are parsed from the
post-SPMD HLO text; per-op wire factors use the ring-algorithm byte counts
with the op's replica-group size g:

  all-reduce      2·(g−1)/g · size     all-gather      (g−1)/g · size(out)
  reduce-scatter  (g−1)/g · size(in)   all-to-all      (g−1)/g · size
  collective-permute  1 · size

MODEL_FLOPS uses 6·N·D (train) / 2·N·D (forward-only), N = active params.
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Optional

import numpy as np

PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # B/s / chip
ICI_BW = 50e9  # B/s / link

# Per-chip (peak_flops, memory_bandwidth) envelopes for single-kernel
# bounds, keyed by `jax.Device.device_kind`. Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s).
# A device that is not in the table has no roofline: asking for one is an
# error, never a fallback to another chip's peaks.
KERNEL_PEAKS = {
    "TPU v5 lite": (PEAK_FLOPS, HBM_BW),
}


def kernel_peaks(device_kind: str) -> tuple:
    """(peak FLOP/s, peak bytes/s) of one chip of ``device_kind``."""
    try:
        return KERNEL_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(KERNEL_PEAKS)}") from None


def kernel_bound_s(flops: float, bytes_accessed: float,
                   device_kind: str) -> float:
    """Roofline lower bound for one kernel launch on one chip of
    ``device_kind``: max(compute-limited, memory-limited) seconds."""
    pf, pb = kernel_peaks(device_kind)
    return max(flops / pf, bytes_accessed / pb)


def achieved_fraction(flops: float, bytes_accessed: float, seconds: float,
                      device_kind: str) -> float:
    """bound/measured — 1.0 means the launch hit the peak model; the
    autotuner records this per (op, shape-bucket) candidate."""
    if seconds <= 0.0:
        return 0.0
    return kernel_bound_s(flops, bytes_accessed, device_kind) / seconds

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(",
)
_SHAPE_RE = re.compile(r"(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64|c64|c128)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^}]*\})")
_GROUPS_ALT_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    counts: dict
    bytes_by_op: dict
    wire_bytes: float  # factor-adjusted bytes on the wire per device


def parse_collectives(hlo_text: str) -> CollectiveStats:
    counts: dict = {}
    raw: dict = {}
    wire = 0.0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        type_str, op, phase = m.group(1), m.group(2), m.group(3)
        if phase == "-done":  # avoid double counting start/done pairs
            continue
        size = _shape_bytes(type_str)
        g = _group_size(line)
        if op == "all-reduce":
            factor = 2.0 * (g - 1) / g
        elif op in ("all-gather", "reduce-scatter", "all-to-all"):
            factor = (g - 1) / g
        else:  # collective-permute
            factor = 1.0
        counts[op] = counts.get(op, 0) + 1
        raw[op] = raw.get(op, 0) + size
        wire += size * factor
    return CollectiveStats(counts=counts, bytes_by_op=raw, wire_bytes=wire)


def _group_size(line: str) -> int:
    m = _GROUPS_ALT_RE.search(line)
    if m:
        return max(int(m.group(2)), 2)
    m = _GROUPS_RE.search(line)
    if m:
        inner = m.group(1).strip("{}")
        n = len([x for x in inner.split(",") if x.strip() != ""])
        return max(n, 2)
    return 2


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    collective_wire_bytes: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float
    useful_ratio: float  # MODEL_FLOPS / (HLO_FLOPs × chips)
    collectives: dict
    memory_analysis: Optional[str] = None

    def to_dict(self):
        return dataclasses.asdict(self)


def descanned_totals(cost1, coll1, cost2, coll2, n_layers: int):
    """Undo cost_analysis's count-the-while-body-once behaviour.

    With layer-scan unroll u, every per-layer quantity appears u times:
    m(u) = a + u·b, so total = a + L·b = m1 + (L-1)·(m2-m1). Negative
    deltas (CSE noise) clamp to zero, leaving m1 as a lower bound.
    """
    def solve(m1, m2):
        delta = max(m2 - m1, 0.0)
        return m1 + (n_layers - 1) * delta

    cost = dict(cost1)
    for key in ("flops", "bytes accessed"):
        cost[key] = solve(float(cost1.get(key, 0.0)), float(cost2.get(key, 0.0)))
    wire = solve(coll1.wire_bytes, coll2.wire_bytes)
    return cost, wire


def build_roofline(
    *,
    arch: str,
    shape: str,
    mesh_desc: str,
    chips: int,
    cost: dict,
    model_flops: float,
    hlo_text: Optional[str] = None,
    wire_bytes: Optional[float] = None,
    collective_counts: Optional[dict] = None,
    memory_analysis: Optional[str] = None,
) -> Roofline:
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    if wire_bytes is None:
        coll = parse_collectives(hlo_text or "")
        wire_bytes = coll.wire_bytes
        collective_counts = coll.counts
    coll = CollectiveStats(
        counts=collective_counts or {}, bytes_by_op={}, wire_bytes=wire_bytes
    )
    compute_s = flops / PEAK_FLOPS
    memory_s = byts / HBM_BW
    collective_s = coll.wire_bytes / ICI_BW
    terms = {
        "compute": compute_s,
        "memory": memory_s,
        "collective": collective_s,
    }
    bottleneck = max(terms, key=terms.get)
    total_hlo_flops = flops * chips
    useful = model_flops / total_hlo_flops if total_hlo_flops else 0.0
    return Roofline(
        arch=arch,
        shape=shape,
        mesh=mesh_desc,
        chips=chips,
        flops_per_device=flops,
        bytes_per_device=byts,
        collective_wire_bytes=coll.wire_bytes,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        bottleneck=bottleneck,
        model_flops=model_flops,
        useful_ratio=useful,
        collectives={"counts": coll.counts, "bytes": coll.bytes_by_op},
        memory_analysis=memory_analysis,
    )


def model_flops_for_cell(cell, n_params_active: int) -> float:
    """6·N·D for train, 2·N·D for prefill, 2·N·B (+ attention KV read
    flops) for one decode step."""
    if cell.kind == "train":
        return 6.0 * n_params_active * cell.batch * cell.seq
    if cell.kind == "prefill":
        return 2.0 * n_params_active * cell.batch * cell.seq
    # decode: one token per request
    flops = 2.0 * n_params_active * cell.batch
    cfg = cell.cfg
    if cfg.n_heads:  # attention reads the KV cache: 2·2·S·H·hd per layer
        windows = cfg.layer_windows()
        for w in windows:
            s_eff = cell.seq if w == 0 else min(w, cell.seq)
            flops += 4.0 * cell.batch * s_eff * cfg.n_heads * cfg.head_dim_
    return flops
