"""Roofline attribution: ledger counts x HLO costs x wall time.

The three observability sources each answer one question:

  * `serve.ledger` (device-resident counters) — how much of the dense work
    was INEFFECTUAL this dispatch (activation zeros, dead k-blocks,
    effective-vs-dense FLOPs/bytes), measured in-graph on the step clock;
  * `analysis.hlo.analyze` (static, loop-aware) — what the compiled
    program MUST execute per dispatch, independent of data;
  * a wall time over the same window — how long the work actually TOOK.

This module joins them. `roofline_point` classifies one (flops, bytes,
wall) triple against a machine roof; `replica_roofline` places BOTH the
dense point (what the hardware ran) and the effective point (what a
sparsity-aware kernel would need to run) on the same roof, once per
replica from drained totals — the gap between them is the activation-skip
opportunity the ledger exists to measure. Per-op device time comes from
a profiler capture, not from host-observed dispatch walls: the chip
benchmark reads it by the program's named scopes (`bench/opscope.py`).

No third-party deps; everything is plain dict/float so results serialize
straight into bench JSON and qor gating.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Machine roof of one chip, with where its numbers come from."""

    peak_flops: float              # flop/s
    peak_bw: float                 # HBM bytes/s
    source: str = ""

    @property
    def ridge(self) -> float:
        """Arithmetic intensity (flop/byte) where the roofs intersect."""
        return self.peak_flops / self.peak_bw


# Published per-chip peaks keyed by jax `Device.device_kind`. A device not
# in the table is an error, never a default: a roofline share against
# guessed peaks would read as a measurement.
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        peak_flops=197e12, peak_bw=819e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip"),
}


def peaks_for(device_kind: str) -> Peaks:
    """The published roof of `device_kind`; KeyError if it has none."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def roofline_point(flops: float, bytes_: float, wall_s: float,
                   peaks: Peaks) -> Dict[str, float]:
    """Classify one workload sample against the roof.

    Returns intensity (flop/byte), achieved flop/s and bytes/s, the roof
    at that intensity, which resource bounds it, and utilization =
    achieved / roof (in the bounding resource).
    """
    bytes_ = max(float(bytes_), 1.0)
    wall_s = max(float(wall_s), 1e-12)
    flops = max(float(flops), 0.0)
    intensity = flops / bytes_
    achieved_flops = flops / wall_s
    achieved_bw = bytes_ / wall_s
    roof = min(peaks.peak_flops, intensity * peaks.peak_bw)
    bound = "compute" if intensity >= peaks.ridge else "memory"
    if bound == "compute":
        utilization = achieved_flops / peaks.peak_flops
    else:
        utilization = achieved_bw / peaks.peak_bw
    return {
        "intensity": intensity,
        "achieved_flops": achieved_flops,
        "achieved_bw": achieved_bw,
        "roof_flops": roof,
        "bound": bound,
        "utilization": utilization,
    }


def replica_roofline(summary: Dict[str, Any], wall_s: float,
                     hlo_cost: Optional[Dict[str, Any]] = None,
                     n_dispatches: int = 1, *,
                     peaks: Peaks) -> Dict[str, Any]:
    """Whole-replica roofline from `LedgerSink.summary()` totals.

    summary: drained cumulative totals (flops_dense / flops_effective /
    bytes_dense / bytes_effective). wall_s: the replica's decode wall
    time over the same window (sum of dispatch durs, or bench wall).
    hlo_cost x n_dispatches supplies static bytes when the per-probe byte
    model is not what you want on the memory axis.
    """
    fd = float(summary.get("flops_dense", 0.0))
    fe = float(summary.get("flops_effective", 0.0))
    if hlo_cost is not None:
        bd = float(hlo_cost["bytes"]) * max(1, int(n_dispatches))
        be = bd * (fe / fd if fd > 0 else 1.0)
    else:
        bd = float(summary.get("bytes_dense", 0.0))
        be = float(summary.get("bytes_effective", 0.0))
    out = {
        "wall_s": float(wall_s),
        "flops_dense": fd,
        "flops_effective": fe,
        "bytes_dense": bd,
        "bytes_effective": be,
        "effective_flop_fraction": fe / fd if fd > 0 else 1.0,
        "dense": roofline_point(fd, bd, wall_s, peaks),
        "effective": roofline_point(fe, be, wall_s, peaks),
    }
    # upper bound on an activation-skip kernel's speedup: the work ratio in
    # whichever resource bounds the dense point on this roof
    if out["dense"]["bound"] == "compute":
        out["skip_speedup_bound"] = fd / fe if fe > 0 else 1.0
    else:
        out["skip_speedup_bound"] = bd / be if be > 0 else 1.0
    return out
