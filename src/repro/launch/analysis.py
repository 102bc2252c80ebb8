"""Compiled-artifact analysis: collective bytes + roofline terms.

The roofline (EXPERIMENTS.md §Roofline) is derived from a compiled
artifact, not from wall time, against the peaks of the target chip
(``PEAKS``, keyed by ``device_kind``):

  compute term    = HLO_FLOPs   / peak bf16 FLOP/s
  memory term     = HLO_bytes   / peak HBM bytes/s
  collective term = coll_bytes  / ICI bytes/s per link

``cost_analysis()`` reports the *per-partition* (per-device) module under
GSPMD, so its flops/bytes are NOT divided by the chip count again; the
collective bytes are parsed per-partition from the HLO text, so they are
likewise per-chip. (Verified empirically in tests/test_analysis.py.)

As of PR 8 the HLO-text parsing itself lives in
``repro.analysis.hlolint.hlo`` — the single parser shared by this
roofline surface and the hlolint contract checks — and is re-exported
here unchanged for existing callers (``benchmarks/roofline.py``,
``tests/test_analysis.py``).
"""
from __future__ import annotations

from dataclasses import asdict, dataclass
from typing import Dict, NamedTuple

# Shared HLO parsing (moved to repro.analysis.hlolint.hlo in PR 8;
# re-exported here for back-compat — the private names too, since the
# parser tests exercise them).
from repro.analysis.hlolint.hlo import (  # noqa: F401
    _COLLECTIVE_LINE_RE,
    _COLLECTIVES,
    _DTYPE_BYTES,
    _TYPE_RE,
    _type_bytes,
    collective_bytes,
    collective_result_shapes,
    scan_trip_counts,
)

class DevicePeaks(NamedTuple):
    flops_bf16: float           # FLOP/s
    hbm_bw: float               # B/s
    ici_bw: float               # B/s per link


#: Per-chip peaks keyed by ``jax.Device.device_kind``. TPU v5e: Google
#: Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM,
#: 1,600 Gbit/s of interchip interconnect per chip over 4 links
#: (50 GB/s each).
PEAKS = {
    "TPU v5 lite": DevicePeaks(flops_bf16=197e12, hbm_bw=819e9,
                               ici_bw=50e9),
}

#: the chip the dry-run and the roofline probe target
TARGET_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> DevicePeaks:
    """The peaks of ``device_kind``; a device missing from ``PEAKS`` is
    an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peak rates for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    bottleneck: str = ""
    model_flops: float = 0.0
    useful_ratio: float = 0.0
    peak_memory_per_device: float = 0.0
    notes: str = ""
    device_kind: str = TARGET_DEVICE_KIND

    def finalize(self) -> "Roofline":
        pk = peaks(self.device_kind)
        self.compute_s = self.flops_per_device / pk.flops_bf16
        self.memory_s = self.bytes_per_device / pk.hbm_bw
        self.collective_s = self.collective_bytes_per_device / pk.ici_bw
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        self.bottleneck = max(terms, key=terms.get)
        total_hlo_flops = self.flops_per_device * self.chips
        self.useful_ratio = (self.model_flops / total_hlo_flops
                             if total_hlo_flops else 0.0)
        return self

    def to_dict(self) -> Dict:
        return asdict(self)


def model_flops_estimate(cfg, shape) -> float:
    """6·N·D (train) / 2·N·D (forward-only), N = active params (MoE)."""
    n = cfg.active_param_count() if cfg.is_moe else cfg.param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch
