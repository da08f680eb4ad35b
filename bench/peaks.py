"""Published peaks of each chip, keyed by ``device_kind``, and the bytes
each data-plane kernel must move per call, counted from shapes alone (so
the count does not depend on how the kernel is built).

TPU v5e: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip).
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_s": 819e9, "bf16_flops": 197e12,
                    "int8_ops": 393e12, "ici_bytes_s": 1600e9 / 8},
}


def peak(kind, what):
    """A peak of ``kind``; a chip missing from the table is an error."""
    if kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {kind!r}; add it to bench/peaks.py")
    return PEAKS[kind][what]


def route_bytes(n):
    """Per packet: 16 B header and 4 B instance id in; member, node, lane
    and valid (4 x 4 B) out."""
    return n * (16 + 4 + 16)


def plan_bytes(n, n_members):
    """Per packet: 4 B node in, 4 B position out; 4 B per member total."""
    return n * 8 + n_members * 4


def seg_masks_bytes(n):
    """Per sorted row: valid, event high and low, DAQ and segment (5 x 4 B)
    in; new-group and duplicate flags (2 x 4 B) out."""
    return n * 28
