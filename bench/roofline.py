"""Peak rates of each chip, and the least time a piece of work needs on it.

The peaks are keyed by ``device_kind`` as JAX reports it. A kind that is not
in the table is an error: a number divided by a guessed peak is no number.

Source of the TPU v5e row: Google Cloud documentation, "TPU v5e" (system
architecture): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM2 at 819 GB/s,
1,600 Gbit/s of inter-chip interconnect per chip.

The operations and bytes a call needs depend on the architecture, and are
counted by the configuration's family (``bench/families/<family>.py``).
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s, dense bf16 matmul
    int8_ops: float          # OP/s
    hbm_bytes: float         # bytes/s
    hbm_capacity: float      # bytes
    ici_bytes: float         # bytes/s per chip, all links
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes=819e9,
        hbm_capacity=16 * 2 ** 30, ici_bytes=1600e9 / 8,
        source='Google Cloud documentation, "TPU v5e"'),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None


def least_time(flops: float, bytes_: float, peaks: Peaks):
    """(seconds, bound) of the roofline: the larger of compute and memory
    time, and which of the two it is."""
    tc, tm = flops / peaks.bf16_flops, bytes_ / peaks.hbm_bytes
    return (tc, "compute") if tc >= tm else (tm, "memory")

