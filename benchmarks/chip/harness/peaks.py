"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture
table): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per
chip.  A kind that is not in the table is an error, not a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float     # FLOP/s
    hbm_bytes_s: float    # B/s
    source: str


TABLE = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_s=819e9,
                         source="Google Cloud, 'TPU v5e' system "
                                "architecture"),
}


def for_kind(device_kind: str) -> Peaks:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(TABLE)}") from None


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: Peaks) -> tuple[float, str]:
    """(percent of the roofline bound reached, which bound applies).

    The least time the chip could take is the larger of operations over
    peak FLOP/s and bytes over peak bandwidth; the share is that time
    over the measured ``seconds``."""
    if seconds <= 0:
        raise ValueError("roofline share of a kernel that took no time")
    t_flops = flops / peaks.bf16_flops
    t_bytes = nbytes / peaks.hbm_bytes_s
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
