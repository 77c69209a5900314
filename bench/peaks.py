"""Published peaks of each chip the benchmark may run on, by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (Cloud TPU system
architecture): 197 TFLOP/s bf16, 394 TOP/s int8, 16 GiB of HBM2 at
819 GB/s, 1,600 Gbit/s (200 GB/s) of inter-chip interconnect per chip.
A kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float     # FLOP/s
    int8_ops: float       # OP/s
    hbm_bytes: float      # bytes of device memory
    hbm_bw: float         # bytes/s
    ici_bw: float         # bytes/s per chip


TABLE = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, int8_ops=394e12,
                         hbm_bytes=16 * 2 ** 30, hbm_bw=819e9,
                         ici_bw=200e9),
}


def lookup(device_kind: str) -> Peaks:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(TABLE)}") from None
