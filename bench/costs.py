"""Operations and bytes of the kernels the benchmark reads, from shapes,
and their share of the chip's roofline.

Bytes count what the kernel must move: each operand read once and its
result written once at the width the work needs (a keep mask is one byte
per entry), never a re-read or a wider intermediate the implementation
chose.  A share above 100% therefore means a wrong count.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
F32 = 4


def peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    try:
        return table[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {PEAKS.name}; known: "
                       f"{sorted(table)}") from None


def xtv(N: int, p: int) -> tuple[int, int]:
    """(flops, bytes) of out = X^T v with X (N, p) float32: one streaming
    pass over X, v read, out written."""
    return 2 * N * p, F32 * (N * p + N + p)


def dpc_screen(K: int, L: int, p: int) -> tuple[int, int]:
    """(flops, bytes) of the fused DPC rule ``C + r ||x|| >= 1`` on a
    (K, L, p) stack: C read, radii (K, L) and column norms (K, p) read, a
    one-byte keep mask (K, L, p) written."""
    return 3 * K * L * p, F32 * (K * L * p + K * L + K * p) + K * L * p


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> float:
    """Percent of the least time the chip could take for this work."""
    least = max(flops / peak["flops_per_s"],
                nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
