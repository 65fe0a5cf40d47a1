"""The counts every family shares: the pairs a causal band lets through, the
roofline, the table of peaks. A family's own operations and bytes are in the
file its family names (families/__init__.py).
"""

from __future__ import annotations


def causal_pairs(seq: int, window: int) -> int:
    """(query, key) pairs a causal mask of band `window` lets through, for one
    sequence of `seq` positions: query i sees keys max(0, i-window+1)..i."""
    if window <= 0 or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def roofline_seconds(ops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(ops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def peak_for(kind: str, peaks: dict) -> dict:
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in peaks.json: add it with its source")
    return peaks[kind]
