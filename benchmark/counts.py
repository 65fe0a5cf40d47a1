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


def laid_out_widths(numbers: int, lane: int = 128) -> str:
    """A pattern over the last dimension of a cached row in a trace's HLO line: any width from the
    `numbers` a row holds up to those in whole lanes. A pool may lay a row out with filling or without;
    a count finds its call at either and counts the numbers' bytes at both, so the share of a pool
    that lost its filling RISES by the bytes it no longer reads (PERF.md section 3, PR 64)."""
    return "(?:" + "|".join(str(w) for w in range(numbers, -(-numbers // lane) * lane + 1)) + ")"


def roofline_seconds(ops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(ops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def peak_for(kind: str, peaks: dict) -> dict:
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in peaks.json: add it with its source")
    return peaks[kind]
