"""Throughput/MFU accounting (SURVEY.md §5.5 rebuild duty).

Peak-FLOPs table for MFU is per-chip bf16 dense compute; MFU =
model_flops_per_token * tokens_per_sec / (peak * chips). The reference
published no throughput numbers — these are the numbers this framework
measures about itself. MFU is a device metric: a device that is not in the
table (the CPU included) has none.
"""

from __future__ import annotations

import jax

# bf16 dense peak FLOP/s per chip, keyed by a substring of jax's device_kind
# (Google Cloud TPU documentation, the system-architecture page of each
# generation)
PEAK_FLOPS = {
    "v5e": 197e12,
    "v5 lite": 197e12,   # PJRT device_kind spelling on v5e
    "v6e": 918e12,
    "v5p": 459e12,
    "v4": 275e12,
}


def detect_peak_flops(device=None) -> float:
    """The chip's published bf16 peak. An unknown device is an error, not a
    default: a nominal peak would print an MFU that means nothing."""
    d = device or jax.devices()[0]
    kind = d.device_kind.lower()
    for name, peak in PEAK_FLOPS.items():
        if name in kind:
            return peak
    raise ValueError(
        f"no peak FLOP/s known for device_kind {d.device_kind!r}: add it to "
        "train/metrics.py PEAK_FLOPS with its source (on the CPU, pass "
        "peak_flops=None: no MFU is reported)")


def flops_per_token_for_batch(model_cfg, batch: dict, seq_len: int) -> int:
    """The model's flops/token ON THIS BATCH LAYOUT — the one place that
    knows gathered-MLM batches (``masked_pos``) only project the masked
    fraction through the vocab head. Both bench.py and the training loop
    derive their MFU basis here so they cannot drift."""
    if "masked_pos" in batch:
        return model_cfg.flops_per_token(batch["masked_pos"].shape[1] / seq_len)
    return model_cfg.flops_per_token()


def transformer_flops_per_token(
    n_params: int, n_layers: int, d_model: int, seq_len: int, *, training: bool = True
) -> int:
    """6N (fwd+bwd) + causal-attention term 12·L·D·T (PaLM appendix formula)."""
    mult = 6 if training else 2
    attn = (12 if training else 4) * n_layers * d_model * seq_len // 2  # causal halves it
    return mult * n_params + attn
