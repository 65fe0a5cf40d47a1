"""Operations and bytes from shapes: the benchmark's own count.

The program's `flops_per_token` counts the embedding table, `max_seq` instead
of the batch's length, and no sliding window; this one counts what the forward
and backward passes require and nothing else:

  - 6 x the parameters that multiply a token's activations: no embedding table
    (a gather), the head counted, for MoE the `top_k` active experts and the
    router only;
  - attention scores and values inside the causal band only;
  - recomputation (remat) is not counted.
"""

from __future__ import annotations


def layer_matmul_params(s: dict) -> int:
    """Parameters of one layer that a token multiplies (norms excluded)."""
    d, hd = s["d_model"], s["head_dim"]
    attn = d * s["heads"] * hd + 2 * d * s["kv_heads"] * hd + s["heads"] * hd * d
    ffn = 3 * d * s["d_ff"]
    if s["experts"]:
        return attn + s["top_k"] * ffn + d * s["experts"]
    return attn + ffn


def layer_params(s: dict) -> int:
    """All parameters of one layer, as stored (every expert, both norms)."""
    d, hd = s["d_model"], s["head_dim"]
    attn = d * s["heads"] * hd + 2 * d * s["kv_heads"] * hd + s["heads"] * hd * d
    ffn = 3 * d * s["d_ff"]
    if s["experts"]:
        ffn = s["experts"] * ffn + d * s["experts"]
    return attn + ffn + 2 * d


def total_params(s: dict) -> int:
    return s["layers"] * layer_params(s) + 2 * s["vocab"] * s["d_model"] + s["d_model"]


def causal_pairs(seq: int, window: int) -> int:
    """(query, key) pairs a causal mask of band `window` lets through, for one
    sequence of `seq` positions: query i sees keys max(0, i-window+1)..i."""
    if window <= 0 or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attn_flops_fwd(s: dict, seq: int) -> int:
    """QK^T and PV of one layer and one sequence, forward: 2 matmuls x 2
    FLOPs x head_dim for each (query, key) pair and query head."""
    return 4 * s["heads"] * s["head_dim"] * causal_pairs(seq, s["window"])


def train_flops_per_token(s: dict, seq: int) -> float:
    """Forward + backward (3 x forward) per trained token."""
    matmul = 2 * (s["layers"] * layer_matmul_params(s) + s["d_model"] * s["vocab"])
    attn = s["layers"] * attn_flops_fwd(s, seq) / seq
    return 3.0 * (matmul + attn)


def flash_call(s: dict, batch: int, seq: int, backward: bool) -> tuple[float, float]:
    """(operations, bytes) one flash-attention call needs at these shapes, for
    a whole batch and one layer. Forward: QK^T and PV inside the band.
    Backward (dq and dkv together): the scores are formed again and four more
    products taken (dV, dP, dQ, dK): 2.5 x the forward's operations.
    Bytes: q, k, v, o once each way in the activation type (2 bytes); the
    backward reads q, k, v, o, do and writes dq, dk, dv."""
    pairs = causal_pairs(seq, s["window"]) * batch
    fwd = 4 * s["heads"] * s["head_dim"] * pairs
    q_bytes = 2 * batch * seq * s["heads"] * s["head_dim"]
    kv_bytes = 2 * batch * seq * s["kv_heads"] * s["head_dim"]
    if not backward:
        return float(fwd), float(2 * q_bytes + 2 * kv_bytes)
    return 2.5 * fwd, float(4 * q_bytes + 4 * kv_bytes)


def roofline_seconds(ops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(ops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def peak_for(kind: str, peaks: dict) -> dict:
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in peaks.json: add it with its source")
    return peaks[kind]
