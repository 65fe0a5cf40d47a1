"""Operations and bytes from shapes for the llama and mixtral families: the
benchmark's own count.

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

from counts import causal_pairs


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


def flash_operands(s: dict, seq: int) -> str:
    """A pattern over a trace event's HLO line that only flash's calls match:
    it reads and writes [rows x heads, positions, head size] in the model's type."""
    return rf"\b\w+\[\d+,{seq},{s['head_dim']}\]"


def flash_layer_step(s: dict, rows: int, seq: int) -> list[tuple[float, float]]:
    """The flash calls one layer makes in one training step, as the step runs them: the forward ONCE
    (since PR 47 the loop saves `flash_o` / `flash_lse`, so no backward runs it again) and ONE backward of
    five products (since PR 52): seven products of a pair where the count held nine until PR 64. The
    reader (readers/kernel_roofline.py) divides the calls it finds in the trace by the length of this
    list, so it has one entry a call the step makes and no more: a step under a rung that runs the
    forward again makes three calls a layer for these two and would read 3/2 high until this list
    follows the rung (PERF.md section 7)."""
    return [flash_call(s, rows, seq, False), flash_call(s, rows, seq, True)]
