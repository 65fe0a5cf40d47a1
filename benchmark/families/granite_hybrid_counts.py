"""Operations and bytes from shapes for the granite_hybrid family: the benchmark's own
count of what its state-space recurrence (both forms) and its routed FFN (both
phases) need, and how to find each one's operations in a trace.

Each kernel `<k>` has `<k>_operands(sizes, engine)` (a pattern over a trace event's
HLO line that only this kernel's operations match), `<k>_call(sizes, engine, means)`
((operations, bytes) of ONE unit of its work: a decode step or a prefill chunk, all
layers that have it) and `<k>_calls(sizes, engine)` ((the jitted program whose
executions count those units, units an execution)); `window_means(delta, engine)`
turns the changes of the replica's counters over the window into the means
(readers/family_roofline.py).

Counted is THE MATHEMATICS, each array once, so that a later kernel of another
shape is read against the same work and a share cannot pass 100:

- the recurrence in a decode STEP reads a live slot's float32 state from HBM once a
  `mamba` layer and writes it once: 2 x 4 x heads x head_dim x state bytes (8.39 MB
  at the published sizes), against 5 x heads x head_dim x state operations (decay,
  the outer product's multiply and add, the read-out's multiply and add): the HBM
  bound. Once a step, NOT an eighth of it as families/olmo_hybrid_counts.py's
  `delta_decode_call`: 64 slots' state of one layer is 268 MB and of nine 2.4 GB,
  so nothing of it can stay on the chip between the steps of a chunk;
- the recurrence in a prefill chunk is the blocked form's products over the
  chunk's rows as the engine dispatched it (padded to their bucket, which the
  kernel computes too), in blocks of BLOCK positions: `C B^T` once a block (it
  does not know the head), then a head the masked product, the old state's
  read-out and the state's update; and the rows in and out in the activations'
  type, B and C once, the state once each way. The state's two products run in
  float32 at full precision (several passes of the matrix unit) and are counted
  as one: the share is low by construction;
- the routed FFN, both phases, as families/exaone_moe_counts.py counts it for every
  routed family (this family's was the first count to read the slabs from the
  program's `tony_serve_experts_touched_total`; since PR 64 that count lives there).

No traffic between the chips that share a layer is counted: none is run.
"""

from __future__ import annotations

from families.exaone_moe_counts import (  # noqa: F401 - the routed FFN's count, by this family's sizes (every layer has one)
    expert_params, moe_decode_call, moe_decode_calls, moe_decode_operands, moe_prefill_call, moe_prefill_calls, moe_prefill_operands)
from families.exaone_moe_counts import routed_means as window_means  # noqa: F401 - this family's kernels need no mean but the routed FFN's

MAMBA, ATTENTION = "mamba", "attention"
#: positions a block of the chunked form counted here (the program's own: tony_tpu/ops/ssd.BLOCK)
BLOCK = 128


def n_of(s: dict, kind: str) -> int:
    return sum(1 for k in s["layer_types"] if k == kind)


def mixer_params(s: dict, kind: str) -> int:
    d = s["d_model"]
    if kind == ATTENTION:
        return d * (s["heads"] + 2 * s["kv_heads"]) * s["head_dim"] + s["heads"] * s["head_dim"] * d
    h, i, n, taps = s["ssm_heads"], s["ssm_heads"] * s["ssm_head_dim"], s["ssm_state"], s["conv_taps"]
    # in_proj (z | x B C | dt), out_proj, the convolution's taps, and five vectors: its bias, A_log, dt_bias, D, the gated norm's weight
    return d * (2 * i + 2 * n + h) + i * d + taps * (i + 2 * n) + (i + 2 * n) + 3 * h + i


def layer_params(s: dict, kind: str) -> int:
    """What this replica holds of one layer (the two block norms excluded): mixer, shared FFN, router, held experts."""
    return mixer_params(s, kind) + 3 * s["d_model"] * s["d_shared"] + s["d_model"] * s["num_experts"] + s["held"][1] * expert_params(s)


def total_params(s: dict) -> int:
    """Parameters this replica holds (the block norms and the final one excluded; the embedding once: the head is tied)."""
    return sum(layer_params(s, kind) for kind in s["layer_types"]) + s["vocab"] * s["d_model"]


def train_flops_per_token(s: dict, seq: int) -> float:
    """Forward + backward a token on THIS share: no training cell reads it (the recurrence has no backward on the tree)."""
    per_tok = s["top_k"] * s["held"][1] / s["num_experts"]
    matmul = 2 * (sum(mixer_params(s, k) + 3 * s["d_model"] * s["d_shared"] + per_tok * expert_params(s) for k in s["layer_types"])
                  + s["d_model"] * s["vocab"])
    attn = n_of(s, ATTENTION) * 4 * s["heads"] * s["head_dim"] * (seq + 1) / 2
    return 3.0 * (matmul + attn + n_of(s, MAMBA) * step_ops(s))


def step_ops(s: dict) -> int:
    """Operations of one position of the recurrence, all heads of a layer: decay, write (multiply, add), read (multiply, add)."""
    return 5 * s["ssm_heads"] * s["ssm_head_dim"] * s["ssm_state"]


def state_bytes(s: dict) -> int:
    """A slot's float32 state, one `mamba` layer."""
    return 4 * s["ssm_heads"] * s["ssm_head_dim"] * s["ssm_state"]


# -- the recurrence in a decode step: every live slot's state, read and written once a `mamba` layer --

def ssd_decode_operands(s: dict, engine: dict) -> str:
    """The state of all slots, one layer, as the program lays it out: [slots, N, H x P] float32."""
    return rf"f32\[{engine['slots']},{s['ssm_state']},{s['ssm_heads'] * s['ssm_head_dim']}\]"


def ssd_decode_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    """One decode step, every `mamba` layer: the live slots' operations, and their state once each way."""
    live = means["live_slots"] * n_of(s, MAMBA)
    return float(step_ops(s) * live), 2.0 * state_bytes(s) * live


def ssd_decode_calls(s: dict, engine: dict) -> tuple[str, int]:
    return "decode_steps", engine.get("decode_chunk", 8)


# -- the recurrence in a prefill chunk: the blocked form ---------------------------------------------

def ssd_prefill_operands(s: dict, engine: dict) -> str:
    """A request's state, one layer, as the call takes and returns it: [N, H x P] float32."""
    return rf"f32\[{s['ssm_state']},{s['ssm_heads'] * s['ssm_head_dim']}\]"


def ssd_prefill_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    """One prefill chunk, every `mamba` layer. A row: `C B^T` against its block (2 BLOCK N, once for all
    heads), then a head the masked product (2 BLOCK P), the read-out and the update (2 N P each)."""
    rows, h, p, n = means["prefill_rows_per_chunk"], s["ssm_heads"], s["ssm_head_dim"], s["ssm_state"]
    ops = rows * (2 * BLOCK * n + h * (2 * BLOCK * p + 4 * n * p))
    nbytes = 2.0 * rows * (2 * h * p + 2 * n) + 2.0 * state_bytes(s)
    return float(ops * n_of(s, MAMBA)), nbytes * n_of(s, MAMBA)


def ssd_prefill_calls(s: dict, engine: dict) -> tuple[str, int]:
    return "prefill_chunk", 1
