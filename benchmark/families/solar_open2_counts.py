"""Operations and bytes from shapes for the solar_open2 family: the benchmark's own
count of what its channel-gated delta rule (both forms) and its routed FFN (both
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

- the rule in a decode STEP reads a live slot's float32 state from HBM once a `kda`
  layer and writes it once: 2 x 4 x heads x dk x dv bytes (8.39 MB at the published
  sizes), against 7 x heads x dk x dv operations (the decay, the probe's multiply
  and add, the write's, the read-out's): the HBM bound. Once a step, NOT an eighth
  of it as families/olmo_hybrid_counts.py's `delta_decode_call`: 128 slots' state of
  one layer is 537 MB, so nothing of it can stay on the chip between the steps of
  a chunk;
- the rule in a prefill chunk is the BLOCKED form's products over the chunk's rows
  as the engine dispatched it (padded to their bucket, which the kernel computes
  too), in blocks of BLOCK positions, a head and row: the old state's probe and
  read-out and the state's update (2 dk dv each), the pairs' two sums over the
  key's channels on the causal half of a block (A and P: 2 dk each a pair, BLOCK / 2
  pairs a row), the solve's product and the read-out of the written rows (2 BLOCK
  dv, and its causal half). That is the sub-blocked form's count (each pair's sum
  over d once, as a product); the pairs taken one by one with an exponential each
  are not what is counted, nor the exponentials, nor the triangular solve's
  sequential rows on the vector unit, and the state's products run in float32 at
  full precision (several passes of the matrix unit) and count once: the share is
  low by construction. Bytes: q, k, v in and o out in the activations' type, the
  log-decay a channel in float32, the state once each way;
- the routed FFN, both phases, as families/exaone_moe_counts.py counts it for every
  routed family: a decode step reads the slabs of the held experts that some live
  slot's row chose, as the program COUNTED them on the device
  (`tony_serve_experts_touched_total`), each once; a prefill chunk 6 D F operations
  a row that lands on a held expert under even routing and every held expert's
  slabs once a layer.

No traffic between the chips that share a layer is counted: none is run.
"""

from __future__ import annotations

from families.exaone_moe_counts import (  # noqa: F401 - the routed FFN's count, by this family's sizes (every layer has one)
    expert_params, moe_decode_call, moe_decode_calls, moe_decode_operands, moe_prefill_call, moe_prefill_calls, moe_prefill_operands)
from families.exaone_moe_counts import routed_means as window_means  # noqa: F401 - this family's kernels need no mean but the routed FFN's

KDA, ATTENTION = "kda", "attention"
#: positions a block of the chunked form counted here (the program's own: tony_tpu/ops/kda.BLOCK)
BLOCK = 64


def n_of(s: dict, kind: str) -> int:
    return sum(1 for k in s["layer_types"] if k == kind)


def width(s: dict) -> int:
    return s["kda_heads"] * s["kda_head_dim"]


def mixer_params(s: dict, kind: str) -> int:
    d = s["d_model"]
    if kind == ATTENTION:
        q = s["heads"] * s["head_dim"]
        return d * (q + 2 * s["kv_heads"] * s["head_dim"]) + d * q + q * d          # q k v, the output gate, W_o
    w, r, h = width(s), s["gate_rank"], s["kda_heads"]
    # q k v and W_o, the two low-rank gates, beta, the convolution's taps, and three vectors: dt_bias, A_log, the head norm's weight
    return d * 3 * w + w * d + 2 * (d * r + r * w) + d * h + s["conv_taps"] * 3 * w + w + h + s["kda_head_dim"]


def layer_params(s: dict, kind: str) -> int:
    """What this replica holds of one layer (the two block norms and the choosing bias excluded): mixer, shared FFN, router, held experts."""
    return mixer_params(s, kind) + 3 * s["d_model"] * s["d_shared"] + s["d_model"] * s["num_experts"] + s["held"][1] * expert_params(s)


def total_params(s: dict) -> int:
    """Parameters this replica holds (norms and biases excluded): the layers, the embedding and the untied head."""
    return sum(layer_params(s, kind) for kind in s["layer_types"]) + 2 * s["vocab"] * s["d_model"]


def train_flops_per_token(s: dict, seq: int) -> float:
    """Forward + backward a token on THIS share: no training cell reads it (the rule has no backward on the tree)."""
    per_tok = s["top_k"] * s["held"][1] / s["num_experts"]
    matmul = 2 * (sum(mixer_params(s, k) + 3 * s["d_model"] * s["d_shared"] + per_tok * expert_params(s) for k in s["layer_types"])
                  + s["d_model"] * s["vocab"])
    attn = n_of(s, ATTENTION) * 4 * s["heads"] * s["head_dim"] * (seq + 1) / 2
    return 3.0 * (matmul + attn + n_of(s, KDA) * step_ops(s))


def step_ops(s: dict) -> int:
    """Operations of one position of the rule, all heads of a layer: decay, probe (multiply, add), write, read."""
    return 7 * s["kda_heads"] * s["kda_head_dim"] ** 2


def state_bytes(s: dict) -> int:
    """A slot's float32 state, one `kda` layer."""
    return 4 * s["kda_heads"] * s["kda_head_dim"] ** 2


# -- the rule in a decode step: every live slot's state, read and written once a `kda` layer -------

def kda_decode_operands(s: dict, engine: dict) -> str:
    """The state of all slots, one layer, as the program lays it out: [slots, H, dk, dv] float32."""
    return rf"f32\[{engine['slots']},{s['kda_heads']},{s['kda_head_dim']},{s['kda_head_dim']}\]"


def kda_decode_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    """One decode step, every `kda` layer: the live slots' operations, and their state once each way."""
    live = means["live_slots"] * n_of(s, KDA)
    return float(step_ops(s) * live), 2.0 * state_bytes(s) * live


def kda_decode_calls(s: dict, engine: dict) -> tuple[str, int]:
    return "decode_steps", engine.get("decode_chunk", 8)


# -- the rule in a prefill chunk: the blocked form -------------------------------------------------

def kda_prefill_operands(s: dict, engine: dict) -> str:
    """A request's state, one layer, as the call takes and returns it: [H, dk, dv] float32 (not the slots')."""
    return rf"f32\[{s['kda_heads']},{s['kda_head_dim']},{s['kda_head_dim']}\]"


def kda_prefill_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    """One prefill chunk, every `kda` layer. A row and head: the state's probe, read-out and update (2 dk dv
    each); A and P on the causal half (2 x 2 dk x BLOCK / 2); W = X R (2 BLOCK dv) and P W on its causal half."""
    rows, h, d = means["prefill_rows_per_chunk"], s["kda_heads"], s["kda_head_dim"]
    ops = rows * h * (6 * d * d + 2 * d * BLOCK + 3 * BLOCK * d)
    itemsize = 2                                                              # the activations' type: bfloat16
    nbytes = rows * width(s) * (4 * itemsize + 4) + 2.0 * state_bytes(s)
    return float(ops * n_of(s, KDA)), float(nbytes * n_of(s, KDA))


def kda_prefill_calls(s: dict, engine: dict) -> tuple[str, int]:
    return "prefill_chunk", 1
