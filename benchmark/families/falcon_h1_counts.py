"""Operations and bytes from shapes for the falcon_h1 family: the benchmark's own count
of what its state-space recurrence and its attention (both in both phases, both in
EVERY layer) need, and how to find each one's operations in a trace.

Each kernel `<k>` has `<k>_operands(sizes, engine)` (a pattern over a trace event's
HLO line that only this kernel's operations match), `<k>_call(sizes, engine, means)`
((operations, bytes) of ONE unit of its work: a decode step or a prefill chunk, all
layers) and `<k>_calls(sizes, engine)` ((the jitted program whose executions count
those units, units an execution)); `window_means(delta, engine)` turns the changes
of the replica's counters over the window into the means (readers/family_roofline.py).

Counted is THE MATHEMATICS, each array once, so that a later kernel of another
shape is read against the same work and a share cannot pass 100:

- the recurrence in a decode STEP reads a live slot's float32 state from HBM once a
  layer and writes it once: 2 x 4 x heads x head_dim x state bytes (8.39 MB at the
  published sizes: 32 x 128 x 256), against 5 x heads x head_dim x state operations
  (decay, the outer product's multiply and add, the read-out's multiply and add): the
  HBM bound. Once a step: 48 slots' state of one layer is 201 MB and of nine 1.8 GB,
  so nothing of it can stay on the chip between the steps of a chunk;
- the recurrence in a prefill chunk is the blocked form's products over the chunk's
  rows as the engine dispatched it (padded to their bucket, which the kernel computes
  too), in blocks of BLOCK positions: `C B^T` once a block and B/C GROUP (it does not
  know the head), then a head the masked product, the old state's read-out and the
  state's update; and the rows in and out in the activations' type, every group's B
  and C once, the state once each way. The state's two products run in float32 at
  full precision (several passes of the matrix unit) and are counted as one: the
  share is low by construction;
- the attention in a decode step reads the keys and values of every live slot's
  context once a layer (the engine's context counter, whatever pages the kernel
  fetches whole): 2 x 2 x kv_heads x head_dim bytes a position and layer, 2,048 B, and
  4 x heads x head_dim operations: the HBM bound;
- the attention in a prefill chunk: the causal pairs its queries see (the host's
  count, `tony_serve_prefill_pairs_total`, a layer) x heads x 4 x head_dim operations,
  whatever the kernel computes under its mask on the diagonal tiles and for a chunk's
  padding rows, against the keys and values those pairs are with, read once, and the
  chunk's queries in and outputs out.
"""

from __future__ import annotations

#: positions a block of the chunked form counted here (the program's own: tony_tpu/ops/ssd.BLOCK)
BLOCK = 128


def inner(s: dict) -> int:
    return s["ssm_heads"] * s["ssm_head_dim"]


def layer_params(s: dict) -> int:
    """One layer (the two block norms excluded): attention, the state-space mixer, the FFN."""
    d, i, gn, h, taps = s["d_model"], inner(s), s["ssm_groups"] * s["ssm_state"], s["ssm_heads"], s["conv_taps"]
    attention = d * (s["heads"] + 2 * s["kv_heads"]) * s["head_dim"] + s["heads"] * s["head_dim"] * d
    # in_proj (z | x | B | C | dt), out_proj, the convolution's taps and bias, A_log, dt_bias, D, the gated norm's weight
    mixer = d * (2 * i + 2 * gn + h) + i * d + (taps + 1) * (i + 2 * gn) + 3 * h + i
    return attention + mixer + 3 * d * s["d_ff"]


def total_params(s: dict) -> int:
    """Parameters held (the norms excluded): the layers, the embedding's held rows and the head's."""
    return s["layers"] * layer_params(s) + 2 * s["vocab"] * s["d_model"]


def train_flops_per_token(s: dict, seq: int) -> float:
    """Forward + backward a token: no training cell reads it (the recurrence has no backward on the tree)."""
    matmul = 2 * (s["layers"] * layer_params(s) + s["d_model"] * s["vocab"])
    attn = s["layers"] * 4 * s["heads"] * s["head_dim"] * (seq + 1) / 2
    return 3.0 * (matmul + attn + s["layers"] * step_ops(s))


def step_ops(s: dict) -> int:
    """Operations of one position of the recurrence, all heads of a layer: decay, write (multiply, add), read (multiply, add)."""
    return 5 * inner(s) * s["ssm_state"]


def state_bytes(s: dict) -> int:
    """A slot's float32 state, one layer."""
    return 4 * inner(s) * s["ssm_state"]


def window_means(delta, engine: dict) -> dict | None:
    """`delta(name=..., where=...)`: the change of one of the replica's counters over the window."""
    chunks, slots = delta(name="tony_serve_engine_chunks_total"), delta(name="tony_serve_decode_slots_total")
    context = delta(name="tony_serve_context_tokens_total")
    p_tokens, p_chunks = delta(name="tony_serve_prefill_tokens_total"), delta(name="tony_serve_prefill_chunks_total")
    pairs = delta(name="tony_serve_prefill_pairs_total")
    if None in (chunks, slots, context, p_tokens, p_chunks, pairs) or not chunks or not slots:
        return None
    h = engine.get("decode_chunk", 8)
    return {"live_slots": slots / chunks, "context_per_slot": context / (slots * h),
            "prefill_rows_per_chunk": p_tokens / p_chunks if p_chunks else 0.0,
            "prefill_pairs_per_chunk": pairs / p_chunks if p_chunks else 0.0}


def _decode_calls(s: dict, engine: dict) -> tuple[str, int]:
    return "decode_steps", engine.get("decode_chunk", 8)


def _prefill_calls(s: dict, engine: dict) -> tuple[str, int]:
    return "prefill_chunk", 1


# -- the recurrence in a decode step: every live slot's state, read and written once a layer ----------

def ssd_decode_operands(s: dict, engine: dict) -> str:
    """The state of all slots, one layer, as the program lays it out: [slots, N, H x P] float32."""
    return rf"f32\[{engine['slots']},{s['ssm_state']},{inner(s)}\]"


def ssd_decode_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    """One decode step, every layer: the live slots' operations, and their state once each way."""
    live = means["live_slots"] * s["layers"]
    return float(step_ops(s) * live), 2.0 * state_bytes(s) * live


ssd_decode_calls = _decode_calls


# -- the recurrence in a prefill chunk: the blocked form ---------------------------------------------

def ssd_prefill_operands(s: dict, engine: dict) -> str:
    """A request's state, one layer, as the call takes and returns it: [N, H x P] float32."""
    return rf"f32\[{s['ssm_state']},{inner(s)}\]"


def ssd_prefill_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    """One prefill chunk, every layer. A row: `C B^T` against its block (2 BLOCK N, once a B/C group), then a
    head the masked product (2 BLOCK P), the read-out and the update (2 N P each)."""
    rows, h, p, n, g = means["prefill_rows_per_chunk"], s["ssm_heads"], s["ssm_head_dim"], s["ssm_state"], s["ssm_groups"]
    ops = rows * (g * 2 * BLOCK * n + h * (2 * BLOCK * p + 4 * n * p))
    nbytes = 2.0 * rows * (2 * h * p + 2 * g * n) + 2.0 * state_bytes(s)
    return float(ops * s["layers"]), nbytes * s["layers"]


ssd_prefill_calls = _prefill_calls


# -- attention in a decode step: every live slot's context, every layer ------------------------------

def attn_decode_operands(s: dict, engine: dict) -> str:
    """The page pool over every layer, whole, as the call reads it through a layer index: [L, pages, Hkv, page, dh]."""
    return rf"\[{s['layers']},\d+,{s['kv_heads']},{engine['page_len']},{s['head_dim']}\]"


def attn_decode_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    """One decode step, every layer: the positions the live slots' queries see (the context counter), their keys and values once."""
    seen = means["live_slots"] * means["context_per_slot"] * s["layers"]
    return 4.0 * s["heads"] * s["head_dim"] * seen, 2.0 * 2 * s["kv_heads"] * s["head_dim"] * seen


attn_decode_calls = _decode_calls


# -- attention in a prefill chunk: the causal pairs, every layer --------------------------------------

def attn_prefill_operands(s: dict, engine: dict) -> str:
    """A layer's call reads the request's whole staging [L, 1, Hkv, max_len, dh] through a layer index."""
    return rf"\[{s['layers']},1,{s['kv_heads']},{engine['max_len']},{s['head_dim']}\]"


def attn_prefill_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    """One prefill chunk, every layer: the pairs its queries see, and the keys and values those are pairs
    with, read once, with the chunk's own queries read and outputs written."""
    t, pairs = max(means["prefill_rows_per_chunk"], 1.0), means["prefill_pairs_per_chunk"]
    keys = pairs / t + t / 2                                    # the context a chunk's last query sees, about
    nbytes = 2.0 * s["head_dim"] * (2 * s["kv_heads"] * keys + 2 * s["heads"] * t)
    return 4.0 * s["heads"] * s["head_dim"] * pairs * s["layers"], nbytes * s["layers"]


attn_prefill_calls = _prefill_calls
