"""Operations and bytes from shapes for the minicpm_sala family: the
benchmark's own count of what its four new kernels need, and how to find each
one's calls in a trace.

Each kernel `<k>` has

  <k>_operands(sizes, engine)        a pattern over a trace event's HLO line that only this
                                     kernel's operations match (an operand shape of its own)
  <k>_call(sizes, engine, means)     (operations, bytes) ONE call needs, at the means the
                                     replica's counters give for the window (live slots,
                                     visible positions a slot, the share of sparse chunks)
  <k>_calls(sizes, engine)           (the jitted program whose executions make the calls,
                                     calls an execution)

Counted is what the algorithm needs, not what a first version does: the sparse
kernels at the VISIBLE keys (a prefill that computes every staged key under a
mask reads low, and says so), the linear ones as the recurrence (4 d^2
operations a head and position), every array once. Activations and pages are
2 bytes a number, the state and compressed keys 4.
"""

from __future__ import annotations

SPARSE, LINEAR = "minicpm4", "lightning-attn"


def n_layers(s: dict, kind: str) -> int:
    return sum(1 for m in s["mixer_types"] if m == kind)


def layer_matmul_params(s: dict, kind: str = "") -> int:
    """Parameters of one layer that a token multiplies (norms excluded); with
    no kind, the mean over the configuration's layers."""
    d = s["d_model"]
    ffn = 3 * d * s["d_ff"]
    sparse = 3 * d * s["heads"] * s["head_dim"] + 2 * d * s["kv_heads"] * s["head_dim"] + ffn   # q, gate, o; k, v
    linear = 5 * d * s["lin_heads"] * s["lin_head_dim"] + ffn                                  # q, k, v, gate, o
    if kind:
        return sparse if kind == SPARSE else linear
    return (n_layers(s, SPARSE) * sparse + n_layers(s, LINEAR) * linear) // s["layers"]


def total_params(s: dict) -> int:
    norms = n_layers(s, SPARSE) * (2 * s["d_model"] + 2 * s["head_dim"]) + n_layers(s, LINEAR) * (
        2 * s["d_model"] + 3 * s["lin_head_dim"]) + s["d_model"]
    return (n_layers(s, SPARSE) * layer_matmul_params(s, SPARSE) + n_layers(s, LINEAR) * layer_matmul_params(s, LINEAR)
            + 2 * s["vocab"] * s["d_model"] + norms)


def visible(s: dict, n: int) -> int:
    """Positions the sparse layers' query at context n may read."""
    if n <= s["sparse_dense_len"]:
        return n
    return min(n, s["sparse_topk"] * s["sparse_block_size"] + s["sparse_window"])


def train_flops_per_token(s: dict, seq: int) -> float:
    """Forward + backward (3 x forward) per trained token: the matmuls, the
    sparse layers' attention at the visible keys, the linear layers' recurrence."""
    matmul = 2 * (s["layers"] * layer_matmul_params(s) + s["d_model"] * s["vocab"])
    pairs = sum(visible(s, n) for n in range(1, seq + 1)) / seq
    attn = n_layers(s, SPARSE) * 4 * s["heads"] * s["head_dim"] * pairs
    recur = n_layers(s, LINEAR) * 4 * s["lin_heads"] * s["lin_head_dim"] ** 2
    return 3.0 * (matmul + attn + recur)


# -- sparse decode attention: one call reads the visible pages of every live slot ---------------

def sparse_decode_operands(s: dict, engine: dict) -> str:
    """The whole page pool is its operand: [sparse layers, pages, kv heads, page, head size]."""
    return rf"\[{n_layers(s, SPARSE)},\d+,{s['kv_heads']},{engine['page_len']},{s['head_dim']}\]"


def sparse_decode_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    """QK^T and PV over the visible positions of the live slots; their keys
    and values read once for the group's query heads."""
    seen = means["live_slots"] * means["visible_per_slot"]
    return 4.0 * s["heads"] * s["head_dim"] * seen, 2.0 * 2 * s["kv_heads"] * s["head_dim"] * seen


def sparse_decode_calls(s: dict, engine: dict) -> tuple[str, int]:
    return "decode_steps", n_layers(s, SPARSE) * engine.get("decode_chunk", 8)


# -- linear decode: the state of every live slot read, decayed, added to, and written ------------

def linear_decode_operands(s: dict, engine: dict) -> str:
    """Every operation that takes or gives the state of all slots, one layer's or the stack of
    them that a step updates in place: f32[(linear layers,) slots, heads, d, d]. XLA fusions with no
    name of their own, but whatever reads or writes the state has it as an operand, so however the
    compiler cuts the step into fusions, the time that matches is the time of the state's traffic."""
    return rf"f32\[({n_layers(s, LINEAR)},)?{engine['slots']},{s['lin_heads']},{s['lin_head_dim']},{s['lin_head_dim']}\]"


def linear_decode_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    per_slot = s["lin_heads"] * s["lin_head_dim"] ** 2
    return 4.0 * per_slot * means["live_slots"], 2.0 * 4 * per_slot * means["live_slots"]


def linear_decode_calls(s: dict, engine: dict) -> tuple[str, int]:
    return "decode_steps", n_layers(s, LINEAR) * engine.get("decode_chunk", 8)


# -- linear prefill: one chunk of positions through the recurrence ------------------------------

def linear_prefill_operands(s: dict, engine: dict) -> str:
    """The Pallas call of ops/linear_attention.linear_attention_chunk (the metric's `match` is its
    name), by the one request's state it takes and returns: f32[heads, d, d]."""
    return rf"f32\[{s['lin_heads']},{s['lin_head_dim']},{s['lin_head_dim']}\]"


def linear_prefill_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    t, width = engine["prefill_chunk"], s["lin_heads"] * s["lin_head_dim"]
    state = s["lin_heads"] * s["lin_head_dim"] ** 2
    return 4.0 * t * state, 2.0 * 4 * t * width + 2.0 * 4 * state


def linear_prefill_calls(s: dict, engine: dict) -> tuple[str, int]:
    return "prefill_chunk", n_layers(s, LINEAR)


# -- sparse prefill: one chunk of queries over their visible keys -------------------------------

def sparse_prefill_operands(s: dict, engine: dict) -> str:
    """Its mask is its own: s8[kv heads, chunk, staged positions]."""
    return rf"s8\[{s['kv_heads']},{engine['prefill_chunk']},{engine['max_len']}\]"


def sparse_prefill_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    """A chunk on the sparse path reads the capped visible set a query; one on
    the dense path (the prompt's first dense_len positions) the causal triangle,
    at the mean of the chunk positions below dense_len. q and o once, and the
    keys and values a query of the chunk can see once."""
    t = engine["prefill_chunk"]
    starts = range(0, s["sparse_dense_len"], t)
    dense_pairs = sum(sum(range(p + 1, p + t + 1)) for p in starts) / max(len(starts), 1)
    sparse_pairs = t * visible(s, s["sparse_dense_len"] + t)
    share = means["sparse_chunk_share"]
    pairs = share * sparse_pairs + (1.0 - share) * dense_pairs
    keys = share * visible(s, s["sparse_dense_len"] + t) + (1.0 - share) * (s["sparse_dense_len"] + t) / 2
    nbytes = 2.0 * 2 * t * s["heads"] * s["head_dim"] + 2.0 * 2 * keys * s["kv_heads"] * s["head_dim"]
    return 4.0 * s["heads"] * s["head_dim"] * pairs, nbytes


def sparse_prefill_calls(s: dict, engine: dict) -> tuple[str, int]:
    return "prefill_chunk", n_layers(s, SPARSE)
