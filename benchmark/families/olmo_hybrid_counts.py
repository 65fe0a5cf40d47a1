"""Operations and bytes from shapes for the olmo_hybrid family: the benchmark's own
count of what its gated delta rule (both forms) and its full attention (both
phases) need, and how to find each one's operations in a trace.

Each kernel `<k>` has `<k>_operands(sizes, engine)` (a pattern over a trace event's
HLO line that only this kernel's operations match), `<k>_call(sizes, engine, means)`
((operations, bytes) of ONE unit of its work: a decode step or an admission's
prefill chunk, all layers of its kind) and `<k>_calls(sizes, engine)` ((the jitted
program whose executions count those units, units an execution));
`window_means(delta, engine)` turns the changes of the replica's counters over the
window into the means (readers/family_roofline.py).

Counted is THE MATHEMATICS, each array once, so that a later kernel of another
shape is read against the same work and a share cannot pass 100:

- the rule in a decode CHUNK reads a live slot's float32 state from HBM once a
  linear layer and writes it once (the HBM bound), against 6 x dk x dv operations
  a head and step (decay and probe, write, read). Once a chunk and not once a
  step: the state is the carry of the chunk's scan over its steps, and the
  compiler keeps it in the chip's second memory space (`S(1)` in the call's HLO
  line) from step to step, so a step's call took 29.6 us where reading and
  writing the state of 12 slots from HBM takes 64.8 at the peak (my chip runs, PR
  50): a bound a call beats is no bound;
- the rule in a prefill chunk is those 6 x dk x dv operations a head and ROW of the
  chunk as the engine dispatched it (its rows padded to their bucket, which the
  kernel computes too); what the blocked form adds to that (the products between a
  block's rows, the triangular solve) is the kernel's own cost and not counted, so
  the share is low by construction: the sequential solve of a block's 64 rows runs
  on the vector unit while the matrix unit waits;
- full attention in a prefill chunk computes the causal pairs the chunk's queries
  see (the host counts them a chunk: tony_serve_prefill_pairs_total), heads x 4 x
  head_dim operations each, whatever the kernel computes under its mask on the
  diagonal tiles (the compute bound);
- full attention in a decode step has NO count here: `attn_decode_roofline_pct.serve`
  would read it against the window's mean context, and this cell's context is not
  the same in the 3 s the trace covers as over the window (sessions are younger
  when the window opens), so the share read 100.8 and 103.6 for a page walk that
  runs near 90% of the HBM peak (PERF.md section 7: the reader needs the
  registry at the trace's own start and end).

A prefill chunk of the engine's runs as one or two programs (`prefill_chunk` up to
the edge of its last page, then `prefill_page` over that page's rows: models/
olmo_hybrid.serving_programs); every chunk ends in exactly one `prefill_page`, so
its executions count the chunks. No traffic between pipeline stages is counted:
none is run.
"""

from __future__ import annotations

LINEAR, FULL = "linear_attention", "full_attention"


def n_of(s: dict, kind: str) -> int:
    return sum(1 for k in s["layer_types"] if k == kind)


def mixer_params(s: dict, kind: str) -> int:
    d = s["d_model"]
    if kind == FULL:
        return d * (s["heads"] + 2 * s["kv_heads"]) * s["head_dim"] + s["heads"] * s["head_dim"] * d
    h, dk, dv = s["lin_heads"], s["lin_key_dim"], s["lin_value_dim"]
    return d * h * (2 * dk + dv) + 2 * d * h * dv + 2 * d * h


def total_params(s: dict) -> int:
    """Parameters this replica holds (norms, the convolution's taps and the decay's excluded): what a decode step reads."""
    ffn = 3 * s["d_model"] * s["d_ff"]
    return sum(mixer_params(s, kind) + ffn for kind in s["layer_types"]) + 2 * s["vocab"] * s["d_model"]


def train_flops_per_token(s: dict, seq: int) -> float:
    """Forward + backward a token: no training cell reads it (the rule has no backward on the tree)."""
    matmul = 2 * (total_params(s) - s["vocab"] * s["d_model"])
    attn = n_of(s, FULL) * 4 * s["heads"] * s["head_dim"] * (seq + 1) / 2
    return 3.0 * (matmul + attn + n_of(s, LINEAR) * rule_ops(s))


def rule_ops(s: dict) -> int:
    """Operations of one position of the rule, all heads of a layer: decay and probe, write, read."""
    return 6 * s["lin_heads"] * s["lin_key_dim"] * s["lin_value_dim"]


def state_bytes(s: dict) -> int:
    """A slot's float32 state, one linear layer."""
    return 4 * s["lin_heads"] * s["lin_key_dim"] * s["lin_value_dim"]


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


# -- the rule in a decode chunk: every live slot's state, read and written once a linear layer ------

def delta_decode_operands(s: dict, engine: dict) -> str:
    """The state of all slots, one layer: [slots, H, dk, dv] float32."""
    return rf"f32\[{engine['slots']},{s['lin_heads']},{s['lin_key_dim']},{s['lin_value_dim']}\]"


def delta_decode_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    """One decode step, every linear layer: its operations, and its share of the chunk's one read and one write of the state."""
    live = means["live_slots"] * n_of(s, LINEAR)
    return float(rule_ops(s) * live), 2.0 * state_bytes(s) * live / engine.get("decode_chunk", 8)


def delta_decode_calls(s: dict, engine: dict) -> tuple[str, int]:
    return "decode_steps", engine.get("decode_chunk", 8)


# -- the rule in a prefill chunk ------------------------------------------------------------------------

def delta_prefill_operands(s: dict, engine: dict) -> str:
    """A head's state, one layer: [H, dk, dv] float32, which the call takes and returns."""
    return rf"f32\[{s['lin_heads']},{s['lin_key_dim']},{s['lin_value_dim']}\]"


def delta_prefill_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    """One prefill chunk, every linear layer: its rows' operations; its q, k, v read and o written in
    the activations' type and the state once each way."""
    rows, h = means["prefill_rows_per_chunk"], s["lin_heads"]
    nbytes = 2.0 * rows * h * 2 * (s["lin_key_dim"] + s["lin_value_dim"]) + 2.0 * state_bytes(s)
    return float(rule_ops(s) * rows * n_of(s, LINEAR)), nbytes * n_of(s, LINEAR)


def delta_prefill_calls(s: dict, engine: dict) -> tuple[str, int]:
    return "prefill_page", 1


# -- full attention in a prefill chunk: the causal pairs ------------------------------------------------

def attn_prefill_operands(s: dict, engine: dict) -> str:
    """A layer's call reads the request's whole staging [Lf, 1, Hkv, max_len, dh] through a layer index."""
    return rf"\[{n_of(s, FULL)},1,{s['kv_heads']},{engine['max_len']},{s['head_dim']}\]"


def attn_prefill_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    """One prefill chunk, every full layer: the pairs its queries see, and the keys and values those are
    pairs with, read once, with the chunk's own queries read and outputs written."""
    t, pairs = max(means["prefill_rows_per_chunk"], 1.0), means["prefill_pairs_per_chunk"]
    keys = pairs / t + t / 2                                    # the context a chunk's last query sees, about
    nbytes = 2.0 * s["head_dim"] * (2 * s["kv_heads"] * keys + 2 * s["heads"] * t)
    return 4.0 * s["heads"] * s["head_dim"] * pairs * n_of(s, FULL), nbytes * n_of(s, FULL)


def attn_prefill_calls(s: dict, engine: dict) -> tuple[str, int]:
    return "prefill_page", 1
