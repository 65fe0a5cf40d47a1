"""Operations and bytes from shapes for the phi4_flash family: the benchmark's own count
of what its selective scans and its differential attention need in both phases, and how
to find each one's operations in a trace.

Each kernel `<k>` has `<k>_operands(sizes, engine)` (a pattern over a trace event's HLO
line that only this kernel's operations match), `<k>_call(sizes, engine, means)`
((operations, bytes) of ONE unit of its work: a decode step or a prefill chunk, all the
layers that make the call) and `<k>_calls(sizes, engine)` ((the jitted program whose
executions count those units, units an execution)); `window_means(delta, engine)` turns
the changes of the replica's counters over the window into the means
(readers/family_roofline.py).

Counted is THE MATHEMATICS, each array once a layer that needs it, so that a later kernel
of another shape is read against the same work and a share cannot pass 100:

- the shared pool in a decode step: the ONE layer's keys and values of every live slot's
  context (the engine's context counter, whatever pages the kernel fetches whole), 2 x
  kv_heads x head_dim elements a position, 5,120 B, ONCE A READING LAYER: the full layer
  and every cross layer, eight a step at the published depth. Whatever implements the
  differential form is read against this: four calls of a one-map kernel, which read
  every page twice, read under 50. Operations: both maps' scores (2 head_dim a key and
  query head) and both maps' products with the pair's values (4 head_dim): 6 x heads x
  head_dim a position and reading layer. The HBM bound;
- a window layer's ring in a decode step: the positions a live slot's query sees,
  min(context, window) of them, the same bytes and operations a position, a window layer;
- the full layer's attention in a prefill chunk: the causal pairs its queries see (the
  host's count, `tony_serve_prefill_pairs_total`, here of ONE layer) x 6 x heads x head_dim,
  whatever the kernel computes on widened queries, under its mask on the diagonal tiles
  and for a chunk's padding rows, against the keys and values those pairs are with, read
  once, and the chunk's queries in and outputs out;
- the selective scan in a decode STEP reads a live slot's float32 state from HBM once a
  mamba layer and writes it once: 2 x 4 x E x N bytes, 655,360 B at 5,120 x 16, against 6 x
  E x N operations (the decay's product and its exponential's argument, the state's
  multiply and add, the read-out's multiply and add): its HBM bound, and the call is
  bound by its launches and lanes at that size, not by it: the share reads low by nature;
- the selective scan in a prefill chunk: the rows as the engine dispatched them (padded to
  their bucket, which the kernel walks too): x, dt in and y out once each in the
  activations' type, a position's B and C once, the state once each way; 6 x E x N
  operations a row. peaks.json has a matrix unit's peak and the HBM's only and the scan is
  the vector unit's, a position at a time: the share is its HBM bound and reads low by
  nature; no peak is invented for it.
"""

from __future__ import annotations


def layers_of(s: dict) -> dict:
    """How many layers of each kind: (mamba, window) periods below the memory layer M, the memory layer itself,
    the full layer, (gmu, cross) periods above."""
    below, above = s["memory_layer"] // 2, (s["layers"] - s["memory_layer"] - 2) // 2
    return {"mamba": below + 1, "window": below, "full": 1, "gmu": above, "cross": above}


def layer_params(s: dict) -> dict:
    """One layer of each kind (the norms, biases and vectors excluded): the mixer and the FFN."""
    d, e, n, r, dh = s["d_model"], s["d_inner"], s["ssm_state"], s["dt_rank"], s["head_dim"]
    q, kv, ffn = s["heads"] * dh, s["kv_heads"] * dh, 3 * d * s["d_ff"]
    return {"mamba": d * 2 * e + e * (r + 2 * n) + r * e + e * d + s["conv_taps"] * e + e * n + ffn,
            "window": d * (q + 2 * kv) + q * d + ffn, "full": d * (q + 2 * kv) + q * d + ffn,
            "gmu": 2 * d * e + ffn, "cross": 2 * d * q + ffn}


def total_params(s: dict) -> int:
    """Parameters held (the norms, biases and vectors excluded): every layer and the tied embedding once."""
    per, count = layer_params(s), layers_of(s)
    return sum(per[k] * count[k] for k in per) + s["vocab"] * s["d_model"]


def train_flops_per_token(s: dict, seq: int) -> float:
    """Forward + backward a token: no training cell reads it (the scan has no backward on the tree)."""
    count = layers_of(s)
    attn = (count["full"] + count["cross"]) * (seq + 1) / 2 + count["window"] * min(s["window"], (seq + 1) / 2)
    return 3.0 * (2 * total_params(s) + pair_ops(s) * attn + count["mamba"] * step_ops(s))


def pair_ops(s: dict) -> int:
    """Operations of one (query position, key position) pair, all heads, both maps: scores over head_dim, values over 2 head_dim."""
    return 6 * s["heads"] * s["head_dim"]


def position_bytes(s: dict) -> int:
    """A position's keys and values in one layer's cache, in the activations' type."""
    return 2 * 2 * s["kv_heads"] * s["head_dim"]


def step_ops(s: dict) -> int:
    """Operations of one position of the selective scan, all channels of a layer."""
    return 6 * s["d_inner"] * s["ssm_state"]


def state_bytes(s: dict) -> int:
    """A slot's float32 state, one layer."""
    return 4 * s["d_inner"] * s["ssm_state"]


def readers(s: dict) -> int:
    """Layers that read the ONE layer's pool in a decode step: the full layer and every cross layer."""
    count = layers_of(s)
    return count["full"] + count["cross"]


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


# -- the shared pool in a decode step: one layer's pages, once a reading layer --------------------------

def diff_decode_operands(s: dict, engine: dict) -> str:
    """The pool of ONE layer's kv-head pairs, whole, as every reading layer's call takes it: [1, pages, Hkv / 2, page, 2 dh]."""
    return rf"\[1,\d+,{s['kv_heads'] // 2},{engine['page_len']},{2 * s['head_dim']}\]"


def diff_decode_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    """One decode step: the positions the live slots' queries see (the context counter), their keys and values once a reading layer."""
    seen = means["live_slots"] * means["context_per_slot"] * readers(s)
    return float(pair_ops(s)) * seen, float(position_bytes(s)) * seen


diff_decode_calls = _decode_calls


# -- a window layer's ring in a decode step --------------------------------------------------------------

def diff_ring_operands(s: dict, engine: dict) -> str:
    """Every window layer's rings of pairs, whole, as a call reads them through a layer index: [P, slots, Hkv / 2, ring, 2 dh]."""
    return rf"\[{layers_of(s)['window']},{engine['slots']},{s['kv_heads'] // 2},\d+,{2 * s['head_dim']}\]"


def diff_ring_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    """One decode step, every window layer: the window's positions of every live slot."""
    seen = means["live_slots"] * min(means["context_per_slot"], s["window"]) * layers_of(s)["window"]
    return float(pair_ops(s)) * seen, float(position_bytes(s)) * seen


diff_ring_calls = _decode_calls


# -- the full layer's attention in a prefill chunk: the causal pairs ------------------------------------

def diff_prefill_operands(s: dict, engine: dict) -> str:
    """The call reads the request's staging of pairs [1, 1, Hkv / 2, max_len, 2 dh] through a layer index."""
    return rf"\[1,1,{s['kv_heads'] // 2},{engine['max_len']},{2 * s['head_dim']}\]"


def diff_prefill_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    """One prefill chunk, the ONE full layer: the pairs its queries see, both maps, and the keys and values those
    are pairs with, read once, with the chunk's own queries read and both maps' outputs written."""
    t, pairs = max(means["prefill_rows_per_chunk"], 1.0), means["prefill_pairs_per_chunk"]
    keys = pairs / t + t / 2                                    # the context a chunk's last query sees, about
    nbytes = position_bytes(s) * keys + 2.0 * s["heads"] * s["head_dim"] * 3 * t
    return float(pair_ops(s)) * pairs, nbytes


diff_prefill_calls = _prefill_calls


# -- the selective scan in a decode step: every live slot's state, read and written once a mamba layer ----

def scan_decode_operands(s: dict, engine: dict) -> str:
    """The state of all slots, one layer, as the call takes and returns it: [slots, N, E] float32."""
    return rf"f32\[{engine['slots']},{s['ssm_state']},{s['d_inner']}\]"


def scan_decode_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    live = means["live_slots"] * layers_of(s)["mamba"]
    return float(step_ops(s) * live), 2.0 * state_bytes(s) * live


scan_decode_calls = _decode_calls


# -- the selective scan in a prefill chunk ---------------------------------------------------------------

def scan_prefill_operands(s: dict, engine: dict) -> str:
    """A request's state, one layer, as the call takes and returns it: [N, E] float32."""
    return rf"f32\[{s['ssm_state']},{s['d_inner']}\]"


def scan_prefill_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    """One prefill chunk, every mamba layer: x and dt in and y out in the activations' type, B and C once, the state once each way."""
    rows, mamba = means["prefill_rows_per_chunk"], layers_of(s)["mamba"]
    nbytes = 2.0 * rows * (3 * s["d_inner"] + 2 * s["ssm_state"]) + 2.0 * state_bytes(s)
    return float(step_ops(s) * rows * mamba), nbytes * mamba


scan_prefill_calls = _prefill_calls
