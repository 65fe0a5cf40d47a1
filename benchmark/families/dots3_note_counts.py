"""Operations and bytes from shapes for the dots3_note family: the benchmark's own
count of what its indexer, its latent attention (both forms, both kinds of layer)
and its routed FFN need, and how to find each one's operations in a trace.

Each kernel `<k>` has `<k>_operands(sizes, engine)` (a pattern over a trace event's
HLO line that only this kernel's operations match), `<k>_call(sizes, engine, means)`
((operations, bytes) of ONE unit of its work: a decode step or a prefill chunk, all
layers of its kind) and `<k>_calls(sizes, engine)` ((the jitted program whose
executions do that work, units an execution)); `window_means(delta, engine)` turns
the changes of the replica's counters over the window into the means (readers/
family_roofline.py). The routed FFN's counts are families/exaone_moe_counts.py's,
over this family's sizes.

Counted is THE MATHEMATICS, each array once, so that a later kernel of another
shape is read against the same work: the indexer scores every position of a
query's context (2 x 64 x 128 operations a query-key pair; a decode step reads a
live slot's index keys once a full layer); decode attention in the absorbed form
reads the rows the indexer chose (a window layer: the window's), 2 bytes a number
of the latent and the rope key (THE NUMBERS, 576 and 1088 a row, not the 640 and
1152 lanes the pools lay them out in: the filling is the layout's cost, and a call
is found at either width), once for all heads, against heads x rows x (2 x
kv_rank + rope) x 2 operations; prefill attention in the expanded form computes
the VISIBLE pairs (a query's chosen 2048, or all while its context is shorter; a
window layer's 513), heads x (nope + rope + v) x 2 operations each, whatever the
kernel computes under its mask, and what building keys and values from the latent
costs is not counted. No traffic between the chips that share a layer is counted:
none is run.
"""

from __future__ import annotations

from counts import laid_out_widths
from families.exaone_moe_counts import (  # noqa: F401 - the routed FFN's kernels, by this family's sizes
    expert_params, moe_decode_call, moe_decode_calls, moe_decode_operands, moe_prefill_call, moe_prefill_calls,
    moe_prefill_operands, n_routed, routed_means)

FULL, SLIDING = "full_attention", "sliding_attention"


def n_kind(s: dict, kind: str) -> int:
    return sum(1 for k in s["kinds"] if k == kind)


def row(s: dict, kind: str) -> int:
    """The numbers a layer of `kind` caches a position: the latent and the rope key (576 a full layer, 1088 a
    window layer). The least a step could read of it, whatever the layout: the program's pools fill a row up
    to whole lanes (640, 1152), and a call is found at any width between (counts.laid_out_widths; until PR 64
    this was the laid-out width, and a pool without the filling would have gone unseen)."""
    p = "" if kind == FULL else "swa_"
    return s[p + "kv_rank"] + s[p + "rope"]


def attention_params(s: dict, kind: str) -> int:
    p = "" if kind == FULL else "swa_"
    d, h, rq, r = s["d_model"], s[p + "heads"], s[p + "q_rank"], s[p + "kv_rank"]
    dn, dr, dv = s[p + "nope"], s[p + "rope"], s[p + "v_dim"]
    n = d * rq + rq * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d + d * h
    if kind == FULL:
        n += rq * s["index_heads"] * s["index_dim"] + d * s["index_dim"] + d * s["index_heads"]
    return n


def total_params(s: dict) -> int:
    """Parameters this replica holds (norms excluded): the weights a decode step reads."""
    attn = sum(attention_params(s, k) for k in s["kinds"])
    routed = (s["shared_experts"] + s["held"][1]) * expert_params(s) + s["d_model"] * s["num_experts"]
    return attn + s["dense_layers"] * 3 * s["d_model"] * s["d_ff"] + n_routed(s) * routed + 2 * s["vocab"] * s["d_model"]


def train_flops_per_token(s: dict, seq: int) -> float:
    """Forward + backward a token on THIS share: no training cell reads it."""
    per_tok = s["top_k"] * s["held"][1] / s["num_experts"]
    matmul = 2 * (sum(attention_params(s, k) for k in s["kinds"]) + s["dense_layers"] * 3 * s["d_model"] * s["d_ff"]
                  + n_routed(s) * (s["shared_experts"] + per_tok) * expert_params(s) + s["d_model"] * s["vocab"])
    pairs = n_kind(s, FULL) * min(seq, s["index_topk"]) * _pair_ops(s, FULL) + n_kind(s, SLIDING) * min(seq, s["window"]) * _pair_ops(s, SLIDING)
    return 3.0 * (matmul + pairs)


def _pair_ops(s: dict, kind: str) -> int:
    """Operations of one visible query-key pair in the expanded form, all heads."""
    p = "" if kind == FULL else "swa_"
    return 2 * s[p + "heads"] * (s[p + "nope"] + s[p + "rope"] + s[p + "v_dim"])


def window_means(delta, engine: dict) -> dict | None:
    """`delta(name=..., where=...)`: the change of one of the replica's counters over the window."""
    means = routed_means(delta, engine)
    seen, context = delta(name="tony_serve_visible_tokens_total"), delta(name="tony_serve_context_tokens_total")
    p_sparse = delta(name="tony_serve_prefill_chunks_total", where={"path": ["sparse"]})
    scored = {phase: delta(name="tony_serve_index_positions_total", where={"phase": [phase]}) for phase in ("decode", "prefill")}
    if means is None or None in (seen, context, p_sparse, *scored.values()):
        return None
    h = engine.get("decode_chunk", 8)
    steps, slot_steps = delta(name="tony_serve_engine_chunks_total") * h, delta(name="tony_serve_decode_slots_total") * h
    p_chunks = delta(name="tony_serve_prefill_chunks_total")
    return {**means, "visible_per_slot": seen / slot_steps, "context_per_slot": context / slot_steps,
            "sparse_chunk_share": p_sparse / p_chunks if p_chunks else 0.0,
            "index_positions_per_step": scored["decode"] / steps,
            "index_pairs_per_chunk": scored["prefill"] / p_chunks if p_chunks else 0.0}


def _read_per_slot(s: dict, means: dict) -> tuple[float, float]:
    """(positions a full layer's step reads a slot, a window layer's): `visible_per_slot` is their mean over the layers."""
    window = min(means["context_per_slot"], s["window"])
    chosen = (means["visible_per_slot"] * s["layers"] - n_kind(s, SLIDING) * window) / max(n_kind(s, FULL), 1)
    return min(max(chosen, 0.0), s["index_topk"]), window


# -- the indexer in a decode step: a live slot's index keys, once a full layer -----------------------

def indexer_decode_operands(s: dict, engine: dict) -> str:
    return rf"\[{n_kind(s, FULL)},\d+,{engine['page_len']},{s['index_dim']}\]"


def indexer_decode_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    positions = means["index_positions_per_step"]              # summed over live slots and full layers already
    return 2.0 * s["index_heads"] * s["index_dim"] * positions, 2.0 * s["index_dim"] * positions


def indexer_decode_calls(s: dict, engine: dict) -> tuple[str, int]:
    return "decode_steps", engine.get("decode_chunk", 8)


# -- the indexer in a prefill chunk -----------------------------------------------------------------

def indexer_prefill_operands(s: dict, engine: dict) -> str:
    return rf"\[{engine['max_len']},{s['index_dim']}\]"


def indexer_prefill_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    pairs, rows = means["index_pairs_per_chunk"], max(means["prefill_rows_per_chunk"], 1.0)
    keys = pairs / rows                                        # the keys a chunk's queries see, read once a chunk
    return 2.0 * s["index_heads"] * s["index_dim"] * pairs, 2.0 * s["index_dim"] * keys + 4.0 * pairs


def indexer_prefill_calls(s: dict, engine: dict) -> tuple[str, int]:
    return "prefill_chunk", 1


# -- decode attention, absorbed: the chosen rows (full layers), the window's (rings) ----------------

def _absorbed(s: dict, kind: str, rows: float) -> tuple[float, float]:
    p = "" if kind == FULL else "swa_"
    return 2.0 * s[p + "heads"] * rows * (2 * s[p + "kv_rank"] + s[p + "rope"]), 2.0 * (s[p + "kv_rank"] + s[p + "rope"]) * rows


def latent_decode_operands(s: dict, engine: dict) -> str:
    return rf"\[1,{engine['slots']},{s['index_topk']},{laid_out_widths(row(s, FULL))}\]"


def latent_decode_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    return _absorbed(s, FULL, means["live_slots"] * _read_per_slot(s, means)[0] * n_kind(s, FULL))


def latent_decode_calls(s: dict, engine: dict) -> tuple[str, int]:
    return "decode_steps", engine.get("decode_chunk", 8)


def latent_ring_decode_operands(s: dict, engine: dict) -> str:
    return rf"\[{n_kind(s, SLIDING)},{engine['slots']},\d+,{laid_out_widths(row(s, SLIDING))}\]"


def latent_ring_decode_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    return _absorbed(s, SLIDING, means["live_slots"] * _read_per_slot(s, means)[1] * n_kind(s, SLIDING))


def latent_ring_decode_calls(s: dict, engine: dict) -> tuple[str, int]:
    return "decode_steps", engine.get("decode_chunk", 8)


# -- prefill attention, expanded: the visible pairs of both kinds of layer --------------------------

def latent_prefill_operands(s: dict, engine: dict) -> str:
    """A full layer's call reads the request's staged rows [max_len, W], a window layer's [tail + chunk, W]: W from
    the row's numbers up to whole lanes."""
    return rf"\[({engine['max_len']},{laid_out_widths(row(s, FULL))}|\d+,{laid_out_widths(row(s, SLIDING))})\]"


def latent_prefill_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    """One prefill chunk, every layer. A chunk on the chosen path (its context past index_topk) sees index_topk
    positions a query on a full layer; a chunk below it the causal half. A window layer's query sees the window."""
    t, k, w = means["prefill_rows_per_chunk"], s["index_topk"], s["window"]
    full = means["sparse_chunk_share"] * t * k + (1.0 - means["sparse_chunk_share"]) * min(t * (t + 1) / 2, t * k)
    pairs = {FULL: full * n_kind(s, FULL), SLIDING: t * w * n_kind(s, SLIDING)}
    ops = sum(_pair_ops(s, kind) * n for kind, n in pairs.items())
    nbytes = 2.0 * t * sum(n_kind(s, kind) * ((k if kind == FULL else w) / max(t, 1) + 1) * row(s, kind) for kind in (FULL, SLIDING))
    return float(ops), nbytes


def latent_prefill_calls(s: dict, engine: dict) -> tuple[str, int]:
    return "prefill_chunk", 1
