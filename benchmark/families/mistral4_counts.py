"""Operations and bytes from shapes for the mistral4 family: the benchmark's own
count of what its dense latent attention (both forms) and its routed FFN need,
and how to find each one's operations in a trace.

Each kernel `<k>` has `<k>_operands(sizes, engine)` (a pattern over a trace event's
HLO line that only this kernel's operations match), `<k>_call(sizes, engine, means)`
((operations, bytes) of ONE unit of its work: a decode step or a prefill chunk, all
layers) and `<k>_calls(sizes, engine)` ((the jitted program whose executions do
that work, units an execution)); `window_means(delta, engine)` turns the changes of
the replica's counters over the window into the means (readers/family_roofline.py).
The routed FFN's counts are families/exaone_moe_counts.py's, over this family's
sizes (every layer routed: `dense_layers` 0).

Counted is THE MATHEMATICS, each array once, so that a later kernel of another
shape is read against the same work. Decode attention in the absorbed form reads
EVERY row of a live slot's context a layer: THE NUMBERS of a row, the latent and
the rope key (kv_rank + rope: 320), 2 bytes each, once for all heads, whatever the
pool lays out beside them (today's fills a row up to 384 lanes and a page's fetch
moves the filling too: that sixth is the layout's cost, and a count that took the
laid-out width would read 85 before and after a pool lost it), against heads x
rows x (2 x kv_rank + rope) x 2 operations (the scores over the numbers, the
weighted sum over the latent). A call is FOUND at any width from the numbers' to
whole lanes' (counts.laid_out_widths).
Prefill attention in the expanded form computes the causal pairs a chunk's queries
see, heads x (nope + rope + v) x 2 operations each, whatever the kernel computes
under its mask; what building keys and values from the latent costs is not
counted. No traffic between the chips that share a layer is counted: none is run.
"""

from __future__ import annotations

from counts import laid_out_widths
from families.exaone_moe_counts import (  # noqa: F401 - the routed FFN's kernels, by this family's sizes
    expert_params, moe_decode_call, moe_decode_calls, moe_decode_operands, moe_prefill_call, moe_prefill_calls,
    moe_prefill_operands, n_routed, routed_means)


def row(s: dict) -> int:
    """The numbers a layer caches a position: the latent and the rope key. The least a step could read of it,
    whatever the layout (until PR 64 this was the width in whole lanes, 384 for 320)."""
    return s["kv_rank"] + s["rope"]


def attention_params(s: dict) -> int:
    d, h, rq, r, dn, dr, dv = s["d_model"], s["heads"], s["q_rank"], s["kv_rank"], s["nope"], s["rope"], s["v_dim"]
    return d * rq + rq * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d


def total_params(s: dict) -> int:
    """Parameters this replica holds (norms excluded): the weights a decode step reads."""
    layer = attention_params(s) + (s["shared_experts"] + s["held"][1]) * expert_params(s) + s["d_model"] * s["num_experts"]
    return s["layers"] * layer + 2 * s["vocab"] * s["d_model"]


def train_flops_per_token(s: dict, seq: int) -> float:
    """Forward + backward a token on THIS share: no training cell reads it."""
    per_tok = s["top_k"] * s["held"][1] / s["num_experts"]
    matmul = 2 * (s["layers"] * (attention_params(s) + (s["shared_experts"] + per_tok) * expert_params(s)) + s["d_model"] * s["vocab"])
    return 3.0 * (matmul + s["layers"] * (seq + 1) / 2 * _pair_ops(s))


def _pair_ops(s: dict) -> int:
    """Operations of one visible query-key pair in the expanded form, all heads."""
    return 2 * s["heads"] * (s["nope"] + s["rope"] + s["v_dim"])


def window_means(delta, engine: dict) -> dict | None:
    """`delta(name=..., where=...)`: the change of one of the replica's counters over the window."""
    means = routed_means(delta, engine)
    context, pairs = delta(name="tony_serve_context_tokens_total"), delta(name="tony_serve_prefill_pairs_total")
    if means is None or None in (context, pairs):
        return None
    slot_steps = delta(name="tony_serve_decode_slots_total") * engine.get("decode_chunk", 8)
    p_chunks = delta(name="tony_serve_prefill_chunks_total")
    return {**means, "context_per_slot": context / slot_steps, "prefill_pairs_per_chunk": pairs / p_chunks if p_chunks else 0.0}


# -- decode attention, absorbed, over every row of the context through the page table ---------------
# `latent_rows_decode` is what latent_paged_decode_roofline_pct.serve reads since PR 64: the numbers of a row.

def latent_rows_decode_operands(s: dict, engine: dict) -> str:
    """The whole latent pool [layers, pages, page_len, W], W from the row's numbers up to whole lanes: only this call takes it."""
    return rf"\[{s['layers']},\d+,{engine['page_len']},{laid_out_widths(row(s))}\]"


def latent_rows_decode_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    """One decode step, every layer: the live slots' context rows, the numbers of each once for all heads."""
    rows = means["live_slots"] * means["context_per_slot"] * s["layers"]
    return 2.0 * s["heads"] * rows * (row(s) + s["kv_rank"]), 2.0 * row(s) * rows


def latent_rows_decode_calls(s: dict, engine: dict) -> tuple[str, int]:
    return "decode_steps", engine.get("decode_chunk", 8)


# The same call counted as the pool LAYS A ROW OUT (the numbers filled up to whole lanes: 384 for 320), which is
# what the metric read until PR 64. No metric reads these three since; tests/test_mistral4.py pins their numbers,
# and a `benchmark` PR may edit no file outside benchmark/: the next PR that may deletes them with that test's lines.
latent_paged_decode_operands, latent_paged_decode_calls = latent_rows_decode_operands, latent_rows_decode_calls


def latent_paged_decode_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    rows, laid_out = means["live_slots"] * means["context_per_slot"] * s["layers"], -(-row(s) // 128) * 128
    return 2.0 * s["heads"] * rows * (laid_out + s["kv_rank"]), 2.0 * laid_out * rows


# -- prefill attention, expanded: the causal pairs ---------------------------------------------------

def latent_prefill_operands(s: dict, engine: dict) -> str:
    """A layer's call reads the request's staged rows [max_len, W], W from the row's numbers up to whole lanes."""
    return rf"\[{engine['max_len']},{laid_out_widths(row(s))}\]"


def latent_prefill_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    """One prefill chunk, every layer: the pairs its queries see (counted on the host a chunk: rows x the
    positions before the chunk + the chunk's own causal half), and the rows those are keys of, read once,
    with the chunk's own written."""
    t, pairs = max(means["prefill_rows_per_chunk"], 1.0), means["prefill_pairs_per_chunk"]
    keys = pairs / t + t / 2                                    # the context a chunk's last query sees, about
    return float(_pair_ops(s) * pairs * s["layers"]), 2.0 * row(s) * (keys + t) * s["layers"]


def latent_prefill_calls(s: dict, engine: dict) -> tuple[str, int]:
    return "prefill_chunk", 1
