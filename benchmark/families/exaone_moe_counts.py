"""Operations and bytes from shapes for the exaone_moe family: the benchmark's
own count of what its routed FFN and its two kinds of decode attention need, and
how to find each one's operations in a trace.

Each kernel `<k>` has

  <k>_operands(sizes, engine)        a pattern over a trace event's HLO line that only this
                                     kernel's operations match (an operand shape of its own)
  <k>_call(sizes, engine, means)     (operations, bytes) of ONE unit of its work (a decode step
                                     or a prefill chunk, all layers), at the window's means
  <k>_calls(sizes, engine)           (the jitted program whose executions do that work, units an execution)

and `window_means(delta, engine)` turns the changes of the replica's counters over the
window into those means (readers/family_roofline.py). Counted is what the algorithm needs,
each array once: a step's routed FFN reads the slabs of the held experts that some live
slot's row chose, AS THE PROGRAM COUNTED THEM on the device; a step's attention reads the
keys and values it may see (a window layer 128 a slot, the full layer the context). Weights,
activations and caches are 2 bytes a number. No traffic between the chips that share a layer
is counted: none is run.

The routed FFN's count is here ONCE for the five routed families (exaone_moe, dots3_note,
mistral4, granite_hybrid, solar_open2 import `routed_means`, `moe_decode_*`, `moe_prefill_*`
from this file): every one of them runs parallel/expert.held_expert_ffn over a bank
[held, D, F] and adds parallel/expert.held_step_counts to its decode chunk's counts.
"""

from __future__ import annotations


def n_window(s: dict) -> int:
    return sum(1 for w in s["windows"] if w)


def n_routed(s: dict) -> int:
    """Layers with a routed FFN: all of them where the family's sizes name no dense ones."""
    return s["layers"] - s.get("dense_layers", 0)


def expert_params(s: dict) -> int:
    return 3 * s["d_model"] * s["d_expert"]


def attention_params(s: dict) -> int:
    return 2 * s["d_model"] * s["heads"] * s["head_dim"] + 2 * s["d_model"] * s["kv_heads"] * s["head_dim"]


def total_params(s: dict) -> int:
    """Parameters this replica holds (norms excluded): the weights a decode step reads."""
    routed = attention_params(s) + (s["shared_experts"] + s["held"][1]) * expert_params(s) + s["d_model"] * s["num_experts"]
    dense = attention_params(s) + 3 * s["d_model"] * s["d_ff"]
    return s["dense_layers"] * dense + n_routed(s) * routed + 2 * s["vocab"] * s["d_model"]


def train_flops_per_token(s: dict, seq: int) -> float:
    """Forward + backward (3 x forward) a token on THIS share (its held experts'
    part of the routed FFN): no training cell reads it."""
    import counts

    per_tok = s["top_k"] * s["held"][1] / s["num_experts"]
    matmul = 2 * (s["layers"] * attention_params(s) + s["dense_layers"] * 3 * s["d_model"] * s["d_ff"]
                  + n_routed(s) * (s["shared_experts"] + per_tok) * expert_params(s) + s["d_model"] * s["vocab"])
    window = max(s["windows"])
    pairs = (n_window(s) * counts.causal_pairs(seq, window) + (s["layers"] - n_window(s)) * counts.causal_pairs(seq, 0)) / seq
    return 3.0 * (matmul + 4 * s["heads"] * s["head_dim"] * pairs)


def routed_means(delta, engine: dict) -> dict | None:
    """The window's means that the routed FFN's counts read, from the changes of the replica's counters:
    `delta(name=..., where=...)`. `touched_per_step` is there where the program counts the slabs it read
    (`tony_serve_experts_touched_total`, since PR 48) and left out where it does not."""
    chunks, slots = delta(name="tony_serve_engine_chunks_total"), delta(name="tony_serve_decode_slots_total")
    rows, touched = delta(name="tony_serve_expert_rows_total"), delta(name="tony_serve_experts_touched_total")
    p_tokens, p_chunks = delta(name="tony_serve_prefill_tokens_total"), delta(name="tony_serve_prefill_chunks_total")
    if None in (chunks, slots, rows, p_tokens, p_chunks) or not chunks or not slots:
        return None
    steps = chunks * engine.get("decode_chunk", 8)
    means = {"live_slots": slots / chunks, "held_rows_per_step": rows / steps,
             "prefill_rows_per_chunk": p_tokens / p_chunks if p_chunks else 0.0}
    if touched is not None:
        means["touched_per_step"] = touched / steps
    return means


def window_means(delta, engine: dict) -> dict | None:
    """`delta(name=..., where=...)`: the change of one of the replica's counters over the window."""
    means, seen = routed_means(delta, engine), delta(name="tony_serve_visible_tokens_total")
    if means is None or seen is None:
        return None
    slot_steps = delta(name="tony_serve_decode_slots_total") * engine.get("decode_chunk", 8)
    return {**means, "visible_per_slot": seen / slot_steps}


def _bank(s: dict) -> str:
    """An expert bank, a layer's or every routed layer's stacked: bf16[(layers,)held,D,F] or [..,F,D]."""
    d, f = s["d_model"], s["d_expert"]
    return rf"bf16\[(\d+,)?{s['held'][1]},({d},{f}|{f},{d})\]"


# -- the routed FFN in a decode step: the chosen held experts' slabs, each once -------------------

def moe_decode_operands(s: dict, engine: dict) -> str:
    return _bank(s)


def moe_decode_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    """One decode step, all routed layers: 2 x 3 D F operations a row that landed on a held expert
    (`held_rows_per_step`, summed over the routed layers on the device), and the slabs of the held
    experts that SOME LIVE SLOT'S ROW CHOSE, each once: `touched_per_step`, the program's own count a
    step and layer (parallel/expert.held_step_counts: `(rows > 0).sum()` over live slots' rows), summed
    over the layers already, plus the rows in and out (under 1% of the bytes at every cell). The
    count is NOT scaled up by slots over live slots: an idle slot's row is computed like any and may
    fetch a slab no live row chose, and that slab is the program's waste, not work the step needs, so
    the share stays under 100 whatever the idle rows choose. Where the program lacks the counter,
    the expectation under even routing stands in: held x (1 - (1 - top_k / E) ^ live slots) a layer,
    which a skewed router undercuts (131 read at `serve_notes` by it: PERF.md section 6, PR 64)."""
    rows, touched = means["held_rows_per_step"], means.get("touched_per_step")
    if touched is None:
        touched = n_routed(s) * s["held"][1] * (1.0 - (1.0 - s["top_k"] / s["num_experts"]) ** means["live_slots"])
    return 2.0 * expert_params(s) * rows, 2.0 * (touched * expert_params(s) + 2 * rows * s["d_model"])


def moe_decode_calls(s: dict, engine: dict) -> tuple[str, int]:
    return "decode_steps", engine.get("decode_chunk", 8)


# -- the routed FFN in a prefill chunk ---------------------------------------------------------------

def moe_prefill_operands(s: dict, engine: dict) -> str:
    return _bank(s)


def moe_prefill_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    """One prefill chunk, all routed layers: 6 D F operations a row that lands on a held expert
    (top_k x held / E of the chunk's rows a layer, UNDER EVEN ROUTING: no device counter exists for a
    chunk's rows or slabs, `held_expert_ffn`'s rows are dropped in the families' `_chunk`), every held
    expert's slabs once (a short chunk is bound by them; with 256 rows or more no held expert goes
    unchosen) and the rows in and out."""
    rows = means["prefill_rows_per_chunk"] * s["top_k"] * s["held"][1] / s["num_experts"]
    nbytes = 2.0 * n_routed(s) * (s["held"][1] * expert_params(s) + 2 * rows * s["d_model"])
    return 2.0 * expert_params(s) * rows * n_routed(s), nbytes


def moe_prefill_calls(s: dict, engine: dict) -> tuple[str, int]:
    return "prefill_chunk", 1


# -- decode attention: a window layer's ring and the full layers' pool ------------------------------

def attn_decode_operands(s: dict, engine: dict) -> str:
    """The operand a call reads tells its kind: the full layers' pool [Lf, pages, Hkv, page, dh] or
    the window layers' rings [Lw, slots, Hkv, ring, dh]."""
    hkv, dh, lw = s["kv_heads"], s["head_dim"], n_window(s)
    return (rf"\[({s['layers'] - lw},\d+,{hkv},{engine['page_len']},{dh}|{lw},{engine['slots']},{hkv},\d+,{dh})\]")


def attn_decode_call(s: dict, engine: dict, means: dict) -> tuple[float, float]:
    """One decode step, all layers: `visible_per_slot` is the mean over layers of what a step may read."""
    seen = means["live_slots"] * means["visible_per_slot"] * s["layers"]
    return 4.0 * s["heads"] * s["head_dim"] * seen, 2.0 * 2 * s["kv_heads"] * s["head_dim"] * seen


def attn_decode_calls(s: dict, engine: dict) -> tuple[str, int]:
    return "decode_steps", engine.get("decode_chunk", 8)
