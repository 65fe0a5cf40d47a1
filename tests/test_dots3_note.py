"""The dots3_note family on the CPU at a tiny size (`tiny-dots3-note`: hidden 64;
layers full, full, sliding, sliding, sliding, the first with a dense FFN; full
layers 4 heads of 16 + 8 over a latent of 16 with an indexer of 4 heads x 16 that
chooses 16 positions; sliding layers 2 heads of 24 + 8 over a latent of 32 under
a window of 9; 8 experts of which 4 held, top-2; float32): the program against the
family's plain reference, the absorbed form against the expanded one, the
indexer's choice against the reference's top-k, the share against the whole, the
latent pool and rings through the engine, the engines that were there as they
were, and the family's files through the benchmark's harness.

Tolerances: program and reference both compute in float32 here and differ in the
order of their sums only (online against whole softmax, absorbed against expanded
products, sorted rows against every expert masked): 2e-5 on logits of size 1-4 is
ten times what was seen (2e-6) and far below what one wrongly chosen position or
expert moves.
"""
import dataclasses
import hashlib
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
TINY, MAX_LEN, PAGE = "tiny-dots3-note", 128, 16
LOGIT_TOL = 2e-5
CELL = "dots3-note-prev.serve_notes"


@pytest.fixture(scope="module")
def tiny(bench, monkeypatch_module):
    monkeypatch_module.setenv("TONY_PALLAS_INTERPRET", "1")
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config(TINY), "serve-1chip")
    module, cfg = families.load("dots3_note").program(sizes, MAX_LEN)
    reference = families.reference(sizes)
    params = bench["chipside"].seed_weights(sizes, 7)
    ref = jax.jit(lambda p, t: reference.forward(p, t, sizes, "f32", t.shape[0]))

    def ref_logits(seq):
        """The reference's logits for every position of `seq`, padded at the end
        to one length (one compile; a causal model's positions do not see it)."""
        return np.asarray(ref(params, jnp.asarray(list(seq) + [0] * (MAX_LEN - len(seq)), jnp.int32)))[:len(seq)]

    return {"sizes": sizes, "module": module, "cfg": cfg, "reference": reference, "params": params,
            "ref_logits": ref_logits}


def _tokens(seed, n, vocab=256):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


# -- the program against the reference ----------------------------------------------------------
@pytest.fixture(scope="module")
def one_forward(tiny):
    seq = _tokens(64, 96)
    got = np.asarray(tiny["module"].forward(tiny["params"], jnp.asarray([seq + [0] * (MAX_LEN - 96)], jnp.int32), tiny["cfg"]))[0]
    return got[:96], tiny["ref_logits"](seq)


@pytest.mark.parametrize("rows", [(0, 9), (6, 20), (20, 96)], ids=["inside-window-and-topk", "across-their-edges", "past-both"])
def test_forward_agrees_with_the_reference(one_forward, rows):
    got, want = one_forward
    assert np.abs(got[rows[0]:rows[1]] - want[rows[0]:rows[1]]).max() < LOGIT_TOL
    assert np.abs(want[rows[0]:rows[1]]).max() > 0.5   # logits of size 1-4, not a row of zeros


def test_the_reference_computes_only_the_rows_its_last_rows_depend_on(tiny):
    """`forward` hands the head the last HEAD_ROWS rows, and `trunk(rows=...)` computes
    under them only what they read: the same rows as the whole computation, to the bit
    (same blocks, same operands), and the layers' first blocks as worked by hand."""
    R, sizes = tiny["reference"], tiny["sizes"]
    kinds = ["full_attention", "full_attention", "sliding_attention", "sliding_attention", "sliding_attention"]
    assert list(sizes["kinds"]) == kinds and sizes["window"] == 9
    # 128 positions in blocks of 8, the last 16 rows wanted: the last layer from block 14, each sliding
    # layer under it 8 rows earlier (window - 1, floored to a block), everything under a full layer whole
    assert R.first_blocks(kinds, 128, 8, 9, 16) == [0, 11, 12, 13, 14]
    assert R.first_blocks(kinds, 128, 8, 9, 128) == [0] * 5
    assert R.first_blocks(kinds[:1] + kinds[2:] + kinds[1:2], 128, 8, 9, 16) == [0, 0, 0, 0, 14]
    seq = jnp.asarray(_tokens(65, MAX_LEN), jnp.int32)
    whole, whole_slack = R.trunk(tiny["params"], seq, sizes, "f32", 8)
    tail, tail_slack = R.trunk(tiny["params"], seq, sizes, "f32", 8, rows=16)
    assert np.array_equal(np.asarray(tail)[-16:], np.asarray(whole)[-16:])
    assert np.array_equal(np.asarray(tail_slack)[-16:], np.asarray(whole_slack)[-16:])
    assert not np.array_equal(np.asarray(tail)[:80], np.asarray(whole)[:80])    # those rows were not computed


def _prefill(tiny, prompt, chunk):
    progs = tiny["module"].serving_programs(tiny["cfg"], "paged")
    staging, pos, logits = progs.init_staging(MAX_LEN), 0, None
    while pos < len(prompt):
        take = min(chunk, len(prompt) - pos)
        pad = progs.prefill_pad(take, chunk, MAX_LEN - pos) if pos + take >= len(prompt) else 0
        toks = jnp.asarray(prompt[pos:pos + take] + [0] * pad, jnp.int32)[None]
        logits, staging = progs.prefill_chunk(tiny["params"], toks, staging, take)
        pos += take
    return progs, staging, np.asarray(logits)[0]


def _admit(progs, staging, slots, slot, n_prompt):
    cache = progs.init_cache(slots, MAX_LEN, PAGE, slots * (MAX_LEN // PAGE) + 1)
    n_pages = MAX_LEN // PAGE
    row = np.arange(1 + slot * n_pages, 1 + (slot + 1) * n_pages).astype(np.int32)
    nc = -(-n_prompt // PAGE)
    fresh = np.zeros(n_pages, np.int32)
    fresh[:nc] = row[:nc]
    return progs.insert(cache, staging, fresh, row, jnp.int32(slot), jnp.int32(n_prompt), jnp.int32(0), jnp.int32(nc))


@pytest.mark.parametrize("prompt_len,chunk", [(5, 16), (12, 16), (37, 16), (77, 32)],
                         ids=["under-window-and-topk", "over-the-window-under-topk", "chunks-do-not-divide", "long-across-pages"])
def test_chunked_prefill_then_decode_agree_with_the_reference(tiny, prompt_len, chunk):
    """Logits, then their consequences step by step: the last prompt position
    from the chunked prefill (the expanded form under the indexer's mask and the
    band), then 12 decode steps through the latent pool, the index keys and the
    rings (the absorbed form over chosen rows), crossing the window (9), the
    indexer's 16 and a page's edge: each step's greedy token is the reference's
    argmax over its full forward of everything so far."""
    prompt = _tokens(prompt_len + chunk, prompt_len)
    progs, staging, last = _prefill(tiny, prompt, chunk)
    assert np.abs(last - tiny["ref_logits"](prompt)[-1]).max() < LOGIT_TOL
    slots, slot = 2, 1
    cache = _admit(progs, staging, slots, slot, prompt_len)
    seq, toks = list(prompt), jnp.zeros((slots,), jnp.int32).at[slot].set(int(np.argmax(last)))
    for _ in range(3):
        fed = int(toks[slot])
        toks, chunk_toks, cache, counts = progs.decode_chunk(tiny["params"], cache, toks, jax.random.PRNGKey(0), n=4,
                                                             temperature=0.0, top_k=0, samp=None)
        for j in range(4):
            seq.append(fed if j == 0 else int(chunk_toks[j - 1, slot]))
            want = tiny["ref_logits"](seq)[-1]
            assert want.max() - want[int(chunk_toks[j, slot])] < LOGIT_TOL
        # one live slot, 4 steps, 4 routed layers, top-2: the choices; the rows are those that landed on a held expert
        rows, rows_max, choices, touched = np.asarray(counts)
        assert touched == rows                    # a token's choices are distinct experts: one slot, one row an expert it touched
        assert choices == 4 * 4 * 2 and 0 < rows_max <= rows <= choices
    assert np.asarray(cache.lengths).tolist() == [0, prompt_len + 12]


# -- the two forms of latent attention, and the indexer's choice ---------------------------------
@pytest.fixture(scope="module")
def latent_case(tiny):
    """Random queries, latent rows and up-projections at widths none of which equals another."""
    H, T, Tk, r, dn, dr, dv, row = 4, 16, 64, 32, 24, 8, 16, 128
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    ckr = jnp.concatenate([jax.random.normal(ks[0], (Tk, r + dr)), jnp.zeros((Tk, row - r - dr))], -1)
    return {"qn": jax.random.normal(ks[1], (H, T, dn)), "qr": jax.random.normal(ks[2], (H, T, dr)), "ckr": ckr,
            "w_uk": jax.random.normal(ks[3], (H, r, dn)) * r ** -0.5, "w_uv": jax.random.normal(ks[4], (H, r, dv)) * r ** -0.5,
            "seen": jnp.asarray(np.random.default_rng(1).random((T, Tk)) < 0.3).at[:, 0].set(True), "r": r, "scale": (dn + dr) ** -0.5}


def test_the_expanded_kernel_agrees_with_plain_arrays(tiny, latent_case):
    """latent_prefill_attention over four tiles of keys and two of queries, two
    heads a group, tiles outside [first, last] neither fetched nor computed."""
    from tony_tpu.ops import latent_attention as LA

    c = latent_case
    seen = c["seen"].at[:8, 48:].set(False).at[8:, :16].set(False).at[8:, 16].set(True)     # query tile 0 sees tiles 0-2, tile 1 tiles 1-3
    want = np.asarray(LA.expanded_attention(c["qn"], c["qr"], c["ckr"], c["w_uk"], c["w_uv"], seen, scale=c["scale"]))
    got = LA.latent_prefill_attention(c["qn"], c["qr"], c["ckr"], c["w_uk"], c["w_uv"], LA.tile_major(seen.astype(jnp.int8), 16),
                                      jnp.asarray([0, 1]), jnp.asarray([2, 3]), scale=c["scale"], block_q=8, head_group=2)
    assert np.abs(np.asarray(got) - want).max() < 1e-5 and np.abs(want).max() > 0.1


def test_the_absorbed_form_equals_the_expanded_form(tiny, latent_case):
    """One query a slot: the query folded through W_uk against the latent rows,
    W_uv after the sum (latent_rows_attention, a block of rows with a validity a
    row and the chunk's own rows beside it) is the expanded form's keys and
    values built from the same rows (expanded_attention)."""
    from tony_tpu.ops import latent_attention as LA

    c = latent_case
    S, R, E = 3, 48, 16
    q_rows = [5, 9, 15]
    rows = jnp.stack([c["ckr"][:R]] * S)[None]                                    # every slot's block holds positions 0 .. 47
    extra = jnp.stack([c["ckr"][R:R + E]] * S)
    valid = jnp.asarray(np.random.default_rng(2).random((S, R)) < 0.5)
    extra_valid = jnp.asarray(np.random.default_rng(3).random((S, E)) < 0.5).at[:, 0].set(True)
    folded = jnp.einsum("htd,hrd->thr", c["qn"][:, q_rows], c["w_uk"])
    q = jnp.concatenate([folded, c["qr"][:, q_rows].transpose(1, 0, 2), jnp.zeros((S, 4, 128 - c["r"] - 8))], -1)
    o_lat = LA.latent_rows_attention(q, rows, jnp.int32(0), valid, extra, extra_valid, r=c["r"], scale=c["scale"])
    got = np.asarray(jnp.einsum("shr,hrd->hsd", o_lat, c["w_uv"]))
    seen = jnp.concatenate([valid, extra_valid], axis=1)
    for s, t in enumerate(q_rows):
        want = np.asarray(LA.expanded_attention(c["qn"][:, t:t + 1], c["qr"][:, t:t + 1], c["ckr"], c["w_uk"], c["w_uv"],
                                                seen[s:s + 1], scale=c["scale"]))[:, 0]
        assert np.abs(got[:, s] - want).max() < 1e-5 and np.abs(want).max() > 0.1


@pytest.fixture(scope="module")
def index_case(tiny):
    T, Tk, hi, di = 32, 96, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    # queries and keys lean one way: with 4 heads a score of exactly 0 (every head's product negative) would be
    # common and tie at the threshold; with the model's 64 heads it does not happen
    return {"qi": jax.random.normal(ks[0], (T, hi, di)) + 1.0, "w": jax.random.normal(ks[1], (T, hi)) * 0.1,
            "ki": jax.random.normal(ks[2], (Tk, di)) + 1.0, "T": T, "Tk": Tk}


@pytest.mark.parametrize("pos0,topk", [(0, 16), (40, 16), (64, 200)], ids=["under-topk-then-over", "over-topk", "topk-larger-than-any-context"])
def test_a_prefill_chunks_choice_is_the_references_top_k(tiny, index_case, pos0, topk):
    """index_scores_prefill -> index_select against the reference's mask (its
    index scores, `lax.top_k`'s threshold), for a chunk whose queries' contexts
    lie under and over `topk`. Where the score gap at the edge (the topk-th
    against the next) is under EDGE, a float32 sum in another order may fall
    either side, and the row is left out: at most two of 32 are."""
    from tony_tpu.ops import sparse_attention as SA

    EDGE = 1e-5
    c, reference = index_case, tiny["reference"]
    keys = SA.index_scores_prefill(c["qi"], c["w"], c["ki"], jnp.int32(pos0), block_q=8, block_k=32)
    scores = np.asarray(SA.key_scores(keys)).transpose(1, 0, 2).reshape(c["T"], c["Tk"])
    want_scores = np.asarray(reference.index_scores(c["qi"], c["w"], c["ki"], "f32"))
    qpos = pos0 + np.arange(c["T"])
    seen = np.arange(c["Tk"])[None, :] <= qpos[:, None]
    assert np.abs(np.where(seen, scores - want_scores, 0)).max() < 1e-5
    assert (np.asarray(keys).transpose(1, 0, 2).reshape(c["T"], c["Tk"])[~seen] == SA.KEY_MIN).all()
    mask = np.asarray(SA.index_select(keys, jnp.int32(pos0 + c["T"]), topk=topk, block_q=8))
    n_tiles = -(-(pos0 + c["T"]) // 32)
    got = mask.transpose(1, 0, 2)[:, :n_tiles].reshape(c["T"], n_tiles * 32) > 0
    want = np.asarray(reference.chosen_mask(jnp.asarray(want_scores), jnp.asarray(seen), topk))[:, :n_tiles * 32]
    ordered = np.sort(np.where(seen, want_scores, -np.inf), axis=1)[:, ::-1]
    clear = np.array([n <= topk or ordered[i, topk - 1] - ordered[i, topk] > EDGE for i, n in enumerate(seen.sum(1))])
    assert clear.sum() >= c["T"] - 3 and (got == want)[clear].all()
    assert (got.sum(1) == np.minimum(qpos + 1, topk)).all()


def test_a_decode_steps_choice_is_the_references_top_k(tiny, index_case):
    """index_scores_decode through a page table, `kth_largest_key` over pool and
    chunk together, `compact_chosen`: the positions a slot's step reads are the
    reference's top-k of its context, listed ascending, for a context under
    topk, one over it, and an idle slot."""
    from tony_tpu.ops import sparse_attention as SA

    c, reference, topk, page = index_case, tiny["reference"], 16, 16
    lens = np.array([10, 90, 0])
    table = np.array([[3, 0, 0, 0, 0, 0, 0, 0], [7, 1, 5, 2, 6, 4, 0, 0], [0] * 8], np.int32)
    pool = np.zeros((2, 8, page, 16), np.float32)
    for s, n in enumerate(lens):
        for p in range(n):
            pool[1, table[s, p // page], p % page] = np.asarray(c["ki"])[p]
    qi, w = c["qi"][:3], c["w"][:3]
    got = np.asarray(SA.index_scores_decode(qi, w, jnp.asarray(pool), jnp.int32(1), jnp.asarray(table), jnp.asarray(lens)))
    want = np.asarray(reference.index_scores(qi, w, c["ki"], "f32"))                                       # [3, 96]
    in_pool = np.arange(128)[None, :] < lens[:, None]
    assert np.abs(np.where(in_pool[:, :96], got[:, :96] - want, 0)).max() < 1e-5
    keys = jnp.where(jnp.asarray(in_pool), SA.order_keys(jnp.asarray(got)), SA.KEY_MIN)
    chosen = keys >= SA.kth_largest_key(keys, topk)
    idx, count = SA.compact_chosen(chosen, topk)
    assert np.asarray(count).tolist() == [10, 16, 0]
    for s, n in enumerate(lens):
        picked = np.sort(np.argsort(-want[s, :n], kind="stable")[:topk])
        assert np.asarray(idx)[s, :int(count[s])].tolist() == picked.tolist()


@pytest.mark.parametrize("k", [1, 7, 40], ids=["the-largest", "a-few", "more-than-there-are"])
def test_the_threshold_search_orders_negative_scores_too(k):
    """`kth_largest` reads entries that are not negative; index scores are sums
    of signed terms. `order_keys` orders all of float32, and `kth_largest_key`
    is the k-th largest of a row (KEY_MIN + 1, below every score, where the row
    has fewer than k readable entries)."""
    from tony_tpu.ops import sparse_attention as SA

    x = np.random.default_rng(5).normal(size=(6, 33)).astype(np.float32)
    x[0, :5] = [0.0, -0.0, 1e-30, -1e-30, -3e38]
    keys = SA.order_keys(jnp.asarray(x))
    assert (np.argsort(np.asarray(keys), axis=1, kind="stable") == np.argsort(x, axis=1, kind="stable"))[1:].all()
    assert np.array_equal(np.asarray(SA.key_scores(keys)), x)
    readable = jnp.asarray(np.arange(33)[None, :] < np.array([33, 33, 20, 8, 1, 33])[:, None])
    kth = np.asarray(SA.kth_largest_key(jnp.where(readable, keys, SA.KEY_MIN), k))[:, 0]
    for row in range(6):
        vals = np.sort(np.asarray(keys)[row][np.asarray(readable)[row]])[::-1]
        assert kth[row] == (vals[k - 1] if len(vals) >= k else SA.KEY_MIN + 1)


def test_compaction_lists_the_set_positions_without_a_sort():
    from tony_tpu.ops import sparse_attention as SA

    rng = np.random.default_rng(9)
    chosen = rng.random((5, 512)) < np.array([0.0, 0.01, 0.05, 0.12, 0.12])[:, None]
    chosen[4, :] = False
    chosen[4, [0, 127, 128, 511]] = True
    idx, count = SA.compact_chosen(jnp.asarray(chosen), 96)
    assert np.asarray(count).tolist() == chosen.sum(1).tolist() and chosen.sum(1).max() <= 96
    for s in range(5):
        assert np.asarray(idx)[s, :int(count[s])].tolist() == np.nonzero(chosen[s])[0].tolist()
    assert (np.asarray(idx) >= 0).all() and (np.asarray(idx) < 512).all()           # what lies past the count is still a position


# -- the share ------------------------------------------------------------------------------------
def test_the_eight_shares_add_up_to_the_whole_layer(bench, tiny):
    """Eight replicas hold one expert each of a routed layer's 8. Each routes
    over all 8, normalises over both chosen and adds its own expert's part; the
    shared expert is computed by all alike and counted once. Together: the uncut
    reference's layer output."""
    from tony_tpu.ops.layers import swiglu
    from tony_tpu.parallel.expert import held_expert_ffn

    sizes = {**tiny["sizes"], "held": (0, 8)}
    params = bench["chipside"].seed_weights(sizes, 13)
    reference = tiny["reference"]
    lp = reference.layer_params(params, 3, sizes)
    h = jax.random.normal(jax.random.PRNGKey(5), (24, sizes["d_model"]), jnp.float32)
    want = np.asarray(reference.routed_ffn_and_slack(h, lp, sizes)[0])
    parts, rows = [], []
    for first in range(8):
        moe = dataclasses.replace(tiny["cfg"], held=(first, 1)).moe
        banks = tuple(lp[k][None, first:first + 1] for k in ("we_gate", "we_up", "we_down"))
        y, r = held_expert_ffn(h, lp["router"], lp["router_bias"], *banks, jnp.int32(0), moe)
        parts.append(np.asarray(y))
        rows.append(int(np.asarray(r).sum()))
    shared = np.asarray(swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"]))
    assert np.abs(sum(parts) + shared - want).max() < 1e-5 and np.abs(want).max() > 0.3
    assert sum(rows) == 24 * 2 and sum(1 for p in parts if np.abs(p).max() > 0.01) >= 6   # every choice lands on one share


# -- the caches through the engine -------------------------------------------------------------------
def _engine(tiny, **kw):
    from tony_tpu.models.serving import ContinuousBatcher

    args = dict(num_slots=2, max_len=MAX_LEN, decode_chunk=4, kv="paged", page_len=PAGE, prefill_chunk=32)
    return ContinuousBatcher(tiny["params"], tiny["cfg"], **{**args, **kw})


def _greedy(tiny, prompt, n):
    seq, out = list(prompt), []
    for _ in range(n):
        out.append(int(np.argmax(tiny["ref_logits"](seq)[-1])))
        seq.append(out[-1])
    return out


def test_a_position_costs_one_row_and_a_window_layers_memory_does_not_grow(tiny):
    """The full layers' pool holds ONE row a position for all heads (the latent
    16 + the rope key 8, in whole lanes) and a 16-wide index key beside it; the
    three window layers keep a ring of 128 rows a slot at any max_len."""
    short, long = _engine(tiny, max_len=128), _engine(tiny, max_len=256)
    assert short.cache.ring.shape == long.cache.ring.shape == (3, 2, 128, 128)
    assert short.cache.c.shape == (2, 2 * 8 + 1, PAGE, 128) and short.cache.ki.shape == (2, 2 * 8 + 1, PAGE, 16)
    assert short.cache.c.shape[1] < long.cache.c.shape[1]                    # the full layers' pool does follow max_len
    cfg = tiny["cfg"]
    assert cfg.widths("full_attention").row == 128 and cfg.tail == 8
    per_head = 2 * cfg.n_heads * (cfg.nope + cfg.rope)                        # what keys and values a head would take
    assert cfg.kv_rank + cfg.rope + cfg.index_dim < per_head


def test_a_slot_used_again_reads_nothing_of_its_last_tenant(tiny):
    """The same request before and after other requests have been through both
    slots, and with every row of the pools and the rings set to 1e4 in between
    (a masked row's weight is exactly 0, so a large finite value shows a read):
    the same tokens, and they are the reference's greedy choice."""
    eng = _engine(tiny)
    prompt = _tokens(50, 50)
    first = eng.submit(prompt, 9)
    assert eng.run()[first] == _greedy(tiny, prompt, 9)
    churn = [eng.submit(_tokens(60 + i, n), 6) for i, n in enumerate((90, 41, 5, 33))]
    done = eng.run()
    assert all(len(done[r]) == 6 for r in churn)
    eng.cache = eng.cache._replace(**{f: jnp.full_like(getattr(eng.cache, f), 1e4) for f in ("c", "ki", "ring")})
    again = eng.submit(prompt, 9)
    assert eng.run()[again] == done[first]


def test_the_engine_counts_index_positions_expert_rows_and_visible_positions(tiny):
    from tony_tpu.obs import metrics as M

    def totals():
        out = {}
        for m in M.REGISTRY.snapshot():
            for s in m["samples"]:
                if "value" in s:
                    key = m["name"] + "".join(f"{{{v}}}" for v in s["labels"].values()) if m["name"].endswith("index_positions_total") else m["name"]
                    out[key] = out.get(key, 0) + s["value"]
        return out

    before = totals()
    eng = _engine(tiny)
    rid = eng.submit(_tokens(9, 40), 9)
    eng.run()
    delta = {k: v - before.get(k, 0) for k, v in totals().items()}
    contexts = np.arange(41, 49)                  # admission emits token 1; two chunks of 4 steps emit the rest
    assert delta["tony_serve_context_tokens_total"] == contexts.sum()
    # two layers in five read the indexer's 16, three the window's 9; the engine adds a chunk's sum as a whole number
    assert delta["tony_serve_visible_tokens_total"] == 2 * int((2 * 16 + 3 * 9) / 5 * 4)
    # the indexer scores every position in context, on both full layers; a prefill chunk the causal pairs of its rows
    assert delta["tony_serve_index_positions_total{decode}"] == 2 * contexts.sum()
    assert delta["tony_serve_index_positions_total{prefill}"] == 2 * (32 * 33 // 2 + 8 * 32 + 8 * 9 // 2)
    assert delta["tony_serve_expert_choices_total"] == 8 * 4 * 2
    assert 0 < delta["tony_serve_expert_rows_max_total"] <= delta["tony_serve_expert_rows_total"] <= 8 * 4 * 2
    assert delta["tony_serve_experts_touched_total"] == delta["tony_serve_expert_rows_total"]      # one slot: a row an expert
    assert len(eng.done[rid]) == 9


# -- the engines that were there, as they were ------------------------------------------------------
#: sha256 (16 hex) of the lowered text of each family's serving programs at its tiny configuration, taken on the
#: parent commit (22d7abf) by the code of `_lowered` below: this PR adds functions to models/paged_cache.py and
#: ops/sparse_attention.py and changes nothing those programs lower to. The two `decode_chunk`s that hold
#: `paged_decode_attention` (llama's, exaone_moe's) were taken again at PR 44, which changed that kernel's body; their
#: `prefill_chunk` and `insert`, and all three of minicpm_sala's (its decode reads listed pages: another call), did not move.
#: exaone_moe's `decode_chunk` was taken again at PR 48, whose chunk returns a fourth count (the held experts a row chose:
#: a3ffab437d62f6ac until then); its `prefill_chunk` did not move (float32 rows take `ragged_dot`, where no group is padded).
#: PR 54 counts a held expert's rows by one compare over the spans (`held_expert_ffn`: a binary search and a scatter until
#: then), so every `decode_chunk` of a family that holds part of its experts was taken again (exaone_moe's here:
#: 2839be267de17b5d until then); a prefill chunk drops the count, so no `prefill_chunk` moved, and llama's and minicpm_sala's three did not
PARENT_LOWERED = {
    "tiny-dense": {"prefill_chunk": "59bbb8e8694afa8f", "insert": "210285f3c6b88e0d", "decode_chunk": "dc951271c6e8cd2c"},
    "tiny-minicpm-sala": {"prefill_chunk": "06929982745c9738", "insert": "f871ff2b11f9c16d", "decode_chunk": "92bb0b1ff9f0801a"},
    "tiny-exaone-moe": {"prefill_chunk": "8fb0f36a0c7df860", "insert": "91cac576a663f3e7", "decode_chunk": "4e3acef181bcd9ab"},
}


def _lowered(bench, config, max_len, page, chunk):
    spec, families = bench["spec"], bench["families"]
    from tony_tpu.models.serving import programs_for

    sizes = spec.model_sizes(spec.config(config), "serve-1chip")
    _, cfg = families.load(sizes["module"]).program(sizes, max_len)
    params = jax.eval_shape(lambda: bench["chipside"].seed_weights(sizes, 7))
    progs, slots, n_pages = programs_for(cfg, "paged"), 2, max_len // page
    cache = jax.eval_shape(lambda: progs.init_cache(slots, max_len, page, slots * n_pages + 1))
    staging = jax.eval_shape(lambda: progs.init_staging(max_len))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    text = {
        "prefill_chunk": jax.jit(lambda p, t, st: progs.prefill_chunk(p, t, st, 5)).lower(params, i32(1, chunk), staging).as_text(),
        "insert": jax.jit(lambda c, st, f, r, s, n, j0, k: progs.insert(c, st, f, r, s, n, j0, k)).lower(
            cache, staging, i32(n_pages), i32(n_pages), i32(), i32(), i32(), i32()).as_text(),
        "decode_chunk": jax.jit(lambda p, c, t, k: progs.decode_chunk(p, c, t, k, n=4, temperature=0.0, top_k=0, samp=None)).lower(
            params, cache, i32(slots), jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text(),
    }
    return {k: hashlib.sha256(v.encode()).hexdigest()[:16] for k, v in text.items()}


@pytest.mark.parametrize("config,page", [("tiny-dense", 16), ("tiny-minicpm-sala", 8), ("tiny-exaone-moe", 16)],
                         ids=["llama", "minicpm_sala", "exaone_moe"])
def test_the_other_families_serving_programs_lower_to_the_parents_text(bench, monkeypatch, config, page):
    monkeypatch.setenv("TONY_PALLAS_INTERPRET", "1")
    assert _lowered(bench, config, 128, page, 32) == PARENT_LOWERED[config]


def test_a_replica_finds_the_familys_presets():
    from tony_tpu.models import dots3_note, registry

    presets = registry.presets()
    assert presets["dots3-note-tiny"] is dots3_note.PRESETS["dots3-note-tiny"]
    assert registry.module_of(presets["dots3-note-tiny"]) is dots3_note and {"tiny", "sala-tiny", "exaone-moe-tiny"} <= set(presets)
    cfg = dots3_note.Dots3NoteConfig()
    assert (cfg.n_layers, cfg.count("full_attention"), cfg.count("sliding_attention"), cfg.d_model) == (46, 13, 33, 5120)
    assert cfg.layer_types[:5] == ("full_attention",) * 2 + ("sliding_attention",) * 3 and cfg.window == 513
    full, swa = cfg.widths("full_attention"), cfg.widths("sliding_attention")
    assert (full.heads, full.kv_rank + full.rope, full.row, full.scale) == (128, 576, 640, 192 ** -0.5)
    assert (swa.heads, swa.kv_rank + swa.rope, swa.row, swa.scale) == (64, 1088, 1152, 256 ** -0.5) and cfg.tail == 512
    with pytest.raises(ValueError, match="held"):
        dots3_note.Dots3NoteConfig(held=(250, 16))
    with pytest.raises(ValueError, match="kv='paged'"):
        dots3_note.serving_programs(dots3_note.DOTS3_NOTE_TINY, "dense")
    params = jax.eval_shape(lambda: dots3_note.init(jax.random.PRNGKey(0), dots3_note.DOTS3_NOTE_TINY))
    assert set(params) == {"embed", "layers", "banks", "final_norm", "lm_head"} and "idx_wq" in params["layers"][1] and (
        "idx_wq" not in params["layers"][2])


# -- the family's files, through the harness -------------------------------------------------------
def test_the_family_resolves_sizes_program_reference_and_counts(bench):
    spec, families = bench["spec"], bench["families"]
    cfg = spec.config("dots3-note-prev")
    sizes = spec.model_sizes(cfg, "serve-1chip")
    assert (sizes["layers"], sizes["vocab"], sizes["d_model"], sizes["d_ff"], sizes["d_expert"]) == (5, 19008, 5120, 13824, 1536)
    assert (sizes["num_experts"], sizes["held"], sizes["top_k"], sizes["routed_scale"], sizes["shared_experts"]) == (256, (0, 32), 8, 1.0, 1)
    assert sizes["kinds"] == ("full_attention", "full_attention", "sliding_attention", "sliding_attention", "sliding_attention")
    assert (sizes["heads"], sizes["q_rank"], sizes["kv_rank"], sizes["nope"], sizes["rope"], sizes["v_dim"]) == (128, 1024, 512, 128, 64, 128)
    assert (sizes["swa_heads"], sizes["swa_kv_rank"], sizes["swa_nope"], sizes["window"], sizes["index_topk"]) == (64, 1024, 192, 513, 2048)
    source = spec.model_sizes(cfg, "source")
    assert (source["layers"], source["vocab"], source["held"]) == (46, 152064, (0, 256))
    assert source["kinds"].count("full_attention") == 13 and source["kinds"].count("sliding_attention") == 33
    hash(tuple(sorted(sizes.items())))                               # check.py keys its traced programs by the sizes
    fam = families.load("dots3_note")
    module, pcfg = fam.program(sizes, 67584)
    assert module.__name__ == "tony_tpu.models.dots3_note" and (pcfg.n_layers, pcfg.held, pcfg.moe.held, pcfg.dtype) == (
        5, (0, 32), (0, 32), "bfloat16")
    ref = families.reference(sizes)
    assert all(callable(getattr(ref, f)) for f in ("seed_key", "init_weights", "forward", "nll", "index_scores", "chosen_mask"))
    assert ref.CONTROL == "fp8" and ref.GRAD_LEAVES == () and "tony_tpu" not in open(ref.__file__).read().split('"""')[2]
    assert callable(fam.serve_install) and families.counts(sizes).__name__ == "families.dots3_note_counts"
    with pytest.raises(KeyError, match="serve-4chip"):
        spec.model_sizes(cfg, "serve-4chip")                         # a deployment the file lacks


@pytest.mark.parametrize("change,error,match", [
    (lambda c: {**c, "n_group": 8}, KeyError, "n_group"),
    (lambda c: {**c, "assumed": {k: v for k, v in c["assumed"].items() if k != "indexer"}}, KeyError, "indexer"),
    (lambda c: {**c, "assumed": {**c["assumed"], "window": {"value": "excludes_itself", "why": ""}}}, ValueError, "window"),
    (lambda c: {**c, "assumed": {**c["assumed"], "lora_rescale": {"value": "none", "why": ""}}}, ValueError, "lora_rescale"),
    (lambda c: {**c, "rope_scaling": {"type": "yarn", "factor": 8}}, ValueError, "rope_scaling"),
    (lambda c: {**c, "num_key_value_heads": 8}, ValueError, "key a head"),
    (lambda c: {**c, "layer_types": {**c["layer_types"], "serve-1chip": ["full_attention"] * 4}}, ValueError, "layer_types"),
], ids=["unknown-key", "missing-assumed", "another-window", "no-rescale", "scaled-rope", "grouped-keys", "kinds-not-the-depth"])
def test_the_family_refuses_what_it_does_not_know(bench, change, error, match):
    spec, families = bench["spec"], bench["families"]
    with pytest.raises(error, match=match):
        families.load("dots3_note").sizes(change(spec.config("dots3-note-prev")), "serve-1chip")


def test_a_checkout_without_the_model_module_has_no_such_family(bench, monkeypatch):
    """The benchmark's files laid over a parent commit: `sizes` raises NoFamily,
    which run.py turns into exit 2 before any launch."""
    spec, families = bench["spec"], bench["families"]
    fam = families.load("dots3_note")
    monkeypatch.setattr(fam, "PROGRAM", os.path.join(ROOT, "tony_tpu", "models", "no_such_model.py"))
    with pytest.raises(families.NoFamily, match="dots3_note"):
        fam.sizes(spec.config("dots3-note-prev"), "serve-1chip")


def test_the_counts_on_hand_worked_sizes(bench):
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config("dots3-note-prev"), "serve-1chip")
    C, engine = families.counts(sizes), spec.workload(CELL)["engine"]
    # ISSUE 43's arithmetic: a full layer's attention 144.1 M, a sliding layer's 90.8 M, an expert 23.6 M
    assert C.attention_params(sizes, "full_attention") == 144_048_128 and C.attention_params(sizes, "sliding_attention") == 90_832_896
    assert C.expert_params(sizes) == 3 * 5120 * 1536 == 23_592_960
    assert round(2 * C.total_params(sizes) / 1e9, 2) == 8.17
    means = {"live_slots": 30.0, "visible_per_slot": (2 * 2048 + 3 * 513) / 5, "context_per_slot": 36_000.0, "held_rows_per_step": 4 * 30.0,
             "prefill_rows_per_chunk": 2048.0, "sparse_chunk_share": 0.9, "index_positions_per_step": 2 * 30 * 36_000.0,
             "index_pairs_per_chunk": 2 * 2048 * 20_000.0}
    ops, nbytes = C.indexer_decode_call(sizes, engine, means)
    assert ops == 16384 * 2 * 30 * 36_000 and nbytes == 256 * 2 * 30 * 36_000                      # 16,384 operations a pair, 256 bytes a key
    ops, nbytes = C.latent_decode_call(sizes, engine, means)
    assert ops == 128 * (2 * 30 * 2048) * 1088 * 2 and nbytes == 1152 * 2 * 30 * 2048            # 1152 bytes a chosen row, once for all heads
    assert ops / nbytes == pytest.approx(241.8, abs=0.1)                                         # at the v5e's ridge (197e12 / 819e9 = 240.5)
    ops, nbytes = C.latent_ring_decode_call(sizes, engine, means)
    assert ops == 64 * (3 * 30 * 513) * 2112 * 2 and nbytes == 2176 * 3 * 30 * 513
    ops, _ = C.indexer_prefill_call(sizes, engine, means)
    assert ops == 16384 * 2 * 2048 * 20_000
    ops, _ = C.latent_prefill_call(sizes, engine, means)
    full = 0.9 * 2048 * 2048 + 0.1 * 2048 * 2049 / 2
    assert ops == pytest.approx(2 * full * 128 * 320 * 2 + 3 * 2048 * 513 * 64 * 384 * 2)
    ops, nbytes = C.moe_decode_call(sizes, engine, means)
    touched = 32 * (1 - (1 - 8 / 256) ** 30)
    assert ops == 2 * 23_592_960 * 120 and nbytes == pytest.approx(2 * (4 * touched * 23_592_960 + 2 * 120 * 5120))
    for kernel, program in (("indexer_decode", "decode_steps"), ("latent_decode", "decode_steps"), ("latent_ring_decode", "decode_steps"),
                            ("indexer_prefill", "prefill_chunk"), ("latent_prefill", "prefill_chunk")):
        assert getattr(C, kernel + "_calls")(sizes, engine)[0] == program
    pats = {k: re.compile(getattr(C, k + "_operands")(sizes, engine)) for k in (
        "indexer_decode", "latent_decode", "latent_ring_decode", "indexer_prefill", "latent_prefill", "moe_decode")}
    pages = engine["num_pages"]
    assert pats["indexer_decode"].search(f"bf16[2,{pages},1024,128]") and not pats["indexer_decode"].search(f"bf16[2,{pages},1024,640]")
    slots = engine["slots"]
    assert pats["latent_decode"].search(f"bf16[1,{slots},2048,640]") and not pats["latent_decode"].search(f"bf16[3,{slots},640,1152]")
    assert pats["latent_ring_decode"].search(f"bf16[3,{slots},640,1152]") and not pats["latent_ring_decode"].search(f"bf16[1,{slots},2048,640]")
    assert pats["indexer_prefill"].search("bf16[67584,128]") and not pats["indexer_prefill"].search("bf16[67584,640]")
    assert pats["latent_prefill"].search("bf16[67584,640]") and pats["latent_prefill"].search("bf16[2560,1152]")
    assert pats["moe_decode"].search("bf16[4,32,5120,1536]") and pats["moe_decode"].search("bf16[32,1536,5120]{2,1,0}")
    deltas = {"tony_serve_engine_chunks_total": 10, "tony_serve_decode_slots_total": 300, "tony_serve_visible_tokens_total": 300 * 8 * 1127,
              "tony_serve_context_tokens_total": 300 * 8 * 36_000, "tony_serve_expert_rows_total": 10 * 8 * 120,
              "tony_serve_prefill_tokens_total": 40960, "tony_serve_prefill_chunks_total": 20,
              "tony_serve_index_positions_total": 1000}
    got = C.window_means(lambda name, where=None: (18 if where == {"path": ["sparse"]} else 80 * 7 if where == {"phase": ["decode"]}
                                                   else 20 * 9 if where == {"phase": ["prefill"]} else deltas.get(name)), engine)
    assert got == {"live_slots": 30.0, "visible_per_slot": 1127.0, "context_per_slot": 36_000.0, "held_rows_per_step": 120.0,
                   "prefill_rows_per_chunk": 2048.0, "sparse_chunk_share": 0.9, "index_positions_per_step": 7.0, "index_pairs_per_chunk": 9.0}
    assert C.window_means(lambda name, where=None: None, engine) is None


def test_the_cells_entries_and_files(bench):
    spec = bench["spec"]
    B = spec.benchmark()
    entry = next(c for c in B["configs"] if c["name"] == "dots3-note-prev")
    assert entry["reduced"] == ["num_hidden_layers", "layer_types", "n_routed_experts", "vocab_size"]
    assert entry["source"] == "https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json"
    w_entry = next(e for e in B["workloads"] if e["name"] == CELL)
    assert w_entry["chips"] == 1 and w_entry["config"] == "dots3-note-prev"
    assert {m["name"] for m in spec.cell_metrics(B, CELL, "end_to_end")} == {"serve_out_tok_s", "setup_s"}
    per_layer = spec.cell_metrics(B, CELL, "per_layer")
    new = ["indexer_decode_roofline_pct.serve", "latent_decode_roofline_pct.serve", "latent_ring_decode_roofline_pct.serve",
           "indexer_prefill_roofline_pct.serve", "latent_prefill_roofline_pct.serve"]
    assert {m["name"] for m in per_layer} == set(new) | {
        "moe_decode_roofline_pct.serve", "moe_prefill_roofline_pct.serve", "expert_rows_max_over_mean.serve", "held_share_pct.serve",
        "launch_s", "slots_active_mean.serve", "host_share_pct.serve", "decode_batch_mean.serve", "visible_share_pct.serve",
        "decode_step_ms.serve_tput", "chunk_period_ms.serve_tput", "host_gap_pct.serve_tput", "host_offcpu_ms.serve_tput",
        "stream_write_ms.serve_tput", "fanout_delay_ms.serve_tput", "write_gap_pct.serve_tput",
        # PR 55: the start-up account and the compiles by source
        "submit_to_am_s", "allocate_s", "register_s", "runtime_init_s", "weights_s", "replica_warmup_s.serve",
        "setup_compile_s.serve", "setup_cache_load_s.serve", "setup_trace_lower_s.serve", "compile_ms_per_pass.serve",
        }
    # the five this cell brought stand together and start at the cell's name (a later latent family's cell joins the last)
    at = next(i for i, m in enumerate(B["per_layer"]) if m["name"] == new[0])
    brought = B["per_layer"][at:at + 5]
    assert [m["name"] for m in brought] == new and all(m["workloads"][0] == CELL for m in brought)
    assert all(CELL in m["workloads"] for m in per_layer)                             # appended to each list it joins
    assert all(spec.metric(m["name"])["moves"] == m["moves"] and spec.metric(m["name"])["reader"] == "family_roofline"
               for m in brought)
    w = spec.workload(CELL)
    eng = w["engine"]
    # 24 slots, not ISSUE 43's 32: at 32 the replica ran out of memory under a ramp of one request's length (PERF.md section 4)
    assert (eng["slots"], eng["max_len"], eng["prefill_chunk"], eng["decode_chunk"]) == (24, 67584, 2048, 8)
    assert eng["max_len"] % eng["page_len"] == 0 and w["traffic"]["arrivals"]["process"] == "closed" and (
        w["traffic"]["arrivals"]["clients"] == eng["slots"])
    p, a = w["traffic"]["prompt_len"], w["traffic"]["answer_len"]
    assert (p["median"], p["sigma"], p["min"], p["max"]) == (32768, 0.4, 16384, 65536) and p["min"] >= 8 * 2048
    assert (a["median"], a["sigma"], a["min"], a["max"]) == (1024, 0.3, 512, 2048) and p["max"] + a["max"] == eng["max_len"]
    assert w["traffic"]["draw_seed"] == 43 and w["check"]["samples"] == 1 and w["check"]["why"]
    cfg = spec.config("dots3-note-prev")
    published = {"hidden_size": 5120, "intermediate_size": 13824, "moe_intermediate_size": 1536, "num_attention_heads": 128,
                 "q_lora_rank": 1024, "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
                 "swa_num_attention_heads": 64, "swa_q_lora_rank": 1024, "swa_kv_lora_rank": 1024, "swa_qk_nope_head_dim": 192,
                 "swa_qk_rope_head_dim": 64, "swa_v_head_dim": 128, "sliding_window_size": 513, "index_n_heads": 64,
                 "index_head_dim": 128, "index_topk": 2048, "num_experts_per_tok": 8, "n_shared_experts": 1,
                 "routed_scaling_factor": 1, "rope_theta": 80000000, "swa_rope_theta": 50000, "first_k_dense_replace": 1,
                 "max_position_embeddings": 524288, "rope_scaling": None, "topk_method": "noaux_tc"}
    assert {k: cfg[k] for k in published} == published
    cut = {"num_hidden_layers": (46, 5), "n_routed_experts": (256, 32), "vocab_size": (152064, 19008)}
    assert {k: (cfg[k]["source"], cfg[k]["serve-1chip"]) for k in cut} == cut and sorted(cfg["reduced"]) == sorted(entry["reduced"])
    assert cfg["layer_types"]["serve-1chip"] == cfg["layer_types"]["source"][:5] and len(cfg["layer_types"]["source"]) == 46
    assert "8 chips" in cfg["deployments"]["serve-1chip"] and "pipeline stages" in cfg["deployments"]["serve-1chip"]
    assert all(set(v) == {"value", "why"} and v["why"] for k, v in cfg["assumed"].items() if isinstance(v, dict))


def test_the_rehearsal_cell_runs_end_to_end_on_the_cpu(tmp_path, bench, startup_account):
    """`tiny-dots3-note.serve` through run.py: the `tony serve` path, the router,
    the replica registered through the family's hook, chunked prefill and decode
    through the latent pool, the index keys and the rings under the interpreter,
    and the harness's own comparison with the reference: `correct`."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT, "HOME": str(tmp_path), "TMPDIR": str(tmp_path)}
    proc = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), "--workload", TINY + ".serve",
                           "--seed", str(2 ** 31 + 43), "--seconds", "3", "--trace", "0"],
                          cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 2, proc.stdout[-3000:]
    assert last["device"]["platform"] == "cpu" and "serve_out_tok_s" in last["metrics"]
    ctl = os.path.join(ROOT, ".bench_work", TINY + ".serve", "out", "ctl")
    names = {m["name"] for m in json.load(open(os.path.join(ctl, "snap.close.json")))["metrics"]}
    assert {"tony_serve_index_positions_total", "tony_serve_expert_rows_total", "tony_serve_visible_tokens_total"} <= names
    # PR 55: the same run's start-up by stage (its .jhist's stamps) and its compiles by source (snap0), read by the
    # listed cells' readers, and the window's compile time printed
    startup_account(bench["spec"], TINY + ".serve")
