"""The mistral4 family on the CPU at a tiny size (`tiny-mistral4`: hidden 64, three
layers, 4 heads of 8 + 16 over a latent of 16, YaRN factor 8 over an original
length of 32 so that a rotary pair is untouched, one blended and six divided, and
queries past position 31 are scaled; 8 experts of which 4 held, top-2 by softmax;
float32): the program against the family's plain reference, the paged absorbed
decode against the expanded form, the YaRN table and the query scale against
numbers worked by hand, the prefix-hit path against the miss path through the
engine, the shares against the whole, the engines that were there as they were,
and the family's files through the benchmark's harness.

Tolerances: program and reference both compute in float32 here and differ in the
order of their sums only (online against whole softmax, absorbed against expanded
products, sorted rows against every expert masked): 2e-5 on logits of size 1-4 is
ten times what was seen (2e-6) and far below what one wrongly chosen expert moves.
"""
import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
TINY, MAX_LEN, PAGE = "tiny-mistral4", 128, 16
LOGIT_TOL = 2e-5
CONFIG, CELL = "mistral-small-4-119b", "mistral-small-4-119b.serve_docqa"


@pytest.fixture(scope="module")
def tiny(bench, monkeypatch_module):
    monkeypatch_module.setenv("TONY_PALLAS_INTERPRET", "1")
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config(TINY), "serve-1chip")
    module, cfg = families.load("mistral4").program(sizes, MAX_LEN)
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


@pytest.mark.parametrize("rows", [(0, 32), (32, 64), (64, 96)], ids=["query-scale-1", "scale-ln2", "scale-ln3"])
def test_forward_agrees_with_the_reference(one_forward, rows):
    got, want = one_forward
    assert np.abs(got[rows[0]:rows[1]] - want[rows[0]:rows[1]]).max() < LOGIT_TOL
    assert np.abs(want[rows[0]:rows[1]]).max() > 0.5   # logits of size 1-4, not a row of zeros


def test_the_reference_computes_only_the_rows_its_last_rows_read(tiny):
    """`forward` hands the head the last HEAD_ROWS rows, and `trunk(rows=...)` has the last layer
    compute those rows' blocks only: the same rows as the whole computation, to the bit."""
    R, sizes = tiny["reference"], tiny["sizes"]
    seq = jnp.asarray(_tokens(65, MAX_LEN), jnp.int32)
    whole, whole_slack = R.trunk(tiny["params"], seq, sizes, "f32", 8)
    tail, tail_slack = R.trunk(tiny["params"], seq, sizes, "f32", 8, rows=16)
    assert np.array_equal(np.asarray(tail)[-16:], np.asarray(whole)[-16:])
    assert np.array_equal(np.asarray(tail_slack)[-16:], np.asarray(whole_slack)[-16:])
    assert not np.array_equal(np.asarray(tail)[:80], np.asarray(whole)[:80])    # the last layer did not compute those


def _prefill(tiny, prompt, chunk, staging=None, pos=0):
    progs = tiny["module"].serving_programs(tiny["cfg"], "paged")
    staging, logits = progs.init_staging(MAX_LEN) if staging is None else staging, None
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


@pytest.mark.parametrize("prompt_len,chunk", [(5, 16), (29, 16), (37, 16), (77, 32)],
                         ids=["one-short-chunk", "into-the-scaled-queries", "chunks-do-not-divide", "long-across-pages"])
def test_chunked_prefill_then_decode_agree_with_the_reference(tiny, prompt_len, chunk):
    """Logits, then their consequences step by step: the last prompt position
    from the chunked prefill (the expanded form under the causal mask), then 12
    decode steps through the paged latent pool (the absorbed form over every
    row of the context, the chunk's own rows beside the pages'), crossing a
    page's edge and position 32, where the query's scale leaves 1: each step's
    greedy token is the reference's argmax over its full forward of everything
    so far."""
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
        # one live slot, 4 steps, 3 layers, top-2: the choices; the rows are those that landed on a held expert
        rows, rows_max, choices, touched = np.asarray(counts)
        assert touched == rows                    # a token's choices are distinct experts: one slot, one row an expert it touched
        assert choices == 4 * 3 * 2 and 0 < rows_max <= rows <= choices
    assert np.asarray(cache.lengths).tolist() == [0, prompt_len + 12]


# -- the paged absorbed decode against the expanded form ------------------------------------------
@pytest.mark.parametrize("lengths,step", [((40, 0, 33, 16), 0), ((47, 5, 32, 1), 3), ((64, 17, 0, 48), 7)],
                         ids=["partial-last-pages-an-idle-slot", "staged-rows-beside-the-pages", "whole-pages-a-full-chunk"])
def test_the_paged_absorbed_decode_equals_the_expanded_form(tiny, lengths, step):
    """One query a slot over the slot's pages of a pool of two layers (pages out
    of order, a partial last page, a slot with nothing in the pool between
    slots that hand a fetch on) and the chunk's own rows up to `step`: the
    query folded through W_uk against the latent rows, W_uv after the sum, is
    the expanded form's keys and values built from the same rows."""
    from tony_tpu.ops import latent_attention as LA

    H, r, dn, dr, dv, row, page, E = 4, 32, 24, 8, 16, 128, 16, 128
    S, max_pages = len(lengths), 4
    ks = jax.random.split(jax.random.PRNGKey(3), 7)
    pool = jnp.concatenate([jax.random.normal(ks[0], (2, 1 + S * max_pages, page, r + dr)),
                            jnp.full((2, 1 + S * max_pages, page, row - r - dr), 7.0)], -1)   # the filling is never read: q is 0 there
    table = jnp.asarray(np.random.default_rng(4).permutation(S * max_pages).reshape(S, max_pages) + 1, jnp.int32)
    extra = jnp.concatenate([jax.random.normal(ks[1], (S, E, r + dr)), jnp.zeros((S, E, row - r - dr))], -1)
    qn, qr = jax.random.normal(ks[2], (H, S, dn)), jax.random.normal(ks[3], (H, S, dr))
    w_uk, w_uv = jax.random.normal(ks[4], (H, r, dn)) * r ** -0.5, jax.random.normal(ks[5], (H, r, dv)) * r ** -0.5
    q = jnp.concatenate([jnp.einsum("hsd,hrd->shr", qn, w_uk), qr.transpose(1, 0, 2), jnp.zeros((S, H, row - r - dr))], -1)
    scale, layer = (dn + dr) ** -0.5 * 2.2, 1
    o_lat = LA.latent_paged_decode(q, pool, jnp.int32(layer), jnp.asarray(lengths, jnp.int32), table, extra, jnp.int32(step), r=r, scale=scale)
    got = np.asarray(jnp.einsum("shr,hrd->hsd", o_lat, w_uv))
    for s, n in enumerate(lengths):
        rows = jnp.concatenate([pool[layer, table[s]].reshape(max_pages * page, row)[:n], extra[s, :step + 1]])
        want = np.asarray(LA.expanded_attention(qn[:, s:s + 1], qr[:, s:s + 1], rows, w_uk, w_uv, jnp.ones((1, rows.shape[0]), bool),
                                                scale=scale))[:, 0]
        assert np.abs(got[:, s] - want).max() < 1e-5 and np.abs(want).max() > 0.05


# -- the rope: YaRN's table and the query's scale, by hand -----------------------------------------
def test_the_yarn_table_at_the_published_numbers(bench):
    """low 12 and high 25 of 32 pairs for beta_fast 32, beta_slow 1 over 8192
    positions at theta 1e4: pairs 0-12 keep their frequency, 13-24 are blended
    along the ramp, 25-31 are divided by 128; cos and sin carry no factor
    (mscale = mscale_all_dim). The program's table (ops/layers.rope_frequencies,
    kind "yarn") and the reference's `yarn_inv_freq` agree."""
    from tony_tpu.ops import layers as L

    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config(CONFIG), "serve-1chip")
    R = families.reference(sizes)
    assert L.yarn_correction_range(64, 1e4, 32, 1, 8192) == R.yarn_range(sizes) == (12, 25)
    f = 1e4 ** (-np.arange(0, 64, 2) / 64)
    inv = np.asarray(R.yarn_inv_freq(sizes), np.float64)
    assert np.allclose(inv[:13], f[:13], rtol=1e-6) and np.allclose(inv[25:], f[25:] / 128, rtol=1e-6)
    ramp = (np.arange(13, 25) - 12) / 13
    assert np.allclose(inv[13:25], f[13:25] * (1 - ramp) + f[13:25] / 128 * ramp, rtol=1e-6)
    cos, sin = L.rope_frequencies(64, 9000, 1e4, ("yarn", 128.0, 32.0, 1.0, 8192, 1.0, 1.0))
    for p in (1, 777, 8999):
        assert np.allclose(np.asarray(cos[p]), np.cos(p * inv), atol=2e-3) and np.allclose(np.asarray(sin[p]), np.sin(p * inv), atol=2e-3)
    assert np.allclose(np.asarray(cos[5]), np.cos(5 * inv), atol=1e-6)
    # a factor on cos and sin where mscale and mscale_all_dim differ: mscale(128, 1) / mscale(128, 0.5)
    scaled, _ = L.rope_frequencies(64, 4, 1e4, ("yarn", 128.0, 32.0, 1.0, 8192, 1.0, 0.5))
    assert np.allclose(np.asarray(scaled[0]), (0.1 * math.log(128) + 1) / (0.05 * math.log(128) + 1))
    with pytest.raises(ValueError, match="yarn"):
        L.rope_frequencies(64, 4, 1e4, ("ntk", 2.0))


def test_the_tiny_table_has_all_three_bands(tiny):
    R, sizes = tiny["reference"], tiny["sizes"]
    assert R.yarn_range(sizes) == (0, 2)
    f = 1e4 ** (-np.arange(0, 16, 2) / 16)
    assert np.allclose(np.asarray(R.yarn_inv_freq(sizes)), np.concatenate([f[:1], f[1:2] * (0.5 + 0.5 / 8), f[2:] / 8]), rtol=1e-6)


def test_the_query_scale_below_and_past_the_original_length(bench, tiny):
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config(CONFIG), "serve-1chip")
    _, cfg = families.load("mistral4").program(sizes, 35840)
    pos = jnp.asarray([0, 8191, 8192, 16383, 16384, 33791])
    want = [1.0, 1.0, 1 + 0.1 * math.log(2), 1 + 0.1 * math.log(2), 1 + 0.1 * math.log(3), 1 + 0.1 * math.log(5)]
    assert np.allclose(np.asarray(tiny["module"].query_scale(pos, cfg)), want, rtol=1e-6)
    assert np.allclose(np.asarray(families.reference(sizes)._query_scale(pos, sizes)), want, rtol=1e-6)
    assert cfg.scale == pytest.approx(128 ** -0.5 * (0.1 * math.log(128) + 1) ** 2) and cfg.row == 384
    assert families.reference(sizes)._score_scale(sizes) == pytest.approx(cfg.scale)


# -- the share ------------------------------------------------------------------------------------
def test_the_four_shares_add_up_to_the_whole_layer(bench, tiny):
    """Four replicas hold two experts each of a layer's 8. Each routes over all
    8 (softmax, top-2, renormalised) and adds its own experts' part; the shared
    expert is computed by all alike and counted once. Together: the uncut
    reference's layer output."""
    from tony_tpu.ops.layers import swiglu
    from tony_tpu.parallel.expert import held_expert_ffn

    sizes = {**tiny["sizes"], "held": (0, 8)}
    params = bench["chipside"].seed_weights(sizes, 13)
    reference = tiny["reference"]
    lp = reference.layer_params(params, 1)
    h = jax.random.normal(jax.random.PRNGKey(5), (24, sizes["d_model"]), jnp.float32)
    want = np.asarray(reference.routed_ffn_and_slack(h, lp, sizes)[0])
    parts, rows = [], []
    for first in range(0, 8, 2):
        moe = dataclasses.replace(tiny["cfg"], held=(first, 2)).moe
        banks = tuple(lp[k][None, first:first + 2] for k in ("we_gate", "we_up", "we_down"))
        y, r = held_expert_ffn(h, lp["router"], None, *banks, jnp.int32(0), moe)
        parts.append(np.asarray(y))
        rows.append(int(np.asarray(r).sum()))
        alone = np.asarray(reference.routed_ffn_and_slack(h, {**lp, **{k: lp[k][first:first + 2] for k in ("we_gate", "we_up", "we_down")}},
                                                          sizes, held=(first, 2), shared=False)[0])
        assert np.abs(parts[-1] - alone).max() < 1e-5                      # a share is the reference's share
    shared = np.asarray(swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"]))
    assert np.abs(sum(parts) + shared - want).max() < 1e-5 and np.abs(want).max() > 0.3
    assert sum(rows) == 24 * 2 and all(np.abs(p).max() > 0.01 for p in parts)   # every choice lands on one share


def test_the_softmax_router_is_multiplied_in_float32_where_a_share_is_held():
    """bfloat16 activations against a float32 router: a layer that holds a share of its experts
    (serving) multiplies at full precision, as the sigmoid branch does; a layer that holds them all
    (mixtral's training path) lowers to what it did."""
    from tony_tpu.parallel.expert import MoEConfig, _gating

    x = jax.ShapeDtypeStruct((1, 8, 64), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((64, 8), jnp.float32)
    text = lambda held: jax.jit(lambda x, w: _gating(x, w, MoEConfig(num_experts=8, top_k=2, held=held))[:2]).lower(x, w).as_text()
    assert "HIGHEST" in text((0, 4)) and "HIGHEST" not in text(None)


# -- the prefix-hit path against the miss path, through the engine ---------------------------------
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


def _counters():
    from tony_tpu.obs import metrics as M

    out = {}
    for m in M.REGISTRY.snapshot():
        for s in m["samples"]:
            if "value" in s:
                out[m["name"] + "".join(f"{{{v}}}" for v in s["labels"].values())] = s["value"]
    return out


def test_a_prefix_hit_gives_the_miss_paths_tokens_and_logits(tiny):
    """A document of three whole pages and a question, then the same document
    under another question: the second request attaches the document's pages
    (`gather_prefix` copies them into its staging, the allocator pins them, its
    insert writes only the pages after them) and prefills the question alone.
    Its tokens are the reference's greedy choice, as the miss path's are; the
    logits of its last prompt row are those of a prefill of the whole prompt."""
    document, q1, q2 = _tokens(70, 3 * PAGE), _tokens(71, 9), _tokens(72, 21)
    before = _counters()
    eng = _engine(tiny)
    first = eng.submit(document + q1, 9)
    assert eng.run()[first] == _greedy(tiny, document + q1, 9) and eng.prefix_hit_tokens == 0
    second = eng.submit(document + q2, 9)
    assert eng.run()[second] == _greedy(tiny, document + q2, 9) and eng.prefix_hit_tokens == 3 * PAGE
    again = eng.submit(document + q1, 9)                                     # the first request once more: now a hit
    assert eng.run()[again] == eng.done[first] and eng.prefix_hit_tokens == 6 * PAGE
    delta = {k: v - before.get(k, 0) for k, v in _counters().items()}
    assert delta["tony_serve_pages_total{shared}"] == 6 and delta["tony_serve_pages_total{fresh}"] == 4 + 2 + 1
    # the miss: chunks of 32 and 25 rows; the hits: one chunk each of 21 and 9 rows after 48 positions
    pairs = lambda pos, take: take * pos + take * (take + 1) // 2
    assert delta["tony_serve_prefill_pairs_total"] == pairs(0, 32) + pairs(32, 25) + pairs(48, 21) + pairs(48, 9)
    # the logits of the last prompt row: a gather of the cached pages and one chunk, against the whole prompt prefilled
    progs, whole, miss = _prefill(tiny, document + q2, 32)
    pages = np.zeros(MAX_LEN // PAGE, np.int32)
    cache = _admit(progs, whole, 2, 0, len(document + q2))
    pages[:3] = np.asarray(cache.page_table)[0, :3]
    gathered = progs.gather_prefix(progs.init_staging(MAX_LEN), cache, jnp.asarray(pages[:3]), 3)
    assert int(gathered.length) == 3 * PAGE and np.array_equal(np.asarray(gathered.c)[:, :3 * PAGE], np.asarray(whole.c)[:, :3 * PAGE])
    _, _, hit = _prefill(tiny, document + q2, 32, staging=gathered, pos=3 * PAGE)
    assert np.abs(hit - miss).max() < LOGIT_TOL and np.abs(miss - tiny["ref_logits"](document + q2)[-1]).max() < LOGIT_TOL


def test_a_slot_used_again_reads_nothing_of_its_last_tenant(tiny):
    """The same request before and after other requests have been through both
    slots, with every row of the pool set to 1e4 in between (a masked row's
    weight is exactly 0, so a large finite value shows a read; the prompt has no
    whole page, so nothing of it is shared): the same tokens."""
    eng = _engine(tiny)
    prompt = _tokens(50, 13)
    first = eng.submit(prompt, 9)
    assert eng.run()[first] == _greedy(tiny, prompt, 9)
    churn = [eng.submit(_tokens(60 + i, n), 6) for i, n in enumerate((90, 41, 5, 33))]
    done = eng.run()
    assert all(len(done[r]) == 6 for r in churn)
    eng.cache = eng.cache._replace(c=jnp.full_like(eng.cache.c, 1e4))
    again = eng.submit(prompt, 9)
    assert eng.run()[again] == done[first]


def test_the_engine_counts_context_and_expert_rows(tiny):
    before = _counters()
    eng = _engine(tiny)
    rid = eng.submit(_tokens(9, 40), 9)
    eng.run()
    delta = {k: v - before.get(k, 0) for k, v in _counters().items()}
    contexts = np.arange(41, 49)                  # admission emits token 1; two chunks of 4 steps emit the rest
    assert delta["tony_serve_context_tokens_total"] == delta["tony_serve_visible_tokens_total"] == contexts.sum()
    assert delta["tony_serve_expert_choices_total"] == 8 * 3 * 2
    assert 0 < delta["tony_serve_expert_rows_max_total"] <= delta["tony_serve_expert_rows_total"] <= 8 * 3 * 2
    assert delta["tony_serve_experts_touched_total"] == delta["tony_serve_expert_rows_total"]      # one slot: a row an expert
    assert len(eng.done[rid]) == 9 and eng.cache.c.shape == (3, 2 * 8 + 1, PAGE, 128)


# -- the engines that were there, as they were ------------------------------------------------------
#: sha256 (16 hex) of the lowered text of `tiny-dots3-note`'s serving programs, taken on the parent commit (5f9950a) by
#: `tests/test_dots3_note._lowered`; the three families older than it are held by that file's own table, whose hashes
#: this PR found as they stood. This PR edits three files those programs import (ops/layers.py: a "yarn" kind of
#: `rope_frequencies`; models/paged_cache.py: `gather_latent_prefix`; parallel/expert.py: `_gating`'s router product
#: where a softmax layer holds a share) and ops/latent_attention.py (`latent_paged_decode`), and changes nothing any of
#: the four lowers to. PR 47 names the two products of `ops/layers.swiglu` (`checkpoint_name`: the remat ladder), which
#: this family's dense and shared FFNs call in prefill: a name lowers to nothing, but the lowering's count of private
#: functions moves by one behind it (`@silu_320` -> `@silu_321`; 80 such lines in prefill, 132 in decode), so until then
#: `prefill_chunk` read c2d5483b10cb7019 and `decode_chunk` 343d101c9f873ea1; with the `_<n>` of every `@name_<n>` taken
#: off, both texts are the parent's (aad441a), line for line. PR 48 adds a fourth count to what `decode_chunk` returns (the
#: held experts a row chose): 2ee4b1e328fd61e1 until then; `prefill_chunk` and `insert` did not move. PR 54 counts a held
#: expert's rows by one compare (tests/test_dots3_note.py's note): `decode_chunk` f1633cc74368550d until then
PARENT_LOWERED_DOTS3 = {"prefill_chunk": "87fd180e4f475ab0", "insert": "9a9d6ec16fc97060", "decode_chunk": "5e5855861908355f"}


def test_the_latent_family_before_this_one_lowers_to_the_parents_text(bench, monkeypatch):
    from tests.test_dots3_note import _lowered

    monkeypatch.setenv("TONY_PALLAS_INTERPRET", "1")
    assert _lowered(bench, "tiny-dots3-note", 128, 16, 32) == PARENT_LOWERED_DOTS3


def test_a_replica_finds_the_familys_presets():
    from tony_tpu.models import mistral4, registry

    presets = registry.presets()
    assert presets["mistral4-tiny"] is mistral4.PRESETS["mistral4-tiny"]
    assert registry.module_of(presets["mistral4-tiny"]) is mistral4 and {"tiny", "sala-tiny", "exaone-moe-tiny", "dots3-note-tiny"} <= set(presets)
    cfg = mistral4.Mistral4Config()
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.kv_rank + cfg.rope, cfg.row, cfg.moe.scoring) == (36, 4096, 32, 320, 384, "softmax")
    with pytest.raises(ValueError, match="held"):
        mistral4.Mistral4Config(held=(120, 16))
    with pytest.raises(ValueError, match="kv='paged'"):
        mistral4.serving_programs(mistral4.MISTRAL4_TINY, "dense")
    params = jax.eval_shape(lambda: mistral4.init(jax.random.PRNGKey(0), mistral4.MISTRAL4_TINY))
    assert set(params) == {"embed", "layers", "final_norm", "lm_head"} and params["layers"]["we_gate"].shape == (3, 4, 64, 32)
    progs = mistral4.serving_programs(mistral4.MISTRAL4_TINY, "paged")
    assert progs.gather_prefix is not None
    # a last chunk: a power of two, never under 32 rows, never past the chunk or the room
    assert [progs.prefill_pad(t, 2048, 4096) + t for t in (5, 64, 65, 1000, 2048)] == [32, 64, 128, 1024, 2048]
    assert progs.prefill_pad(5, 2048, 20) == 15


# -- the family's files, through the harness -------------------------------------------------------
def test_the_family_resolves_sizes_program_reference_and_counts(bench):
    spec, families = bench["spec"], bench["families"]
    cfg = spec.config(CONFIG)
    sizes = spec.model_sizes(cfg, "serve-1chip")
    assert (sizes["layers"], sizes["vocab"], sizes["d_model"], sizes["d_expert"], sizes["dense_layers"]) == (5, 32768, 4096, 2048, 0)
    assert (sizes["num_experts"], sizes["held"], sizes["top_k"], sizes["shared_experts"]) == (128, (0, 32), 4, 1)
    assert (sizes["heads"], sizes["q_rank"], sizes["kv_rank"], sizes["nope"], sizes["rope"], sizes["v_dim"]) == (32, 1024, 256, 64, 64, 128)
    assert sizes["yarn"] == (128.0, 32.0, 1.0, 8192, 1.0, 1.0) and sizes["query_scale_beta"] == 0.1 and sizes["norm_eps"] == 1e-6
    source = spec.model_sizes(cfg, "source")
    assert (source["layers"], source["vocab"], source["held"]) == (36, 131072, (0, 128))
    hash(tuple(sorted(sizes.items())))                               # check.py keys its traced programs by the sizes
    fam = families.load("mistral4")
    module, pcfg = fam.program(sizes, 35840)
    assert module.__name__ == "tony_tpu.models.mistral4" and (pcfg.n_layers, pcfg.held, pcfg.moe.held, pcfg.dtype) == (
        5, (0, 32), (0, 32), "bfloat16")
    ref = families.reference(sizes)
    assert all(callable(getattr(ref, f)) for f in ("seed_key", "init_weights", "forward", "nll", "yarn_inv_freq", "routed_ffn_and_slack"))
    assert ref.CONTROL == "fp8" and ref.GRAD_LEAVES == () and "tony_tpu" not in open(ref.__file__).read().split('"""')[2]
    from families import exaone_moe_reference
    assert ref.TIE_MARGIN is exaone_moe_reference.TIE_MARGIN         # imported, not copied
    assert callable(fam.serve_install) and families.counts(sizes).__name__ == "families.mistral4_counts"
    with pytest.raises(KeyError, match="serve-4chip"):
        spec.model_sizes(cfg, "serve-4chip")                         # a deployment the file lacks


def test_the_configuration_holds_every_published_key_at_its_published_value(bench):
    import json

    spec = bench["spec"]
    cfg = spec.config(CONFIG)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Mistral-Small-4-119B-2603")
    assert cfg["source"] == row["source_url"]
    reduced = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert set(cfg["reduced"]) == reduced
    for key, value in row["config"].items():
        assert (cfg[key]["source"] if key in reduced else cfg[key]) == value, key


@pytest.mark.parametrize("change,error,match", [
    (lambda c: {**c, "scoring_func": "sigmoid"}, KeyError, "scoring_func"),
    (lambda c: {**c, "n_group": 4}, ValueError, "n_group"),
    (lambda c: {**c, "sliding_window": 4096}, ValueError, "sliding_window"),
    (lambda c: {**c, "first_k_dense_replace": 1}, ValueError, "first_k_dense_replace"),
    (lambda c: {**c, "routed_scaling_factor": 2.5}, ValueError, "routed_scaling_factor"),
    (lambda c: {**c, "rope_interleave": False}, ValueError, "rope_interleave"),
    (lambda c: {**c, "assumed": {k: v for k, v in c["assumed"].items() if k != "query_scale"}}, KeyError, "query_scale"),
    (lambda c: {**c, "assumed": {**c["assumed"], "router_scoring": {"value": "sigmoid_plus_bias", "why": ""}}}, ValueError, "router_scoring"),
    (lambda c: {**c, "assumed": {**c["assumed"], "softmax_scale": {"value": "inverse_sqrt_qk_head_dim", "why": ""}}}, ValueError, "softmax_scale"),
    (lambda c: {**c, "rope_parameters": {**c["rope_parameters"], "rope_type": "llama3", "type": "llama3"}}, ValueError, "YaRN"),
    (lambda c: {**c, "num_key_value_heads": 8}, ValueError, "key a head"),
], ids=["unknown-key", "grouped-routing", "a-window", "a-dense-layer", "a-routed-scale", "rotate-half", "missing-assumed",
        "another-scoring", "no-mscale", "another-rope", "grouped-keys"])
def test_the_family_refuses_what_it_does_not_know(bench, change, error, match):
    spec, families = bench["spec"], bench["families"]
    with pytest.raises(error, match=match):
        families.load("mistral4").sizes(change(spec.config(CONFIG)), "serve-1chip")


def test_a_checkout_without_the_model_module_has_no_such_family(bench, monkeypatch):
    """The benchmark's files laid over a parent commit: `sizes` raises NoFamily,
    which run.py turns into exit 2 before any launch."""
    spec, families = bench["spec"], bench["families"]
    fam = families.load("mistral4")
    monkeypatch.setattr(fam, "PROGRAM", os.path.join(ROOT, "tony_tpu", "models", "no_such_model.py"))
    with pytest.raises(families.NoFamily, match="mistral4"):
        fam.sizes(spec.config(CONFIG), "serve-1chip")


def test_the_counts_on_hand_worked_sizes(bench):
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config(CONFIG), "serve-1chip")
    C, engine = families.counts(sizes), spec.workload(CELL)["engine"]
    # ISSUE 46's arithmetic: a layer's attention 28.05 M, an expert 25.17 M, a layer outside its routed experts 53.75 M
    assert C.attention_params(sizes) == 28_049_408 and C.expert_params(sizes) == 25_165_824
    assert C.attention_params(sizes) + C.expert_params(sizes) + 4096 * 128 == 53_739_520
    assert round(2 * C.total_params(sizes) / 1e9, 2) == 9.13
    means = {"live_slots": 64.0, "context_per_slot": 33_000.0, "held_rows_per_step": 5 * 64.0, "prefill_rows_per_chunk": 256.0,
             "prefill_pairs_per_chunk": 256 * 32_768 + 256 * 257 / 2}
    ops, nbytes = C.latent_paged_decode_call(sizes, engine, means)
    rows = 64 * 33_000 * 5
    assert ops == 2 * 32 * rows * (384 + 256) and nbytes == 768 * rows                            # a row as laid out, once for all heads
    assert ops / nbytes == pytest.approx(53.3, abs=0.1)                                            # under the v5e's ridge of 240: the HBM bounds it
    ops, nbytes = C.latent_prefill_call(sizes, engine, means)
    assert ops == 5 * 2 * 32 * 256 * (256 * 32_768 + 256 * 257 / 2)
    ops, nbytes = C.moe_decode_call(sizes, engine, means)
    touched = 32 * (1 - (1 - 4 / 128) ** 64)
    assert ops == 2 * 25_165_824 * 320 and nbytes == pytest.approx(2 * (5 * touched * 25_165_824 + 2 * 320 * 4096))
    import re
    assert re.search(C.latent_paged_decode_operands(sizes, engine), "bf16[5,577,1024,384]") and not re.search(
        C.latent_paged_decode_operands(sizes, engine), "bf16[35840,384]")
    assert re.search(C.latent_prefill_operands(sizes, engine), "bf16[35840,384]")
    assert C.latent_paged_decode_calls(sizes, engine) == ("decode_steps", 8) and C.latent_prefill_calls(sizes, engine) == ("prefill_chunk", 1)


def test_window_means_from_the_replicas_counters(bench):
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config(CONFIG), "serve-1chip")
    C = families.counts(sizes)
    moved = {"tony_serve_engine_chunks_total": 10, "tony_serve_decode_slots_total": 600, "tony_serve_context_tokens_total": 600 * 8 * 33_000,
             "tony_serve_expert_rows_total": 10 * 8 * 320, "tony_serve_prefill_tokens_total": 2560, "tony_serve_prefill_chunks_total": 10,
             "tony_serve_prefill_pairs_total": 10 * 8_000_000}
    means = C.window_means(lambda name, where=None: moved.get(name), {"decode_chunk": 8})
    assert means == {"live_slots": 60.0, "context_per_slot": 33_000.0, "held_rows_per_step": 320.0, "prefill_rows_per_chunk": 256.0,
                     "prefill_pairs_per_chunk": 8_000_000.0}
    assert C.window_means(lambda name, where=None: None if "pairs" in name else moved.get(name), {"decode_chunk": 8}) is None


def test_the_cell_is_the_issues(bench):
    """The cell's engine and traffic, letter for letter, and the benchmark's entries for it. 48 slots and
    callers, ISSUE 46's named fallback: at 64 the replica peaked at 15.01-15.43 GB under the ramp (PERF.md section 4)."""
    spec = bench["spec"]
    w = spec.workload(CELL)
    assert {k: w["engine"][k] for k in ("slots", "max_len", "page_len", "prefill_chunk", "decode_chunk")} == {
        "slots": 48, "max_len": 35840, "page_len": 1024, "prefill_chunk": 2048, "decode_chunk": 8}
    assert w["engine"]["num_pages"] >= 577 and w["engine"]["warm_prefill"] == [64, 128, 256, 512, 1024, 2048]
    t = w["traffic"]
    assert t["draw_seed"] == 46 and t["arrivals"] == {"process": "closed", "clients": 48, "ramp_s": 24.0}
    assert t["prefix"] == {"groups": 8, "tokens": 32768}
    assert t["prompt_len"] == {"dist": "lognormal", "median": 33024, "sigma": 0.006, "min": 32832, "max": 33792}
    assert t["answer_len"] == {"dist": "lognormal", "median": 512, "sigma": 0.3, "min": 256, "max": 1024}
    bench_json = spec.benchmark()
    listed = {m["name"] for m in bench_json["per_layer"] if CELL in m.get("workloads", [])}
    assert {"latent_paged_decode_roofline_pct.serve", "prefix_hit_pct.serve", "latent_prefill_roofline_pct.serve",
            "moe_decode_roofline_pct.serve", "moe_prefill_roofline_pct.serve", "held_share_pct.serve"} <= listed
    assert [m["name"] for m in spec.cell_metrics(bench_json, CELL, "end_to_end")] == ["serve_out_tok_s", "setup_s"]
    for name in ("latent_paged_decode_roofline_pct.serve", "prefix_hit_pct.serve"):
        m = next(m for m in bench_json["per_layer"] if m["name"] == name)
        # this cell first; a later cell whose requests share pages joins `prefix_hit_pct.serve` behind it (PR 50)
        assert m["workloads"][0] == CELL and spec.metric(name)["reader"] in ("family_roofline", "registry_share")
        assert m["workloads"] == [CELL] or name == "prefix_hit_pct.serve"
    # every last prefill chunk the window can see has a warmed shape: a question of 64-1024 rows, in powers of two
    lo, hi = t["prompt_len"]["min"] - t["prefix"]["tokens"], t["prompt_len"]["max"] - t["prefix"]["tokens"]
    assert (lo, hi) == (64, 1024) and {1 << (n - 1).bit_length() for n in range(lo, hi + 1)} <= set(w["engine"]["warm_prefill"])


def test_the_share_reader_adds_the_parts_up(bench):
    from readers import registry_share

    snap = lambda hit, fill: {"metrics": [{"name": "hits", "samples": [{"labels": {}, "value": hit}]},
                                          {"name": "fills", "samples": [{"labels": {}, "value": fill}]}]}
    ctx = {"drive": {"snap0": snap(10, 100), "snap1": snap(1000, 110)}}
    args = {"part": {"name": "hits"}, "rest": [{"name": "fills"}], "scale": 100.0}
    assert registry_share.read(ctx, **args) == pytest.approx(99.0)
    assert registry_share.read({"drive": {"snap0": snap(1, 1), "snap1": snap(1, 1)}}, **args) is None           # nothing moved
    assert registry_share.read(ctx, part={"name": "no_such"}, rest=[{"name": "fills"}]) is None                # a parent without the counter
    assert registry_share.read({"drive": {"snap0": None, "snap1": None}}, **args) is None


# -- the family's rehearsal (benchmark/tests/test_mistral4_rehearsal.py), run with the suite ---------
def _rehearsal():
    import importlib.util

    spec_ = importlib.util.spec_from_file_location("mistral4_rehearsal", os.path.join(BENCH, "tests", "test_mistral4_rehearsal.py"))
    module = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(module)
    return module


test_the_rehearsal_cell_runs_end_to_end_on_the_cpu = _rehearsal().test_the_rehearsal_cell_runs_end_to_end_on_the_cpu
test_the_control_lies_far_from_the_reference_and_the_program_on_it = _rehearsal().test_the_control_lies_far_from_the_reference_and_the_program_on_it
