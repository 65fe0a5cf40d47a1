"""The olmo_hybrid family on the CPU at a tiny size (`tiny-olmo-hybrid`: hidden 64,
two periods of [linear_attention x 3, full_attention], 4 heads: keys of 8 and
values of 16 under the gated delta rule, heads of 16 under full attention, a
convolution of 4 taps, float32; pages of 16 positions): the program against
the family's plain reference, and the state's snapshots at page edges through
the engine (a hit, a match that ends on the snapshot's page, an eviction, an
overwritten place, the counters). The rule's, the convolution's and the chunk
attention's forms are tests/test_olmo_hybrid_rule.py; the engines that were
there, the family's files through the benchmark's harness and its rehearsal are
tests/test_olmo_hybrid_family.py.

Tolerances. On logits: this tiny network turns a
relative change of 1e-7 in its embedding into 4e-4 on its last layer's rows (a
delta rule with random projections decays by e^-100 at one token and by nothing
at the next), so program and reference, both float32, agree to 3e-4 on logits of
size 4 and LOGIT_TOL is 2e-3; a bfloat16 state moves them by 0.1.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

TINY, MAX_LEN, PAGE = "tiny-olmo-hybrid", 256, 16
LOGIT_TOL = 2e-3


@pytest.fixture(scope="module")
def tiny(bench, interpreted):
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config(TINY), "serve-1chip")
    module, cfg = families.load("olmo_hybrid").program(sizes, MAX_LEN, PAGE, 8)
    reference = families.reference(sizes)
    params = bench["chipside"].seed_weights(sizes, 7)
    ref = jax.jit(lambda p, t: reference.forward(p, t, sizes, "f32", 32))

    def ref_logits(seq):
        """The reference's logits for every position of `seq`, padded at the end
        to one length (one compile; a causal model's positions do not see it)."""
        return np.asarray(ref(params, jnp.asarray(list(seq) + [0] * (MAX_LEN - len(seq)), jnp.int32)))[:len(seq)]

    return {"sizes": sizes, "module": module, "cfg": cfg, "reference": reference, "params": params,
            "ref_logits": ref_logits}


def _tokens(seed, n, vocab=256):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


# -- the program against the reference ----------------------------------------------------------
def _a_rows_logits(tiny, tokens):
    """What `forward` computes for one row (`_finish(_chunk(...))` over the whole row), called OUTSIDE a jit: every
    layer's kernel is then a program of its own, compiled once for all the layers of its kind. `forward`'s one
    `lax.map` program holds a copy of the kernel a layer and compiles every copy (65 s here at 128 tokens, for 18)."""
    m, cfg, n = tiny["module"], tiny["cfg"], len(tokens)
    x = m._chunk(tiny["params"], jnp.asarray(tokens, jnp.int32), m._init_staging(cfg, n), jnp.int32(n), cfg)[0]
    return np.asarray(m._finish(x, tiny["params"], cfg))


@pytest.fixture(scope="module")
def one_forward(tiny):
    seq = _tokens(64, 96)
    return _a_rows_logits(tiny, seq + [0] * 32)[:96], tiny["ref_logits"](seq)


def test_forward_is_every_rows_chunk_and_finish(tiny):
    """`forward` itself, its `lax.map` over a batch of two rows of one short block: the rows' logits as above
    (one compiled program against the same operations one by one: within the tolerance the reference is held to)."""
    rows = [_tokens(66, 16), _tokens(67, 16)]
    got = np.asarray(tiny["module"].forward(tiny["params"], jnp.asarray(rows, jnp.int32), tiny["cfg"]))
    for row, logits in zip(rows, got):
        assert np.abs(logits - _a_rows_logits(tiny, row)).max() < LOGIT_TOL and np.abs(logits).max() > 0.5


@pytest.mark.parametrize("rows", [(0, 32), (32, 64), (64, 96)], ids=["first-block", "second-block", "third-block"])
def test_forward_agrees_with_the_reference(one_forward, rows):
    got, want = one_forward
    assert np.abs(got[rows[0]:rows[1]] - want[rows[0]:rows[1]]).max() < LOGIT_TOL
    assert np.abs(want[rows[0]:rows[1]]).max() > 0.5   # logits of size 1-4, not a row of zeros


@pytest.mark.parametrize("kind", ["linear_attention", "full_attention"])
def test_one_layer_of_each_kind_agrees_with_the_reference(tiny, kind):
    """A trunk of ONE layer of the kind (the tolerance of the whole network is the
    network's sensitivity; one layer's is the sums' order: 2e-5 of rows of size 6)."""
    R, m, sizes, cfg = tiny["reference"], tiny["module"], tiny["sizes"], tiny["cfg"]
    at = sizes["layer_types"].index(kind)
    params = dict(tiny["params"], layers=[tiny["params"]["layers"][at]])
    seq = jnp.asarray(_tokens(65, 128), jnp.int32)              # `one_forward`'s length: the layer's kernels are the programs compiled there
    want = np.asarray(R.hidden(params, seq, dict(sizes, layer_types=(kind,), layers=1), "f32", 32))
    one = dataclasses.replace(cfg, layer_types=(kind,))
    got = np.asarray(m._chunk(params, seq, m._init_staging(one, 128), jnp.int32(128), one)[0])
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max() and np.abs(want).max() > 2


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


def _admit(tiny, staging, slots, slot, n_prompt):
    m, cfg = tiny["module"], tiny["cfg"]
    n_pages = MAX_LEN // PAGE
    cache = m._init_cache(cfg, slots, MAX_LEN, PAGE, slots * n_pages + 1)
    row = np.arange(1 + slot * n_pages, 1 + (slot + 1) * n_pages).astype(np.int32)
    nc = -(-n_prompt // PAGE)
    fresh = np.zeros(n_pages, np.int32)
    fresh[:nc] = row[:nc]
    return m.insert_prefill(cache, staging, fresh, row, jnp.int32(slot), jnp.int32(n_prompt), jnp.int32(0), jnp.int32(nc),
                            jnp.int32(-1))


@pytest.mark.parametrize("prompt_len,chunk", [(5, 32), (29, 32), (49, 32), (77, 32)],
                         ids=["one-short-chunk", "under-a-chunk", "chunks-do-not-divide", "long-across-pages"])
def test_chunked_prefill_then_decode_agree_with_the_reference(tiny, prompt_len, chunk):
    """LOGITS: the last prompt row from the chunked prefill (each chunk cut at its
    last page's edge: `prefill_chunk` then `prefill_page`), then 6 decode steps a
    position at a time through the paged pool, the carried state and the carried
    convolution tail, crossing a page's edge: every step's logits against the
    reference's full forward of everything so far."""
    m, cfg = tiny["module"], tiny["cfg"]
    prompt = _tokens(prompt_len + chunk, prompt_len)
    _, staging, last = _prefill(tiny, prompt, chunk)
    assert np.abs(last - tiny["ref_logits"](prompt)[-1]).max() < LOGIT_TOL
    slots, slot = 2, 1
    cache = _admit(tiny, staging, slots, slot, prompt_len)
    seq, toks = list(prompt), np.zeros(slots, np.int32)
    toks[slot] = int(np.argmax(last))
    for _ in range(6):
        seq.append(int(toks[slot]))
        logits, cache = m.decode_logits(tiny["params"], cache, jnp.asarray(toks), cfg)
        assert np.abs(np.asarray(logits)[slot] - tiny["ref_logits"](seq)[-1]).max() < LOGIT_TOL
        toks[slot] = int(np.argmax(np.asarray(logits)[slot]))
    assert np.asarray(cache.lengths).tolist() == [0, prompt_len + 6]


# -- through the engine: snapshots of the state at page edges ---------------------------------------
def _engine(tiny, snapshots=8, **kw):
    from tony_tpu.models.serving import ContinuousBatcher

    args = dict(num_slots=2, max_len=MAX_LEN, decode_chunk=4, kv="paged", page_len=PAGE, prefill_chunk=32)
    return ContinuousBatcher(tiny["params"], dataclasses.replace(tiny["cfg"], snapshots=snapshots), **{**args, **kw})


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


def _delta(before):
    return {k: v - before.get(k, 0) for k, v in _counters().items()}


def test_a_sessions_next_turn_hits_its_pages_and_its_state(tiny):
    """A first turn of 77 tokens leaves four whole pages and a snapshot at their
    edge (64). The same prompt again matches up to it, restores it and prefills
    13 rows: the cold path's tokens, bit for bit. The next turn (the prompt, the
    answer, nine new tokens: 95) matches the same four pages, prefills 31 rows
    and leaves a snapshot at 80; the turn after it matches up to THAT. Every
    answer is the reference's greedy choice, and the counters count what happened."""
    before = _counters()
    eng = _engine(tiny)
    first_turn = _tokens(70, 77)
    first = eng.submit(first_turn, 9)
    answer = eng.run()[first]
    assert answer == _greedy(tiny, first_turn, 9) and eng.prefix_hit_tokens == 0
    again = eng.submit(first_turn, 9)
    assert eng.run()[again] == answer and eng.prefix_hit_tokens == 64
    second_turn = first_turn + answer + _tokens(71, 9)
    second = eng.submit(second_turn, 9)
    answer2 = eng.run()[second]
    assert answer2 == _greedy(tiny, second_turn, 9) and eng.prefix_hit_tokens == 128
    third_turn = second_turn + answer2 + _tokens(72, 9)
    third = eng.submit(third_turn, 9)
    assert eng.run()[third] == _greedy(tiny, third_turn, 9) and eng.prefix_hit_tokens == 128 + 80
    d = _delta(before)
    # taken: the first turn's (64), the second's (80), the third's (112); the repeat's edge had one already
    assert (d["tony_serve_state_snapshots_total{taken}"], d["tony_serve_state_snapshots_total{restored}"]) == (3, 3)
    assert d.get("tony_serve_state_snapshots_total{dropped}", 0) == 0
    pairs = lambda pos, take: take * pos + take * (take + 1) // 2
    assert d["tony_serve_prefill_pairs_total"] == (pairs(0, 32) + pairs(32, 32) + pairs(64, 13) + pairs(64, 13) + pairs(64, 31)
                                                   + pairs(80, 33))


def test_a_hits_logits_are_the_cold_paths_and_the_references(tiny):
    """The logits of a turn's last prompt row, three ways: the whole prompt
    prefilled cold; the matched pages gathered, the snapshot restored and the rest
    prefilled; the reference. The hit runs the program the cold path ran on the
    same rows from a copy of the same state: EQUAL, not close."""
    m, cfg = tiny["module"], tiny["cfg"]
    prompt = _tokens(73, 93)
    progs, whole, cold = _prefill(tiny, prompt, 32)
    edge = (len(prompt) - 1) // PAGE                                                   # 5 pages: 80 positions
    n_pages = MAX_LEN // PAGE
    cache = m.insert_prefill(m._init_cache(cfg, 2, MAX_LEN, PAGE, 2 * n_pages + 1), whole, np.arange(1, n_pages + 1, dtype=np.int32),
                             np.arange(1, n_pages + 1, dtype=np.int32), jnp.int32(0), jnp.int32(len(prompt)), jnp.int32(0),
                             jnp.int32(-(-len(prompt) // PAGE)), jnp.int32(3))
    pages = np.zeros(n_pages, np.int32)
    pages[:edge] = np.arange(1, edge + 1)
    staged = m.gather_prefix(m._init_staging(cfg, MAX_LEN), cache, pages, jnp.int32(edge), jnp.int32(3))
    assert int(staged.length) == edge * PAGE
    assert np.array_equal(np.asarray(staged.k)[:, :, :, :edge * PAGE], np.asarray(whole.k)[:, :, :, :edge * PAGE])
    assert np.array_equal(np.asarray(staged.state), np.asarray(whole.edge_state)) and np.abs(np.asarray(staged.state)).max() > 0
    _, _, hit = _prefill(tiny, prompt, 32, staging=staged, pos=edge * PAGE)
    assert np.array_equal(hit, cold) and np.abs(cold - tiny["ref_logits"](prompt)[-1]).max() < LOGIT_TOL


def test_a_match_ends_on_the_snapshots_page_not_past_it(tiny):
    """A store of ONE place. A first turn (77) keeps its state at 64; a second
    request (another document, 50) takes the place: the first's entry is dropped
    and its pages stay. The first prompt again finds four resident pages and no
    state at any edge of them: no page is used (all four unpinned again), the
    whole prompt is prefilled, the answer is right; the state it keeps at its
    own copy of the fourth page goes to the RESIDENT fourth page (the copy is
    freed unregistered: first writer wins), so the turn after it hits."""
    before = _counters()
    eng = _engine(tiny, snapshots=1)
    first_turn, other = _tokens(74, 77), _tokens(75, 50)
    first = eng.submit(first_turn, 9)
    answer = eng.run()[first]
    eng.submit(other, 5)
    eng.run()
    again = eng.submit(first_turn, 9)
    assert eng.run()[again] == answer == _greedy(tiny, first_turn, 9) and eng.prefix_hit_tokens == 0
    # the repeat took the place back and the resident page at 64 has it: a turn on top of it now hits
    second_turn = first_turn + answer + _tokens(76, 9)
    second = eng.submit(second_turn, 9)
    assert eng.run()[second] == _greedy(tiny, second_turn, 9) and eng.prefix_hit_tokens == 64
    d = _delta(before)
    assert (d["tony_serve_state_snapshots_total{taken}"], d["tony_serve_state_snapshots_total{dropped}"],
            d["tony_serve_state_snapshots_total{restored}"]) == (4, 3, 1)
    # every pin was given back: nothing is live once the engine has drained
    assert eng.allocator.live_pages() == 0


def test_the_deepest_page_with_a_snapshot_ends_the_match():
    """The allocator alone: a chain of five resident pages of which the second
    and the fourth have their edge's state kept: a match may use four of them;
    when the fourth's page is evicted from the reuse pool its entry goes with it
    and its place is free again; an overwritten place drops the oldest entry."""
    from tony_tpu.models.paged_cache import PageAllocator

    a = PageAllocator(8)                                                               # pages 1..7
    pages = a.alloc(5)
    keys = [(j, bytes([j])) for j in range(5)]
    for p, key in zip(pages, keys):
        a.register(p, key)
    assert a.keep_state(pages[1], 2) == 0 and a.keep_state(pages[3], 2) == 1 and a.keep_state(pages[3], 2) == -1
    for p in pages:
        a.release(p)                                                                   # into the reuse pool, LRU first
    matched = a.match_prefix(keys)
    assert matched == pages and a.deepest_state(matched) == 4 and a.deepest_state(matched[:3]) == 2 and a.deepest_state(matched[:1]) == 0
    for p in matched:
        a.release(p)
    a.alloc(2 + 4)                                                                     # two free pages, then the four oldest of the pool
    assert a.state_at(pages[1]) is None and a.state_at(pages[3]) is None and a.match_prefix(keys) == []
    # both places are free again: two more snapshots fit, a third takes the oldest one's
    b = PageAllocator(8)
    p = b.alloc(3)
    assert [b.keep_state(x, 2) for x in p] == [0, 1, 0] and b.state_at(p[0]) is None and b.state_at(p[2]) == 0
    b.release(p[1])                                                                    # no key: freed, and its state with it
    assert b.state_at(p[1]) is None and b.keep_state(p[0], 2) == 1


def test_an_evicted_page_means_no_hit_and_a_right_answer(tiny):
    """A pool of 13 pages (and the one no slot may own). A first turn of 77
    leaves four registered pages in the reuse pool with a snapshot; two requests
    of 90 and 100 tokens need 7 + 8 pages and evict them; the first prompt again
    matches nothing and is served whole."""
    before = _counters()
    eng = _engine(tiny, num_pages=14)
    first_turn = _tokens(77, 77)
    first = eng.submit(first_turn, 9)
    answer = eng.run()[first]
    for seed, n in ((78, 90), (79, 100)):
        eng.submit(_tokens(seed, n), 9)
    eng.run()
    hits = eng.prefix_hit_tokens
    again = eng.submit(first_turn, 9)
    assert eng.run()[again] == answer == _greedy(tiny, first_turn, 9) and eng.prefix_hit_tokens == hits == 0
    d = _delta(before)
    assert d["tony_serve_state_snapshots_total{dropped}"] >= 1 and d.get("tony_serve_state_snapshots_total{restored}", 0) == 0


def test_a_slot_used_again_reads_nothing_of_its_last_tenant(tiny):
    """The same request before and after other requests have been through both
    slots, with the pool set to 1e4 and every slot's state and tail to 1e3 in
    between (the prompt has no whole page, so nothing of it is shared)."""
    eng = _engine(tiny)
    prompt = _tokens(50, 13)
    first = eng.submit(prompt, 9)
    assert eng.run()[first] == _greedy(tiny, prompt, 9)
    churn = [eng.submit(_tokens(60 + i, n), 6) for i, n in enumerate((90, 41, 5, 33))]
    done = eng.run()
    assert all(len(done[r]) == 6 for r in churn)
    c = eng.cache
    eng.cache = c._replace(k=jnp.full_like(c.k, 1e4), v=jnp.full_like(c.v, 1e4), state=tuple(jnp.full_like(s, 1e3) for s in c.state),
                           tail=jnp.full_like(c.tail, 1e3))
    again = eng.submit(prompt, 9)
    assert eng.run()[again] == done[first]
