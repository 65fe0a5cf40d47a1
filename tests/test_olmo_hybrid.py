"""The olmo_hybrid family on the CPU at a tiny size (`tiny-olmo-hybrid`: hidden 64,
two periods of [linear_attention x 3, full_attention], 4 heads: keys of 8 and
values of 16 under the gated delta rule, heads of 16 under full attention, a
convolution of 4 taps, float32; pages of 16 positions): the rule's chunk and step
forms against the position-at-a-time recurrence, the convolution chunked against
whole, the program against the family's plain reference, the state's snapshots at
page edges through the engine (a hit, a match that ends on the snapshot's page, an
eviction, an overwritten place, the counters), the engines that were there as
they were, and the family's files through the benchmark's harness.

Tolerances. The rule's forms differ from the recurrence in the order of their
sums: 2e-5 of the largest output is ten times what was seen (2e-6) with keys at
random, and 5e-4 with keys nearly parallel and beta near 2, where every row of a
block corrects every other (1e-4 seen; the recurrence itself is that sensitive
there). A state kept in bfloat16 moves the same outputs by 1e-2 and fails both
(`test_a_bfloat16_state_fails_the_tolerance`). On logits: this tiny network turns a
relative change of 1e-7 in its embedding into 4e-4 on its last layer's rows (a
delta rule with random projections decays by e^-100 at one token and by nothing
at the next), so program and reference, both float32, agree to 3e-4 on logits of
size 4 and LOGIT_TOL is 2e-3; a bfloat16 state moves them by 0.1.
"""
import dataclasses
import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
TINY, MAX_LEN, PAGE = "tiny-olmo-hybrid", 256, 16
LOGIT_TOL = 2e-3
CONFIG, CELL = "olmo-hybrid-7b", "olmo-hybrid-7b.serve_sessions"


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules, by name, with benchmark/ on the path for as
    long as this file's tests run."""
    before = list(sys.path)
    sys.path.insert(0, BENCH)
    import chipside
    import families
    import spec

    yield {"spec": spec, "families": families, "chipside": chipside}
    sys.path[:] = before


@pytest.fixture(scope="module")
def monkeypatch_module():
    mp = pytest.MonkeyPatch()
    yield mp
    mp.undo()


@pytest.fixture(scope="module")
def interpreted(monkeypatch_module):
    monkeypatch_module.setenv("TONY_PALLAS_INTERPRET", "1")


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


# -- the rule: chunk and step against the recurrence a position at a time -----------------------------
def _rule_inputs(seed, H, T, dk, dv, beta_shift=0.0, decay=(-7.0, -3.0), parallel=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v = (jax.random.normal(ks[i], (H, T, d)) for i, d in enumerate((dk, dk, dv)))
    if parallel:
        k = jnp.abs(k) + 2.0                                      # every key within a few degrees of every other
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[3], (H, T)) + beta_shift)
    g = -jnp.exp(jax.random.uniform(ks[4], (H, T), minval=decay[0], maxval=decay[1]))
    return q, k, v, g, beta, jax.random.normal(ks[5], (H, dk, dv))


RULE_CASES = {
    "random-keys": (dict(), 2e-5),
    "beta-near-0": (dict(beta_shift=-6.0), 2e-5),
    "beta-near-2-keys-nearly-parallel": (dict(beta_shift=6.0, parallel=True), 5e-4),
    "strong-decay": (dict(decay=(0.0, 5.0)), 2e-5),               # g down to -148 a token: a state wiped at a token
    "weak-decay": (dict(decay=(-12.0, -9.0)), 2e-5),
}


#: heads, positions, block. A program of the blocked rule holds the most heads up to `CHUNK_HEADS` (4) that divide H: 1, 2,
#: 3 (six heads: two programs), ONE of thirteen (a prime over the bound: the one-head program), 3 of thirty, 4 of 64
CHUNKS = {"two-heads": (2, 48, 16), "one-head": (1, 48, 16), "six-heads-in-two-programs": (6, 48, 16), "thirteen-heads-a-program-each": (13, 48, 16),
          "thirty-heads-by-three": (30, 32, 16), "sixty-four-heads-by-four": (64, 32, 16), "three-heads-in-blocks-of-64": (3, 128, 64)}


@pytest.mark.parametrize("chunk", list(CHUNKS))
@pytest.mark.parametrize("case", list(RULE_CASES))
def test_the_chunk_form_is_the_recurrence(interpreted, case, chunk):
    """Positions in blocks (a block's edge inside the chunk), then the same with
    eleven fewer of them counting (a padded last chunk): outputs and state."""
    from tony_tpu.ops import delta_rule as D

    kw, tol = RULE_CASES[case]
    H, T, block = CHUNKS[chunk]
    q, k, v, g, beta, s0 = _rule_inputs(3, H, T, 8, 16, **kw)
    want_o, want_s = D.gated_delta_scan(q, k, v, g, beta, s0)
    o, s = D.gated_delta_chunk(q, k, v, g, beta, s0, block=block)
    scale = float(jnp.abs(want_o).max())
    assert float(jnp.abs(o - want_o).max()) < tol * scale and float(jnp.abs(s - want_s).max()) < tol * float(jnp.abs(want_s).max())
    valid = T - 11
    o, s = D.gated_delta_chunk(q, k, v, g, beta, s0, jnp.int32(valid), block=block)
    _, want_s = D.gated_delta_scan(*(a[:, :valid] for a in (q, k, v, g, beta)), s0)
    assert float(jnp.abs(o[:, :valid] - want_o[:, :valid]).max()) < tol * scale
    assert float(jnp.abs(s - want_s).max()) < tol * float(jnp.abs(want_s).max())


@pytest.mark.parametrize("heads", [2, 6], ids=["two-heads-a-program", "two-programs-of-three-heads"])
@pytest.mark.parametrize("case", ["beta-near-2-keys-nearly-parallel", "strong-decay", "weak-decay"])
@pytest.mark.parametrize("cut", [16, 32])
def test_a_chunk_boundary_inside_a_prompt_carries_the_state(interpreted, cut, case, heads):
    """Two chunks, the second from the first's state: the one recurrence."""
    from tony_tpu.ops import delta_rule as D

    kw, tol = RULE_CASES[case]
    *x, s0 = _rule_inputs(4, heads, 64, 8, 16, **kw)
    want_o, want_s = D.gated_delta_scan(*x, s0)
    first, mid = D.gated_delta_chunk(*(a[:, :cut] for a in x), s0, block=16)
    second, s = D.gated_delta_chunk(*(a[:, cut:] for a in x), mid, block=16)
    assert float(jnp.abs(jnp.concatenate([first, second], axis=1) - want_o).max()) < tol * float(jnp.abs(want_o).max())
    assert float(jnp.abs(s - want_s).max()) < tol * float(jnp.abs(want_s).max())


@pytest.mark.parametrize("shape", [(4, 128, 64), (3, 48, 16)], ids=["four-heads-of-two-blocks", "three-heads-in-blocks-of-16"])
@pytest.mark.parametrize("case", ["beta-near-2-keys-nearly-parallel", "strong-decay"])
def test_a_program_of_several_heads_is_its_heads_one_at_a_time(interpreted, case, shape):
    """ONE program of all the heads against the same inputs a head at a time (the
    one-head program, the parent's grid): the same operations a head in the same
    order, so the outputs and the states are equal BIT FOR BIT, not within a tolerance."""
    from tony_tpu.ops import delta_rule as D

    H, T, block = shape
    args = _rule_inputs(7, H, T, 8, 16, **RULE_CASES[case][0])
    o, s = D.gated_delta_chunk(*args, jnp.int32(T - 5), block=block)
    alone = [D.gated_delta_chunk(*(a[n:n + 1] for a in args), jnp.int32(T - 5), block=block) for n in range(H)]
    assert bool((o == jnp.concatenate([a for a, _ in alone])).all()) and bool((s == jnp.concatenate([b for _, b in alone])).all())


@pytest.mark.parametrize("case", ["random-keys", "beta-near-2-keys-nearly-parallel", "strong-decay"])
def test_the_step_form_is_the_recurrence(interpreted, case):
    """Three slots, each at another position of the sequence with another state, four steps on."""
    from tony_tpu.ops import delta_rule as D

    kw, tol = RULE_CASES[case]
    q, k, v, g, beta, s0 = _rule_inputs(5, 4, 12, 8, 16, **kw)
    at = np.array([0, 3, 7])
    state = jnp.stack([s0 * (1 + i) for i in range(3)])
    want = [D.gated_delta_scan(*(a[:, p:p + 4] for a in (q, k, v, g, beta)), state[i]) for i, p in enumerate(at)]
    for j in range(4):
        o, state = D.gated_delta_step(*(a[:, at + j].swapaxes(0, 1) for a in (q, k, v, g, beta)), state)
        for i in range(3):
            assert float(jnp.abs(o[i] - want[i][0][:, j]).max()) < tol * float(jnp.abs(want[i][0]).max())
    assert all(float(jnp.abs(state[i] - want[i][1]).max()) < tol * float(jnp.abs(want[i][1]).max()) for i in range(3))


def test_a_bfloat16_state_fails_the_tolerance(interpreted):
    """What the tolerances above are tight enough to tell: the recurrence with its
    state rounded to bfloat16 after every position is 1e-2 off, 500 tolerances."""
    from tony_tpu.ops import delta_rule as D

    q, k, v, g, beta, s0 = _rule_inputs(3, 2, 48, 8, 16)
    want, _ = D.gated_delta_scan(q, k, v, g, beta, s0)
    state, outs = s0, []
    for t in range(48):
        o, state = D.gated_delta_scan(*(a[:, t:t + 1] for a in (q, k, v, g, beta)), state)
        state = state.astype(jnp.bfloat16).astype(jnp.float32)
        outs.append(o)
    assert float(jnp.abs(jnp.concatenate(outs, 1) - want).max()) > 100 * 2e-5 * float(jnp.abs(want).max())


@pytest.mark.parametrize("rows,valid", [(32, None), (64, None), (64, 41), (512, 300)],
                         ids=["one-tile", "two-chunks", "a-padded-chunk", "tiles-of-256-rows"])
def test_the_convolution_in_chunks_is_the_whole_one(interpreted, rows, valid):
    """A sequence convolved whole against the same in two chunks with the tail
    carried, and the steps that follow; `valid` short of the second chunk: the
    tail is the last three inputs that count."""
    from tony_tpu.ops import delta_rule as D

    C, half = 128, rows // 2
    ks = jax.random.split(jax.random.PRNGKey(rows), 2)
    u, w = jax.random.normal(ks[0], (rows + 2, C)), jax.random.normal(ks[1], (4, C))
    xp = jnp.concatenate([jnp.zeros((3, C)), u])
    acc = sum(w[j] * xp[j:j + rows + 2] for j in range(4))
    want = acc * jax.nn.sigmoid(acc)
    first, tail = D.short_conv_chunk(u[:half], jnp.zeros((3, C)), w)
    second, tail = D.short_conv_chunk(u[half:rows], tail, w, None if valid is None else jnp.int32(valid - half))
    n = rows if valid is None else valid
    assert float(jnp.abs(jnp.concatenate([first, second])[:n] - want[:n]).max()) < 1e-5
    assert np.array_equal(np.asarray(tail), np.asarray(u[n - 3:n]))
    tails = jnp.stack([tail, tail])
    for j in range(2):                                                                 # decode goes on from the tail
        y, tails = D.short_conv_step(jnp.stack([u[n + j], u[n + j]]), tails, w)
        if valid is None:
            assert float(jnp.abs(y[1] - want[n + j]).max()) < 1e-5


@pytest.mark.parametrize("pos0", [0, 16, 48, 96], ids=["first-chunk", "a-tile-in", "across-tiles", "last-tiles"])
def test_the_chunk_attention_is_causal_attention_over_the_staged_keys(interpreted, pos0):
    """32 queries at pos0.. against 128 staged keys in tiles of 32 (q blocks of
    16): whole tiles before the diagonal unmasked, the diagonal's by position,
    tiles past a q block's last row not computed (they hold 1e4)."""
    from tony_tpu.ops.attention import chunk_prefill_attention

    H, T, d, Tk = 3, 32, 16, 128
    ks = jax.random.split(jax.random.PRNGKey(pos0), 3)
    q, k, v = jax.random.normal(ks[0], (H, T, d)), jax.random.normal(ks[1], (H, Tk, d)), jax.random.normal(ks[2], (H, Tk, d))
    live = (jnp.arange(Tk) < pos0 + T)[None, :, None]
    o = chunk_prefill_attention(q, jnp.where(live, k, 1e4), jnp.where(live, v, 1e4), jnp.int32(pos0), jnp.int32(pos0 + T),
                                block_q=16, block_k=32)
    s = jnp.einsum("htd,hkd->htk", q, k) * d ** -0.5
    mask = jnp.arange(Tk)[None, :] <= (pos0 + jnp.arange(T))[:, None]
    want = jnp.einsum("htk,hkd->htd", jax.nn.softmax(jnp.where(mask, s, -1e30), -1), v)
    assert float(jnp.abs(o - want).max()) < 2e-6
    # the same from a request's whole staging [L, 1, Hkv, Tk, d] and a layer's index: no layer's slice is handed in
    stage = lambda a: jnp.stack([jnp.full_like(a, 1e4), jnp.where(live, a, 1e4)])[:, None]
    staged = chunk_prefill_attention(q, stage(k), stage(v), jnp.int32(pos0), jnp.int32(pos0 + T), jnp.int32(1), block_q=16, block_k=32)
    assert np.array_equal(np.asarray(staged), np.asarray(o))


# -- the program against the reference ----------------------------------------------------------
@pytest.fixture(scope="module")
def one_forward(tiny):
    seq = _tokens(64, 96)
    got = np.asarray(tiny["module"].forward(tiny["params"], jnp.asarray([seq + [0] * 32], jnp.int32), tiny["cfg"]))[0]
    return got[:96], tiny["ref_logits"](seq)


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
    seq = jnp.asarray(_tokens(65, 64), jnp.int32)
    want = np.asarray(R.hidden(params, seq, dict(sizes, layer_types=(kind,), layers=1), "f32", 32))
    one = dataclasses.replace(cfg, layer_types=(kind,))
    got = np.asarray(m._chunk(params, seq, m._init_staging(one, 64), jnp.int32(64), one)[0])
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


# -- the engines that were there, as they were ------------------------------------------------------
#: sha256 (16 hex) of the lowered text of `tiny-mistral4`'s jitted serving programs, taken on the parent commit
#: (7df6d50) by the code of `_lowered_mistral4` below (its `insert` counts pages on the host, so the jitted
#: functions are lowered, not the engine's closures). The four families older than it are held by
#: tests/test_dots3_note.py's and tests/test_mistral4.py's tables, whose hashes this PR found as they stood. This
#: PR edits three files those programs import: models/serving.py (one optional field of `ServingPrograms` and the
#: question `_match_prefix_into` asks it), models/paged_cache.py (the allocator's table of states at page edges, a
#: counter) and ops/attention.py (`chunk_prefill_attention`, appended), and changes nothing any of the five lowers to.
#: PR 54 counts a held expert's rows by one compare (tests/test_dots3_note.py's note): `decode_chunk` 855f93154a825272 until then
PARENT_LOWERED_MISTRAL4 = {"prefill_chunk": "e78e42ba67c18c24", "insert": "a4fa53841bc9026a", "gather_prefix": "40c7cd604b36b119",
                           "decode_chunk": "87689cf3c5047563"}


def _lowered_mistral4(bench, max_len=128, page=16, chunk=32):
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config("tiny-mistral4"), "serve-1chip")
    m, cfg = families.load("mistral4").program(sizes, max_len)
    params = jax.eval_shape(lambda: bench["chipside"].seed_weights(sizes, 7))
    slots, n_pages = 2, max_len // page
    cache = jax.eval_shape(lambda: m._init_cache(cfg, slots, max_len, page, slots * n_pages + 1))
    staging = jax.eval_shape(lambda: m._init_staging(cfg, max_len))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    text = {
        "prefill_chunk": m.prefill_chunk.lower(params, i32(1, chunk), staging, i32(), cfg).as_text(),
        "insert": m.insert_prefill.lower(cache, staging, i32(n_pages), i32(n_pages), i32(), i32(), i32(), i32()).as_text(),
        "gather_prefix": m.gather_prefix.lower(staging, cache, i32(n_pages), i32()).as_text(),
        "decode_chunk": m.decode_steps.lower(params, cache, i32(slots), jax.ShapeDtypeStruct((2,), jnp.uint32), cfg, 4).as_text(),
    }
    return {k: hashlib.sha256(v.encode()).hexdigest()[:16] for k, v in text.items()}


def test_the_newest_family_before_this_one_lowers_to_the_parents_text(bench, interpreted):
    assert _lowered_mistral4(bench) == PARENT_LOWERED_MISTRAL4


@pytest.mark.parametrize("config,family", [("tiny-dense", "llama"), ("tiny-mistral4", "mistral4")])
def test_a_family_with_no_state_beside_its_pages_matches_the_chain_it_matched_before(bench, interpreted, config, family):
    """`prefix_usable` is None for them: `_match_prefix_into` pins the longest
    resident chain up to its cap and uses all of it. A prompt of five whole
    pages and three tokens, then the same five pages under another ending: 80
    tokens hit, the pins of exactly those five pages taken and given back."""
    from tony_tpu.models.serving import ContinuousBatcher, programs_for

    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config(config), "serve-1chip")
    _, cfg = families.load(family).program(sizes, 128)
    assert programs_for(cfg, "paged").prefix_usable is None and programs_for(cfg, "paged").gather_prefix is not None
    eng = ContinuousBatcher(bench["chipside"].seed_weights(sizes, 7), cfg, num_slots=2, max_len=128, decode_chunk=4, kv="paged",
                            page_len=16, prefill_chunk=32)
    document = _tokens(80, 80)
    eng.submit(document + _tokens(81, 3), 5)
    eng.run()
    pinned = []
    match = eng.allocator.match_prefix
    eng.allocator.match_prefix = lambda keys: pinned.append(match(keys)) or pinned[-1]
    eng.submit(document + _tokens(82, 7), 5)
    eng.run()
    assert eng.prefix_hit_tokens == 80 and [len(p) for p in pinned if p] == [5] and eng.allocator.live_pages() == 0


def test_a_replica_finds_the_familys_presets():
    from tony_tpu.models import olmo_hybrid, registry

    presets = registry.presets()
    assert presets["olmo-hybrid-tiny"] is olmo_hybrid.PRESETS["olmo-hybrid-tiny"]
    assert registry.module_of(presets["olmo-hybrid-tiny"]) is olmo_hybrid and {"tiny", "sala-tiny", "mistral4-tiny"} <= set(presets)
    cfg = olmo_hybrid.OlmoHybridConfig()
    assert (cfg.n_layers, cfg.count("linear_attention"), cfg.count("full_attention"), cfg.d_model, cfg.conv_channels) == (32, 24, 8, 3840, 11520)
    with pytest.raises(ValueError, match="kv='paged'"):
        olmo_hybrid.serving_programs(olmo_hybrid.OLMO_HYBRID_TINY, "dense")
    with pytest.raises(ValueError, match="edges of pages"):
        olmo_hybrid._init_cache(olmo_hybrid.OLMO_HYBRID_TINY, 2, 128, 32, 9)
    with pytest.raises(ValueError, match="neither"):
        olmo_hybrid.init(jax.random.PRNGKey(0), dataclasses.replace(olmo_hybrid.OLMO_HYBRID_TINY, layer_types=("sliding_attention",)))
    params = jax.eval_shape(lambda: olmo_hybrid.init(jax.random.PRNGKey(0), olmo_hybrid.OLMO_HYBRID_TINY))
    assert set(params) == {"embed", "layers", "final_norm", "lm_head"} and len(params["layers"]) == 8
    assert params["layers"][0]["w_qkv"].shape == (64, 128) and params["layers"][3]["w_qkv"].shape == (64, 192)
    assert "conv" in params["layers"][0] and "conv" not in params["layers"][3]


# -- the family's files, through the harness -------------------------------------------------------
def test_the_family_resolves_sizes_program_reference_and_counts(bench):
    spec, families = bench["spec"], bench["families"]
    cfg = spec.config(CONFIG)
    sizes = spec.model_sizes(cfg, "serve-1chip")
    assert (sizes["layers"], sizes["vocab"], sizes["d_model"], sizes["d_ff"]) == (8, 100352, 3840, 11008)
    assert sizes["layer_types"] == ("linear_attention",) * 3 + ("full_attention",) + ("linear_attention",) * 3 + ("full_attention",)
    assert (sizes["heads"], sizes["kv_heads"], sizes["head_dim"]) == (30, 30, 128)
    assert (sizes["lin_heads"], sizes["lin_key_dim"], sizes["lin_value_dim"], sizes["conv_taps"]) == (30, 96, 192, 4)
    source = spec.model_sizes(cfg, "source")
    assert source["layers"] == 32 and source["layer_types"].count("full_attention") == 8
    hash(tuple(sorted(sizes.items())))                               # check.py keys its traced programs by the sizes
    fam = families.load("olmo_hybrid")
    module, pcfg = fam.program(sizes, 20480, 256, 32)
    assert module.__name__ == "tony_tpu.models.olmo_hybrid" and (pcfg.n_layers, pcfg.page_len, pcfg.snapshots, pcfg.dtype) == (8, 256, 32, "bfloat16")
    assert families.reference(sizes).__name__ == "families.olmo_hybrid_reference" and families.reference(sizes).CONTROL == "fp8"
    assert families.counts(sizes).__name__ == "families.olmo_hybrid_counts"


def test_the_configuration_holds_every_published_key_at_its_published_value(bench):
    """Against the catalog's row where the catalog is installed; the two cut keys
    carry the source's value beside the deployment's."""
    cfg = bench["spec"].config(CONFIG)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("the catalog of architectures is not installed here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Olmo-Hybrid-7B")
    assert cfg["source"] == row["source_url"] and sorted(cfg["reduced"]) == ["layer_types", "num_hidden_layers"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key]["source"] == value, key
        else:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"]["serve-1chip"] == 8 and cfg["layer_types"]["serve-1chip"] == row["config"]["layer_types"][:8]
    assert "four pipeline stages" in cfg["deployments"]["serve-1chip"] and cfg["assumed"]["head_dim"]["value"] == 128


@pytest.mark.parametrize("change,error,match", [
    ({"rope_parameters": {"rope_theta": 10000.0}}, ValueError, "rope_parameters"),
    ({"linear_allow_neg_eigval": False}, ValueError, "linear_allow_neg_eigval"),
    ({"sliding_window": 4096}, KeyError, "does not know"),
    ({"linear_num_value_heads": 60}, ValueError, "a head each"),
    ({"assumed": {}}, KeyError, "assumed"),
], ids=["a-rope", "beta-below-one", "an-unknown-key", "grouped-value-heads", "nothing-assumed"])
def test_the_family_refuses_what_it_does_not_know(bench, change, error, match):
    cfg = {**bench["spec"].config(CONFIG), **change}
    with pytest.raises(error, match=match):
        bench["spec"].model_sizes(cfg, "serve-1chip")


def test_an_assumed_choice_is_one_value(bench):
    cfg = bench["spec"].config(CONFIG)
    cfg = {**cfg, "assumed": {**cfg["assumed"], "full_attention_rope": {"value": "rotate_half", "why": "a guess"}}}
    with pytest.raises(ValueError, match="full_attention_rope"):
        bench["spec"].model_sizes(cfg, "serve-1chip")


def test_a_checkout_without_the_model_module_has_no_such_family(bench, monkeypatch):
    """The benchmark's files laid over a parent commit: run.py's own process says
    so and exits 2 before any launch."""
    fam = bench["families"].load("olmo_hybrid")
    monkeypatch.setattr(fam, "PROGRAM", os.path.join(ROOT, "tony_tpu", "models", "no_such_model.py"))
    with pytest.raises(bench["families"].NoFamily, match="from the commit"):
        bench["spec"].model_sizes(bench["spec"].config(CONFIG), "serve-1chip")


def test_the_counts_on_hand_worked_sizes(bench):
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config(CONFIG), "serve-1chip")
    C, engine = families.counts(sizes), spec.workload(CELL)["engine"]
    assert C.mixer_params(sizes, "linear_attention") == 3840 * (11520 + 5760 + 5760 + 60) == 88_704_000
    assert C.mixer_params(sizes, "full_attention") == 4 * 3840 * 3840 and C.total_params(sizes) == 2_435_389_440
    assert C.rule_ops(sizes) == 6 * 30 * 96 * 192 and C.state_bytes(sizes) == 4 * 30 * 96 * 192
    means = {"live_slots": 16.0, "context_per_slot": 10_000.0, "prefill_rows_per_chunk": 512.0, "prefill_pairs_per_chunk": 512 * 9000.0}
    ops, nbytes = C.delta_decode_call(sizes, engine, means)
    assert (ops, nbytes) == (6 * 30 * 96 * 192 * 16 * 6, 2 * 4 * 30 * 96 * 192 * 16 * 6 / 8)      # 6 linear layers; 212 MB a CHUNK of 8 steps
    ops, _ = C.delta_prefill_call(sizes, engine, means)
    assert ops == 6 * 30 * 96 * 192 * 512 * 6
    ops, _ = C.attn_prefill_call(sizes, engine, means)
    assert ops == 4 * 30 * 128 * 512 * 9000 * 2
    import re
    assert re.search(C.delta_decode_operands(sizes, engine), "(f32[8,30,1,192]{3,2,1,0:T(1,128)S(1)}, f32[8,30,96,192]{3,2,1,0:T(8,128)S(1)}) custom-call")
    assert re.search(C.attn_prefill_operands(sizes, engine), "bf16[2,1,30,20480,128]{4,3,2,1,0}")
    assert not re.search(C.attn_prefill_operands(sizes, engine), "bf16[2,801,30,256,128]{4,3,2,1,0}")     # not the pool
    assert not hasattr(C, "attn_decode_call")                    # no count of the page walk here: the counts' docstring says why
    assert re.search(C.delta_prefill_operands(sizes, engine), "f32[30,96,192]{2,1,0}")
    assert C.delta_prefill_calls(sizes, engine) == ("prefill_page", 1) == C.attn_prefill_calls(sizes, engine)
    assert C.delta_decode_calls(sizes, engine) == ("decode_steps", 8)


def test_window_means_from_the_replicas_counters(bench):
    sizes = bench["spec"].model_sizes(bench["spec"].config(CONFIG), "serve-1chip")
    C = bench["families"].counts(sizes)
    deltas = {"tony_serve_engine_chunks_total": 10, "tony_serve_decode_slots_total": 150, "tony_serve_context_tokens_total": 150 * 8 * 9000,
              "tony_serve_prefill_tokens_total": 7 * 1024, "tony_serve_prefill_chunks_total": 7, "tony_serve_prefill_pairs_total": 7 * 700 * 9000}
    means = C.window_means(lambda name, where=None: deltas.get(name), {"decode_chunk": 8})
    assert means == {"live_slots": 15.0, "context_per_slot": 9000.0, "prefill_rows_per_chunk": 1024.0, "prefill_pairs_per_chunk": 700 * 9000.0}
    assert C.window_means(lambda name, where=None: None, {}) is None                   # a program without the counters


def test_the_cell_is_the_issues(bench):
    spec = bench["spec"]
    w, b = spec.workload(CELL), spec.benchmark()
    t, e = w["traffic"], w["engine"]
    assert w["kind"] == "serve" and w["deployment"] == "serve-1chip" and w["chips"] == 1 and w["config"] == CONFIG
    assert t["arrivals"] == {"process": "closed", "clients": e["slots"], "ramp_s": 24.0} and e["slots"] == 8     # the issue's second fallback
    assert t["sessions"] == {"turns": 12, "turn_tokens": 256, "think_s": 0.5} and "prefix" not in t
    assert t["prompt_len"] == {"dist": "lognormal", "median": 6144, "sigma": 0.4, "min": 4096, "max": 12288}
    assert t["answer_len"] == {"dist": "lognormal", "median": 256, "sigma": 0.3, "min": 128, "max": 384}
    assert (t["draw_seed"], t["drain_s"]) == (50, 120)
    assert {k: e[k] for k in ("max_len", "page_len", "num_pages", "prefill_chunk", "decode_chunk", "warm_prefill", "snapshots")} == {
        "max_len": 20480, "page_len": 256, "num_pages": 801, "prefill_chunk": 2048, "decode_chunk": 8,
        "warm_prefill": [256, 512, 1024, 2048], "snapshots": 32}
    # the longest turn fits: the last turn's prompt and its answer
    assert 12288 + 11 * (384 + 256) + 384 <= e["max_len"] and e["snapshots"] >= 2 * e["slots"]
    listed = {m["name"] for m in spec.cell_metrics(b, CELL, "per_layer")}
    assert {"delta_decode_roofline_pct.serve", "delta_prefill_roofline_pct.serve", "attn_prefill_roofline_pct.serve",
            "prefix_hit_pct.serve", "launch_s", "decode_step_ms.serve_tput"} <= listed and "attn_decode_roofline_pct.serve" not in listed
    assert {m["name"] for m in spec.cell_metrics(b, CELL, "end_to_end")} == {"serve_out_tok_s", "setup_s"}
    for name in ("delta_decode_roofline_pct.serve", "delta_prefill_roofline_pct.serve", "attn_prefill_roofline_pct.serve"):
        m = next(m for m in b["per_layer"] if m["name"] == name)
        # the cell that brought the metric is its first; a later family with the kernel joins behind it (falcon-h1-34b, PR 59)
        assert m["workloads"][0] == CELL and spec.metric(name)["reader"] == "family_roofline"
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"] and entry["file"] == "benchmark/configs/olmo-hybrid-7b.json"


# -- the family's rehearsal and the generator's sessions (benchmark/tests/test_olmo_hybrid_rehearsal.py), run with the suite
def _rehearsal():
    import importlib.util

    spec_ = importlib.util.spec_from_file_location("olmo_hybrid_rehearsal", os.path.join(BENCH, "tests", "test_olmo_hybrid_rehearsal.py"))
    module = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(module)
    return module


test_the_rehearsal_cell_runs_end_to_end_on_the_cpu = _rehearsal().test_the_rehearsal_cell_runs_end_to_end_on_the_cpu
test_the_control_lies_far_from_the_reference_and_the_program_on_it = _rehearsal().test_the_control_lies_far_from_the_reference_and_the_program_on_it
test_a_sessions_later_turns_resend_the_conversation = _rehearsal().test_a_sessions_later_turns_resend_the_conversation
test_a_session_stops_at_stop_and_after_an_error = _rehearsal().test_a_session_stops_at_stop_and_after_an_error
