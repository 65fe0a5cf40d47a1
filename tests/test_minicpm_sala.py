"""MiniCPM-SALA on the CPU at a tiny size (`tiny-minicpm-sala`: 2 periods of
[sparse, linear x 3], hidden 64, block 8, kernel 4 / stride 2, top-k 3, window
16, dense length 32, float32, so that 100 positions exercise the sparse path):
the program against the family's plain reference, the ops against their
definitions, the engine's handling of pages and state, the llama engine's
tokens as they were before the engine had a seam, and the family's files
through the benchmark's harness (found as tests/test_benchmark.py finds it).

Tolerances: program and reference both compute in float32 here and differ in
the order of their sums only (chunked against literal recurrence, online
against whole softmax): 2e-5 on logits of size 1-2 is 50 times what was seen
(4e-7) and a hundredth of what one wrongly chosen block moves (2e-3).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
TINY, MAX_LEN, PAGE = "tiny-minicpm-sala", 128, 8
LOGIT_TOL = 2e-5


@pytest.fixture(scope="module")
def tiny(bench, monkeypatch_module):
    monkeypatch_module.setenv("TONY_PALLAS_INTERPRET", "1")
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config(TINY), "serve-1chip")
    module, cfg = families.load("minicpm_sala").program(sizes, MAX_LEN)
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
    """One sequence of 96 positions through the program's `forward` and through
    the reference: its rows up to 32 have a dense context, the rest a sparse one."""
    seq = _tokens(96, 96)
    got = np.asarray(tiny["module"].forward(tiny["params"], jnp.asarray([seq], jnp.int32), tiny["cfg"]))[0]
    return got, tiny["ref_logits"](seq)


@pytest.mark.parametrize("rows", [(0, 32), (24, 48), (32, 96)], ids=["dense", "across-the-dense-length", "sparse"])
def test_forward_agrees_with_the_reference(one_forward, rows):
    got, want = one_forward
    assert np.abs(got[rows[0]:rows[1]] - want[rows[0]:rows[1]]).max() < LOGIT_TOL
    assert np.abs(want[rows[0]:rows[1]]).max() > 0.5   # logits of size 1-2, not a row of zeros


def test_a_row_the_reference_did_not_compute_is_never_correct(bench, tiny, monkeypatch):
    """The reference computes its head for a sequence's last HEAD_ROWS rows (the
    rows the serving check reads at 50k positions). A comparison that reads an
    earlier row must fail, not pass on a row of equal logits."""
    reference, sizes = tiny["reference"], tiny["sizes"]
    monkeypatch.setattr(reference, "HEAD_ROWS", 16)
    seq = jnp.asarray(_tokens(5, 64), jnp.int32)
    logits = np.asarray(reference.forward(tiny["params"], seq, sizes, "f32", 64))
    assert np.isnan(logits[:48]).all() and np.isfinite(logits[48:]).all()
    assert np.abs(logits[48:] - tiny["ref_logits"](np.asarray(seq))[48:]).max() < 1e-6
    check = __import__("check")
    answer = {"prompt": _tokens(5, 40), "tokens": _tokens(6, 4)}    # its rows 39..42 lie before the last 16 of 64
    assert not check.check_serve(tiny["params"], sizes, [answer], pad_seq=64, pad_answer=4)["worst_gap"] <= 1e9
    # the same answer where every row it reads is computed (another padded length: check.py keeps its traced programs)
    monkeypatch.setattr(reference, "HEAD_ROWS", 2048)
    assert check.check_serve(tiny["params"], sizes, [answer], pad_seq=128, pad_answer=4)["worst_gap"] < 10


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


@pytest.mark.parametrize("prompt_len,chunk", [(77, 32), (64, 16), (20, 32)],
                         ids=["chunks-do-not-divide", "chunks-divide", "dense-one-chunk"])
def test_chunked_prefill_then_paged_decode_agree_with_the_reference(tiny, prompt_len, chunk):
    """Logits, not tokens: the last prompt position from the chunked prefill,
    then 14 decode steps through pages, compressed keys and state (they cross
    a page's edge and several strides), each against the reference's full
    forward over everything so far."""
    module, cfg, params = tiny["module"], tiny["cfg"], tiny["params"]
    prompt = _tokens(prompt_len + chunk, prompt_len)
    progs, staging, last = _prefill(tiny, prompt, chunk)
    want = tiny["ref_logits"](prompt)[-1]
    assert np.abs(last - want).max() < LOGIT_TOL
    slots, slot, steps = 2, 1, 14
    cache = progs.init_cache(slots, MAX_LEN, PAGE, slots * (MAX_LEN // PAGE) + 1)
    covered, held = -(-(prompt_len + steps) // PAGE), -(-prompt_len // PAGE)
    row = np.zeros(MAX_LEN // PAGE, np.int32)
    row[:covered] = np.arange(3, 3 + covered)
    fresh = np.zeros(MAX_LEN // PAGE, np.int32)
    fresh[:held] = row[:held]
    cache = progs.insert(cache, staging, fresh, row, jnp.int32(slot), jnp.int32(prompt_len), jnp.int32(0), jnp.int32(held))
    assert cache.state.dtype == jnp.float32 and cache.ck.dtype == jnp.float32
    seq, tok = list(prompt), int(np.argmax(want))
    for _ in range(steps):
        toks = np.zeros(slots, np.int32)
        toks[slot] = tok
        logits, cache = module.decode_logits(params, cache, jnp.asarray(toks), cfg)
        seq.append(tok)
        want = tiny["ref_logits"](seq)[-1]
        assert np.abs(np.asarray(logits)[slot] - want).max() < LOGIT_TOL, len(seq)
        tok = int(np.argmax(want))


@pytest.mark.parametrize("chunk", [1, 7, 16, 64])
def test_linear_chunk_agrees_with_the_literal_recurrence(chunk):
    """The chunked form against one position at a time, with a state carried
    in, and with only part of a padded chunk counting."""
    from tony_tpu.ops.linear_attention import linear_attention_chunk, linear_attention_step, log_decay

    rng = np.random.default_rng(chunk)
    heads, d = 4, 16
    q, k, v = (jnp.asarray(rng.standard_normal((1, heads, chunk, d)), jnp.float32) for _ in range(3))
    state0 = jnp.asarray(rng.standard_normal((1, heads, d, d)), jnp.float32)
    slopes = log_decay(heads)
    assert np.allclose(np.exp(-np.asarray(slopes)), [np.exp(-2.0 ** (-8 * (h + 1) / heads)) for h in range(heads)])
    state, outs, states = state0, [], []
    for t in range(chunk):
        o, state = linear_attention_step(q[:, :, t], k[:, :, t], v[:, :, t], state, slopes)
        outs.append(o)
        states.append(state)
    got, after = linear_attention_chunk(q, k, v, state0, slopes, block=min(chunk, 8) if chunk % 8 == 0 else chunk)
    assert np.abs(np.stack(outs, 2) - got).max() < 1e-4 and np.abs(after - states[-1]).max() < 1e-4
    valid = (chunk + 1) // 2
    _, part = linear_attention_chunk(q, k, v, state0, slopes, valid=jnp.int32(valid), block=chunk)
    assert np.abs(part - states[valid - 1]).max() < 1e-4


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_programs_chosen_blocks_are_the_references(tiny, seed):
    """Seeded float32 queries and keys: the same blocks, ties and all
    (neighbouring blocks share a compressed key, so equal scores are common)."""
    from tony_tpu.ops import sparse_attention as SA

    sizes, sp = tiny["sizes"], tiny["cfg"].sparse
    rng = np.random.default_rng(seed)
    t, hkv, g, d = 96, 2, 2, 16
    q = jnp.asarray(rng.standard_normal((t, hkv, g, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((t, hkv, d)), jnp.float32)
    pos = jnp.arange(t)
    got = np.asarray(SA.chosen_blocks(SA.block_scores(q, SA.compress_keys(k, sp), pos + 1, sp), pos + 1, sp))
    R = tiny["reference"]
    want = np.asarray(R.chosen_blocks(q, R.compressed_keys(k, sizes), pos, sizes, "f32"))
    sparse = np.arange(t) + 1 > sp.dense_len
    assert got.shape == want.shape and (got[sparse] == want[sparse]).all()
    assert (got[sparse].sum(-1) <= sp.topk).all() and got[sparse][..., 0].all()
    assert got[~sparse].all()


# the fused selection at one shape (one trace a spec): 32 queries from `pos0` against 128 positions, tiles of 16
# queries x 4 blocks, so 16 blocks are 4 tiles. (what the keys hold, pos0, dense_len or None for the config's 32)
SELECT_CASES = {
    "random": ("random", 40, None),
    "ties-at-the-last-place": ("equal", 60, None),           # (i) every block scores the same: the earlier ones win
    "fewer-valid-than-topk": ("random", 0, 0),               # (ii) contexts of 1..32: 0 to 4 blocks with a finished kernel
    "straddles-the-dense-length": ("random", 16, None),      # (iii) contexts 17..48 around dense_len 32
    "ends-inside-a-tile": ("nan-past", 36, None),            # (iv) 33 kernels end in context: tile 2 of 0..3 in part, 3 not at all
    "ends-far-before-max-len": ("nan-past", 8, None),        # (iv) 19 kernels: tiles 2 and 3 are skipped, and hold NaN
}


@pytest.mark.parametrize("case", sorted(SELECT_CASES))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_fused_selection_chooses_the_same_blocks(tiny, seed, case):
    """`block_select` against `block_scores` (the XLA form: decode's, and the
    oracle) and against the reference: the same bool set, block for block."""
    from tony_tpu.ops import sparse_attention as SA

    keys, pos0, dense_len = SELECT_CASES[case]
    sizes, sp, R = dict(tiny["sizes"]), tiny["cfg"].sparse, tiny["reference"]
    if dense_len is not None:
        sp, sizes["sparse_dense_len"] = sp._replace(dense_len=dense_len), dense_len
    rng = np.random.default_rng(seed)
    t, hkv, g, d = 32, 2, 2, 16
    q = jnp.asarray(rng.standard_normal((t, hkv, g, d)), jnp.float32)
    k = rng.standard_normal((MAX_LEN, hkv, d)).astype(np.float32)
    if keys == "equal":
        k[:] = k[0]
    if keys == "nan-past":
        k[pos0 + t:] = np.nan                                 # no query's context holds them
    k, pos = jnp.asarray(k), pos0 + jnp.arange(t)
    kc = SA.compress_keys(k, sp)
    fused, kth = SA.block_select(q, kc, pos + 1, sp, block_q=16, block_b=4)
    got = np.asarray(SA.chosen_blocks(fused, pos + 1, sp, kth))
    oracle = np.asarray(SA.chosen_blocks(SA.block_scores(q, kc, pos + 1, sp), pos + 1, sp))
    want = np.asarray(R.chosen_blocks(q, R.compressed_keys(k, sizes), pos, sizes, "f32"))
    sparse = np.asarray(pos) + 1 > sp.dense_len
    assert not np.isnan(np.asarray(fused)).any() and (np.asarray(kth) == np.asarray(SA.kth_largest(fused, sp.topk))).all()
    assert got.shape == oracle.shape == want.shape and (got == oracle).all() and (got[sparse] == want[sparse]).all()
    assert got[~sparse].all() and sparse.any()
    held = (np.asarray(fused) >= 0)[sparse].sum(-1)           # blocks a finished kernel overlaps, and the first
    assert (got[sparse].sum(-1) == np.minimum(held, sp.topk)).all() and got[sparse][..., 0].all()
    if keys == "equal":
        assert got[sparse][..., :sp.topk].all()
    if case == "fewer-valid-than-topk":
        assert set(held.ravel()) == {1, 2, 3, 4}              # block 0 alone while no kernel has finished


@pytest.mark.parametrize("k", [1, 3, 64])
@pytest.mark.parametrize("seed", [0, 1])
def test_the_kth_largest_is_found_without_a_sort(seed, k):
    """Against `np.partition`, on float32 with duplicates, +inf and -1 (a block
    no kernel overlaps): the k-th largest, or 0 where fewer than k are >= 0."""
    from tony_tpu.ops.sparse_attention import kth_largest

    rng = np.random.default_rng(seed)
    x = rng.choice(rng.random(40, np.float32) * np.float32(10.0) ** rng.integers(-30, 3, 40).astype(np.float32), (24, 2, 100))
    x[rng.random(x.shape) < 0.2] = -1.0
    x[rng.random(x.shape) < 0.05] = 0.0
    x[:, :, 0] = np.inf
    x[:6, :, 1:] = -1.0                                       # rows with one entry that is not negative
    x[6:8] = np.float32(0.25)                                 # rows of one value
    want = np.maximum(np.partition(x, -k, axis=-1)[..., -k], 0)[..., None]
    got = np.asarray(kth_largest(jnp.asarray(x), k))
    assert got.dtype == np.float32 and got.shape == want.shape and (got == want).all()
    assert (want[:6] == (np.inf if k == 1 else 0)).all() and (k == 1 or len(np.unique(want)) > 4)


@pytest.fixture(scope="module")
def watched_prefill(tiny):
    """The program's `prefill_chunk`, traced afresh with a `block_select` that
    says when it RUNS (both branches of a `cond` are traced; one executes)."""
    module, runs = tiny["module"], []
    real = module.block_select

    def watched(*args, **kw):
        jax.debug.callback(lambda: runs.append(1))
        return real(*args, **kw)

    # a function of its own: a trace is kept by the function traced, and `prefill_chunk`'s may be an earlier test's
    fn = jax.jit(lambda *args, cfg: module.prefill_chunk.__wrapped__(*args, cfg), static_argnames=("cfg",))
    progs = module.serving_programs(tiny["cfg"], "paged")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(module, "block_select", watched)
        fn(tiny["params"], jnp.zeros((1, 16), jnp.int32), progs.init_staging(MAX_LEN), jnp.int32(16), cfg=tiny["cfg"])
    jax.effects_barrier()
    assert not runs                                           # a first chunk of 16 is dense
    return fn, runs, progs


@pytest.mark.parametrize("over", [0, 1], ids=["ends-at-the-dense-length", "ends-one-past-it"])
def test_the_counter_and_the_program_take_the_same_path(tiny, watched_prefill, over):
    """`prefill_path(pos, take)` feeds `tony_serve_prefill_chunks_total{path}`;
    `_sparse_attend` branches on the device. A padded last chunk whose prompt
    ends at the dense length, and one position past it: they must not part."""
    fn, runs, progs = watched_prefill
    sp = tiny["cfg"].sparse
    prompt = _tokens(40 + over, sp.dense_len + over)
    staging, paths = progs.init_staging(MAX_LEN), []
    for pos in range(0, len(prompt), 16):
        take = min(16, len(prompt) - pos)
        toks = jnp.asarray(prompt[pos:pos + take] + [0] * (16 - take), jnp.int32)[None]
        del runs[:]
        logits, staging = fn(tiny["params"], toks, staging, jnp.int32(take), cfg=tiny["cfg"])
        jax.effects_barrier()
        paths.append(progs.prefill_path(pos, take))
        assert (len(runs) > 0) == (paths[-1] == "sparse"), (pos, take)
    assert paths == ["dense", "dense"] + ["sparse"] * over
    assert np.abs(np.asarray(logits)[0] - tiny["ref_logits"](prompt)[-1]).max() < LOGIT_TOL


@pytest.mark.parametrize("longest", [32, 48], ids=["dense", "sparse"])
def test_a_chunks_attention_is_the_unfused_formulations_to_the_last_bit(tiny, longest):
    """`_sparse_attend` against the parent's way (every row's scores in XLA,
    `chosen_blocks` over them, all-true where the context is dense): on a
    dense chunk the rows that count, on a sparse chunk every row."""
    from tony_tpu.ops import sparse_attention as SA

    module, cfg = tiny["module"], tiny["cfg"]
    sp, t = cfg.sparse, 16
    rng = np.random.default_rng(longest)
    q = jnp.asarray(rng.standard_normal((t, cfg.n_heads, cfg.head_dim)), jnp.float32)
    keys, values = (jnp.asarray(rng.standard_normal((cfg.n_kv_heads, MAX_LEN, cfg.head_dim)), jnp.float32) for _ in range(2))
    pos0 = longest - t                                        # dense: a chunk of contexts 17..32; sparse: 33..48
    positions = pos0 + jnp.arange(t, dtype=jnp.int32)
    got = jax.jit(lambda *a: module._sparse_attend(*a, cfg))(q, keys, values, positions, jnp.int32(longest), jnp.int32(pos0 + t))
    g = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(t, cfg.n_kv_heads, g, cfg.head_dim)
    chosen = SA.chosen_blocks(SA.block_scores(qg, SA.compress_keys(keys.transpose(1, 0, 2), sp), positions + 1, sp), positions + 1, sp)
    assert bool(chosen.all()) == (longest <= sp.dense_len)
    mask = SA.prefill_mask(chosen, positions, MAX_LEN, sp)
    want = SA.masked_prefill_attention(qg.transpose(1, 2, 0, 3), keys, values, mask, jnp.int32(pos0 + t))
    assert np.array_equal(np.asarray(got), np.asarray(want.transpose(2, 0, 1, 3).reshape(t, cfg.n_heads, cfg.head_dim)))


def test_the_decode_kernel_reads_only_the_pages_it_was_given(monkeypatch):
    """Every page that is not in a (slot, head)'s list is NaN: the output is
    finite and equals attention over the listed pages' visible positions."""
    monkeypatch.setenv("TONY_PALLAS_INTERPRET", "1")
    from tony_tpu.ops.decode_attention import sparse_paged_decode_attention

    rng = np.random.default_rng(0)
    slots, hkv, g, d, page, pages_total, layers = 2, 2, 2, 16, 8, 40, 2
    kp = rng.standard_normal((layers, pages_total, hkv, page, d)).astype(np.float32)
    vp = rng.standard_normal((layers, pages_total, hkv, page, d)).astype(np.float32)
    q = rng.standard_normal((slots, hkv * g, d)).astype(np.float32)
    cur_k, cur_v = (rng.standard_normal((slots, hkv, d)).astype(np.float32) for _ in range(2))
    staged_k, staged_v = (rng.standard_normal((slots, 4, hkv, d)).astype(np.float32) for _ in range(2))
    lengths, staged = np.array([75, 30]), np.array([2, 0])   # cache positions, the last `staged` of them in the staging
    win_lo = np.array([60, 0])
    n = 12
    logical = np.zeros((slots, hkv, n), np.int32)
    full = np.zeros((slots, hkv, n), bool)
    counts = np.zeros((slots, hkv), np.int32)
    lists = {(0, 0): [(0, 1), (3, 1), (7, 0), (8, 0), (9, 0)], (0, 1): [(0, 1), (5, 1), (7, 1), (8, 0), (9, 0)],
             (1, 0): [(0, 1), (1, 1), (2, 1), (3, 1)], (1, 1): [(0, 1), (1, 1), (2, 1), (3, 1)]}
    table = rng.permutation(np.arange(1, pages_total))[:slots * 10].reshape(slots, 10)
    pages = np.zeros((slots, hkv, n), np.int32)
    layer = 1
    poison_k, poison_v = np.full_like(kp, np.nan), np.full_like(vp, np.nan)
    want = np.zeros((slots, hkv * g, d), np.float32)
    for (s, h), entries in lists.items():
        counts[s, h] = len(entries)
        keys, vals = [], []
        pool_len = lengths[s] - staged[s]
        for c, (b, whole) in enumerate(entries):
            logical[s, h, c], full[s, h, c], pages[s, h, c] = b, whole, table[s, b]
            poison_k[layer, table[s, b], h], poison_v[layer, table[s, b], h] = kp[layer, table[s, b], h], vp[layer, table[s, b], h]
            for r in range(page):
                p = b * page + r
                if p < pool_len and (whole or p >= win_lo[s]):
                    keys.append(kp[layer, table[s, b], h, r])
                    vals.append(vp[layer, table[s, b], h, r])
        keys += [staged_k[s, j, h] for j in range(staged[s])] + [cur_k[s, h]]
        vals += [staged_v[s, j, h] for j in range(staged[s])] + [cur_v[s, h]]
        keys, vals = np.stack(keys), np.stack(vals)
        for r in range(g):
            w = np.exp((keys @ q[s, h * g + r]) / np.sqrt(d))
            want[s, h * g + r] = (w / w.sum()) @ vals
    got = np.asarray(sparse_paged_decode_attention(
        jnp.asarray(q), jnp.asarray(poison_k), jnp.asarray(poison_v), jnp.int32(layer), jnp.asarray(pages),
        jnp.asarray(logical), jnp.asarray(full), jnp.asarray(counts), jnp.asarray(lengths, jnp.int32),
        jnp.asarray(win_lo, jnp.int32), cur_k=jnp.asarray(cur_k), cur_v=jnp.asarray(cur_v),
        staged_k=jnp.asarray(staged_k), staged_v=jnp.asarray(staged_v), staged_count=jnp.asarray(staged, jnp.int32)))
    assert np.isfinite(got).all() and np.abs(got - want).max() < 1e-5


# -- the engine: pages beside state ----------------------------------------------------------------
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


def test_a_slot_used_again_starts_from_a_zero_state(tiny):
    """The same request before and after other requests have been through both
    slots: the same tokens, and they are the reference's greedy choice (state,
    compressed keys and stride sums of a slot's last tenant leave no trace)."""
    eng = _engine(tiny)
    prompt = _tokens(50, 70)
    first = eng.submit(prompt, 9)
    assert eng.run()[first] == _greedy(tiny, prompt, 9)
    churn = [eng.submit(_tokens(60 + i, n), 6) for i, n in enumerate((90, 41, 55, 33))]
    again = eng.submit(prompt, 9)
    done = eng.run()
    assert all(len(done[r]) == 6 for r in churn)
    assert done[again] == done[first]
    assert eng.cache.state.dtype == jnp.float32 and eng.cache.state.shape[:2] == (6, 2)


def test_requests_with_a_common_prefix_answer_as_they_do_alone(tiny):
    """Two prompts that share their first 64 tokens (8 full pages), together
    and after each other: each answers as it does alone, and no page is handed
    from one to the other: a page's keys without the linear layers' state at
    its edge are half a prefix."""
    head = _tokens(70, 64)
    a, b = head + _tokens(71, 13), head + _tokens(72, 29)
    alone = {}
    for name, p in (("a", a), ("b", b)):
        eng = _engine(tiny)
        rid = eng.submit(p, 8)
        alone[name] = eng.run()[rid]
    eng = _engine(tiny)
    ra, rb = eng.submit(a, 8), eng.submit(b, 8)
    done = eng.run()
    rb2 = eng.submit(b, 8)
    assert (done[ra], done[rb], eng.run()[rb2]) == (alone["a"], alone["b"], alone["b"])
    assert eng.prefix_hit_tokens == 0 and alone["a"] == _greedy(tiny, a, 8)


def test_the_engine_counts_context_visible_positions_state_and_chunks(tiny):
    from tony_tpu.obs import metrics as obs_metrics

    def totals():
        out = {}
        for m in obs_metrics.REGISTRY.snapshot():
            for s in m["samples"]:
                out[m["name"] + "".join(f"/{v}" for v in s["labels"].values())] = s.get("value")
        return out

    before = totals()
    eng = _engine(tiny)
    rid = eng.submit(_tokens(80, 70), 9)     # 70 = 32 + 32 + 6: one dense chunk, two on the sparse path
    eng.run()
    after = totals()
    delta = lambda k: after.get(k, 0) - (before.get(k) or 0)
    # admission emits token 1; two chunks of 4 steps at contexts 71..78 emit the rest
    contexts = np.arange(71, 79)
    assert delta("tony_serve_context_tokens_total") == contexts.sum()
    assert delta("tony_serve_visible_tokens_total") == np.minimum(contexts, 3 * 8 + 16).sum()
    assert (delta("tony_serve_prefill_chunks_total/dense"), delta("tony_serve_prefill_chunks_total/sparse")) == (1, 2)
    assert eng.done[rid] and len(eng.done[rid]) == 9


GOLDEN = {   # taken on the parent commit (7da9699), tiny-dense, seed 7: ContinuousBatcher before it had a seam
    "tokens": [[225, 102, 197, 191, 189, 95, 241, 224, 224], [94, 3, 82, 75, 49, 235, 202, 65, 234, 36],
               [3, 50, 24, 47, 230, 27, 173, 209, 253, 101, 91], [123, 108, 43, 168, 195, 16, 199, 186, 3, 79, 233, 0],
               [63, 56, 3, 50, 60, 192, 180, 35, 4, 168, 195, 99, 26]],
    "engines": {"paged-chunked": dict(kv="paged", page_len=16, prefill_chunk=16), "dense": dict(kv="dense"),
                "paged-whole": dict(kv="paged", page_len=32)},
}


@pytest.mark.parametrize("engine", sorted(GOLDEN["engines"]))
def test_the_llama_engine_answers_as_before_the_seam(bench, monkeypatch, engine):
    monkeypatch.setenv("TONY_PALLAS_INTERPRET", "1")
    from tony_tpu.models.serving import ContinuousBatcher

    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config("tiny-dense"), "serve-1chip")
    _, cfg = families.load("llama").program(sizes, 128)
    params = bench["chipside"].seed_weights(sizes, 7)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, sizes["vocab"], n).tolist() for n in (5, 23, 40, 17, 33)]
    eng = ContinuousBatcher(params, cfg, num_slots=2, max_len=128, decode_chunk=4, **GOLDEN["engines"][engine])
    rids = [eng.submit(p, 9 + i) for i, p in enumerate(prompts)]
    done = eng.run()
    assert [done[r] for r in rids] == GOLDEN["tokens"]


def test_a_replica_finds_every_servable_modules_presets():
    from tony_tpu.models import llama, minicpm_sala, registry

    presets = registry.presets()
    assert presets["tiny"] is llama.PRESETS["tiny"] and presets["sala-tiny"] is minicpm_sala.PRESETS["sala-tiny"]
    assert registry.module_of(presets["sala-tiny"]) is minicpm_sala and registry.module_of(presets["tiny"]) is llama
    llama.PRESETS["registered-later"] = llama.PRESETS["tiny"]      # what a family's serve_install does
    try:
        assert "registered-later" in registry.presets()
    finally:
        del llama.PRESETS["registered-later"]


# -- the family's files, through the harness -------------------------------------------------------
def test_the_family_resolves_sizes_program_reference_and_counts(bench):
    spec, families = bench["spec"], bench["families"]
    cfg = spec.config("minicpm-sala")
    sizes = spec.model_sizes(cfg, "serve-1chip")
    assert (sizes["layers"], sizes["layers_published"], sizes["vocab"], sizes["d_model"], sizes["d_ff"]) == (
        12, 32, 73448, 4096, 16384)
    assert sizes["mixer_types"] == ("minicpm4", "lightning-attn", "lightning-attn", "lightning-attn") * 3
    assert [i for i, m in enumerate(spec.model_sizes(cfg, "source")["mixer_types"]) if m == "minicpm4"] == [
        0, 9, 16, 17, 22, 29, 30, 31]
    assert [sizes[k] for k in ("sparse_kernel_size", "sparse_kernel_stride", "sparse_block_size", "sparse_topk",
                               "sparse_init_blocks", "sparse_window", "sparse_dense_len")] == [32, 16, 64, 64, 1, 2048, 8192]
    fam = families.load("minicpm_sala")
    module, pcfg = fam.program(sizes, 50688)
    assert module.__name__ == "tony_tpu.models.minicpm_sala" and callable(module.forward)
    assert (pcfg.n_layers, pcfg.d_model, pcfg.residual_scale, pcfg.sparse.list_len()) == (12, 4096, 1.4 / 32 ** 0.5, 128)
    ref = families.reference(sizes)
    assert all(callable(getattr(ref, f)) for f in ("seed_key", "init_weights", "forward", "nll"))
    assert ref.CONTROL == "fp8" and ref.GRAD_LEAVES == () and "tony_tpu" not in open(ref.__file__).read().split('"""')[2]
    assert callable(fam.serve_install) and families.counts(sizes).__name__ == "families.minicpm_sala_counts"


@pytest.mark.parametrize("change,error,match", [
    (lambda c: {**c, "sliding_window": 4096}, KeyError, "sliding_window"),
    (lambda c: {**c, "assumed": {k: v for k, v in c["assumed"].items() if k != "sparse_topk"}}, KeyError, "sparse_topk"),
    (lambda c: {**c, "assumed": {**c["assumed"], "block_score": {"value": "sum", "why": ""}}}, ValueError, "block_score"),
    (lambda c: {**c, "attn_use_rope": True}, ValueError, "attn_use_rope"),
    (lambda c: {**c, "mixer_types": ["minicpm4"] * 3}, ValueError, "mixer_types"),
], ids=["unknown-key", "missing-assumed-size", "another-block-score", "a-switch-it-does-not-compute", "mixers-not-the-depth"])
def test_the_family_refuses_what_it_does_not_know(bench, change, error, match):
    spec, families = bench["spec"], bench["families"]
    with pytest.raises(error, match=match):
        families.load("minicpm_sala").sizes(change(spec.config("minicpm-sala")), "serve-1chip")


def test_the_counts_on_hand_worked_sizes(bench):
    spec, families = bench["spec"], bench["families"]
    s = spec.model_sizes(spec.config("minicpm-sala"), "serve-1chip")
    own = families.counts(s)
    # the issue's arithmetic: a sparse layer 52.4 M + 201.3 M, a linear one 83.9 M + 201.3 M
    assert own.layer_matmul_params(s, "minicpm4") == 3 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 4096 * 16384 == 253_755_392
    assert own.layer_matmul_params(s, "lightning-attn") == 5 * 4096 * 4096 + 3 * 4096 * 16384 == 285_212_672
    # 3.33 B in the 12 layers + 0.60 B of embedding and head (the whole vocabulary): 7.86 GB in bf16
    assert abs(own.total_params(s) - 3.93e9) < 0.01e9 and abs(own.total_params(s) - 2 * 73448 * 4096 - 3.33e9) < 0.01e9
    assert [own.visible(s, n) for n in (100, 8192, 8193, 50000)] == [100, 8192, 6144, 6144]
    eng = {"slots": 16, "max_len": 50688, "page_len": 64, "prefill_chunk": 2048, "decode_chunk": 8}
    means = {"live_slots": 10.0, "visible_per_slot": 6144.0, "sparse_chunk_share": 1.0}
    # 10 slots x 6144 positions: QK^T and PV for 32 heads of 128; keys and values of 2 heads, 2 bytes
    assert own.sparse_decode_call(s, eng, means) == (4.0 * 32 * 128 * 61440, 2.0 * 2 * 2 * 128 * 61440)
    assert own.sparse_decode_calls(s, eng) == ("decode_steps", 3 * 8)
    # a slot's state of one layer: 32 x 128 x 128 float32, read and written
    assert own.linear_decode_call(s, eng, means) == (4.0 * 524288 * 10, 8.0 * 524288 * 10)
    assert own.linear_decode_calls(s, eng) == ("decode_steps", 9 * 8)
    assert own.linear_prefill_call(s, eng, means) == (4.0 * 2048 * 524288, 8.0 * 2048 * 4096 + 8.0 * 524288)
    ops, nbytes = own.sparse_prefill_call(s, eng, means)
    assert ops == 4.0 * 32 * 128 * 2048 * 6144 and nbytes == 4.0 * 2048 * 4096 + 4.0 * 6144 * 256
    dense_ops, _ = own.sparse_prefill_call(s, eng, {**means, "sparse_chunk_share": 0.0})
    assert dense_ops == 4.0 * 32 * 128 * np.mean([sum(range(p + 1, p + 2049)) for p in (0, 2048, 4096, 6144)])
    import re
    pool = "%c = bf16[16,2,16,128]{3,2,1,0} custom-call(s32[32,128]{1,0} %a, bf16[3,12673,2,64,128]{4,3,2,1,0} %kp)"
    assert re.search(own.sparse_decode_operands(s, eng), pool) and not re.search(own.sparse_prefill_operands(s, eng), pool)
    assert re.search(own.sparse_prefill_operands(s, eng), "custom-call(s8[2,2048,50688]{2,1,0} %mask)")
    assert re.search(own.linear_decode_operands(s, eng), "%f = f32[16,32,128,128]{3,2,1,0} fusion(")
    chunk = "%linear_attention_chunk.1 = (bf16[32,2048,128]{2,1,0}, f32[32,128,128]{2,1,0}) custom-call(f32[32]{0} %s"
    assert re.search(own.linear_prefill_operands(s, eng), chunk) and not re.search(own.linear_prefill_operands(s, eng), pool)
    assert own.train_flops_per_token(s, 4096) > 6 * s["layers"] * own.layer_matmul_params(s)


def test_the_serving_roofline_reader_by_hand(bench):
    import importlib
    import types

    spec, families = bench["spec"], bench["families"]
    counts = importlib.import_module("counts")
    read = importlib.import_module("readers.serve_roofline").read
    s = spec.model_sizes(spec.config("minicpm-sala"), "serve-1chip")
    w = {"engine": {"slots": 16, "max_len": 50688, "page_len": 64, "prefill_chunk": 2048, "decode_chunk": 8}}
    run = types.SimpleNamespace(sizes=s, w=w, peaks=spec.load_json("peaks.json"))

    def snap(chunks, slots, seen, dense, sparse):
        one = lambda name, value, **labels: {"name": name, "samples": [{"labels": labels, "value": value}]}
        return {"metrics": [one("tony_serve_engine_chunks_total", chunks), one("tony_serve_decode_slots_total", slots),
                            one("tony_serve_visible_tokens_total", seen),
                            {"name": "tony_serve_prefill_chunks_total", "samples": [
                                {"labels": {"path": "dense"}, "value": dense}, {"labels": {"path": "sparse"}, "value": sparse}]}]}

    kernel = '%call = bf16[16,2,16,128]{3,2,1,0} custom-call(bf16[3,12673,2,64,128]{4,3,2,1,0} %kp), custom_call_target="tpu_custom_call"'
    other = '%other = bf16[2,16,2048,128]{3,2,1,0} custom-call(s8[2,2048,50688]{2,1,0} %m), custom_call_target="tpu_custom_call"'
    ctx = {"run": run, "device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "drive": {"snap0": snap(100, 1000, 8_000_000, 10, 10), "snap1": snap(200, 2000, 8_000_000 + 8000 * 6144, 10, 30)},
           "trace": {"op_time_s": {kernel: 0.012, other: 0.5}, "op_count": {kernel: 48, other: 6},
                     "modules": {"jit_decode_steps(123)": [0.1, 0.1], "jit_prefill_chunk(7)": [0.2]}}}
    peak = counts.peak_for("TPU v5 lite", run.peaks)
    means = {"live_slots": 10.0, "visible_per_slot": 6144.0, "sparse_chunk_share": 1.0}
    least = counts.roofline_seconds(*families.counts(s).sparse_decode_call(s, w["engine"], means), peak)
    assert read(ctx, kernel="sparse_decode", match="tpu_custom_call") == pytest.approx(100 * least * 24 * 2 / 0.012)
    # an execution the capture cut short counts as the part of a whole one it lasted: 0.1 + 0.1 + 0.04 is 2.4 executions
    cut = {**ctx["trace"], "modules": {"jit_decode_steps(123)": [0.1, 0.04, 0.1]}}
    assert read({**ctx, "trace": cut}, kernel="sparse_decode", match="tpu_custom_call") == pytest.approx(100 * least * 24 * 2.4 / 0.012)
    # a program without the counters (the parent), or a trace without the kernel: nothing, never 0 and no error
    assert read({**ctx, "drive": {"snap0": {"metrics": []}, "snap1": {"metrics": []}}}, kernel="sparse_decode", match="tpu_custom_call") is None
    assert read({**ctx, "trace": {"op_time_s": {other: 0.5}, "op_count": {other: 6}, "modules": ctx["trace"]["modules"]}},
                kernel="sparse_decode", match="tpu_custom_call") is None
    assert read({**ctx, "trace": None}, kernel="sparse_decode", match="tpu_custom_call") is None
    # a cell of a family that has no such kernel (a sweep of an unlisted llama workload scans every serve metric)
    llama = types.SimpleNamespace(sizes=spec.model_sizes(spec.config("mistral-7b"), "serve-1chip"), w=w, peaks=run.peaks)
    assert read({**ctx, "run": llama}, kernel="sparse_decode", match="tpu_custom_call") is None


def test_the_cells_entries_and_files(bench):
    spec = bench["spec"]
    B, cell = spec.benchmark(), "minicpm-sala.serve_longdoc"
    entry = next(c for c in B["configs"] if c["name"] == "minicpm-sala")
    assert entry["reduced"] == ["num_hidden_layers", "mixer_types"] and B["configs"][1] is entry
    assert B["workloads"][4]["name"] == cell and B["workloads"][4]["chips"] == 1
    # judged by tokens/s: the gaps of a request due in the window arrive mostly after it has closed (PERF.md section 2)
    assert {m["name"] for m in spec.cell_metrics(B, cell, "end_to_end")} == {"serve_out_tok_s", "setup_s"}
    per_layer = spec.cell_metrics(B, cell, "per_layer")
    assert {m["name"] for m in per_layer} == {
        "sparse_decode_roofline_pct.serve", "linear_decode_roofline_pct.serve", "linear_prefill_roofline_pct.serve",
        "sparse_prefill_roofline_pct.serve", "visible_share_pct.serve", "decode_step_ms.serve_tput", "chunk_period_ms.serve_tput",
        "launch_s", "slots_active_mean.serve", "host_share_pct.serve", "decode_batch_mean.serve",
        # PR 41: the host side of a pass
        "host_gap_pct.serve_tput", "host_offcpu_ms.serve_tput", "stream_write_ms.serve_tput", "fanout_delay_ms.serve_tput",
        "write_gap_pct.serve_tput",
        # PR 55: the start-up account and the compiles by source
        "submit_to_am_s", "allocate_s", "register_s", "runtime_init_s", "weights_s", "replica_warmup_s.serve",
        "setup_compile_s.serve", "setup_cache_load_s.serve", "setup_trace_lower_s.serve", "compile_ms_per_pass.serve",
        }
    # a per-layer metric moves an end-to-end metric its cell reports, and its file says what BENCHMARK.json says
    assert {m["moves"] for m in per_layer} == {"serve_out_tok_s", "setup_s"}
    assert all(spec.metric(m["name"])["moves"] == m["moves"] for m in B["per_layer"])
    launch = next(m for m in B["per_layer"] if m["name"] == "launch_s")
    assert launch["workloads"] == [w["name"] for w in B["workloads"]] and len(B["workloads"]) >= 5
    w = spec.workload(cell)
    assert (w["engine"]["slots"], w["engine"]["max_len"], w["engine"]["prefill_chunk"], w["engine"]["decode_chunk"]) == (
        16, 50688, 2048, 8)
    assert w["engine"]["page_len"] == 64 and w["engine"]["num_pages"] == 16 * (50688 // 64) + 1
    p, a = w["traffic"]["prompt_len"], w["traffic"]["answer_len"]
    assert (p["min"], p["max"], a["min"], a["max"]) == (12288, 49152, 384, 1536) and p["max"] + a["max"] == 50688
    published = {"hidden_size": 4096, "intermediate_size": 16384, "num_attention_heads": 32, "num_key_value_heads": 2,
                 "head_dim": 128, "vocab_size": 73448, "lightning_nh": 32, "lightning_nkv": 32, "lightning_head_dim": 128,
                 "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 256, "mup_denominator": 32, "rope_theta": 10000,
                 "rms_norm_eps": 1e-06, "max_position_embeddings": 524288}
    cfg = spec.config("minicpm-sala")
    assert {k: cfg[k] for k in published} == published and cfg["num_hidden_layers"]["source"] == 32


def test_the_rehearsal_cell_runs_end_to_end_on_the_cpu(tmp_path, bench, startup_account):
    """`tiny-minicpm-sala.serve` through run.py: the `tony serve` path, the
    router, the replica registered through the family's hook, chunked prefill
    and paged decode under the interpreter, and the harness's own comparison
    with the reference: `correct`."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT, "HOME": str(tmp_path), "TMPDIR": str(tmp_path)}
    proc = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), "--workload", TINY + ".serve",
                           "--seed", str(2 ** 31 + 29), "--seconds", "3", "--trace", "0"],
                          cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 2, proc.stdout[-3000:]
    assert last["device"]["platform"] == "cpu" and "serve_out_tok_s" in last["metrics"]
    # the host side's account of a pass (docs/observability.md "Where a pass's host time goes"): the window's
    # two registry snapshots, which the run left behind, give each of its three metrics something to read
    from readers import registry_delta  # benchmark/ is on the path while `bench` lives

    ctl = os.path.join(ROOT, ".bench_work", TINY + ".serve", "out", "ctl")
    drive = {tag: json.load(open(os.path.join(ctl, f"snap.{name}.json"))) for tag, name in (("snap0", "open"), ("snap1", "close"))}
    for name in ("host_offcpu_ms.serve_tput", "stream_write_ms.serve_tput", "fanout_delay_ms.serve_tput"):
        value = registry_delta.read({"drive": drive}, **bench["spec"].metric(name)["args"])
        assert value is not None and value >= 0.0, name
    # PR 55: the same run's start-up by stage (its .jhist's stamps) and its compiles by source (snap0), read by the
    # listed cells' readers, and the window's compile time printed
    startup_account(bench["spec"], TINY + ".serve")
