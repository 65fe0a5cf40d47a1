"""The falcon_h1 family on the CPU at a tiny size (`tiny-falcon-h1`: hidden 64, two
layers, each a Mamba-2 mixer of 4 heads of 32 over a state of 16 in 2 B/C groups
beside rotary attention of 10 / 2 heads of 16 (FIVE query heads a kv head, as
published) on one normed input, an FFN of 160, a convolution of 4 taps with a bias,
the published muP scalars, float32; pages of 16 positions): the state-space scan's
chunk and step forms WITH GROUPS against the position-at-a-time recurrence, the
program against the family's plain reference (forward; prefill chunks then decode
through the engine's pool, state and tail), every scalar shown to matter, the
engines that were there as they were, and the family's files through the
benchmark's harness.

Tolerances. The chunk form differs from the recurrence in the order of its sums
and in taking a decay as exp of a difference of running sums: 2e-5 of the largest
output, as for one group (tests/test_granite_hybrid.py; 4e-7 seen); the step form
is the recurrence's own arithmetic. On logits: the seeded model's are of size ONE
(the configuration's `assumed.matrix_init`: every scalar cancels against its
matrix in the draw; 3.8 at most here); program and reference, both float32, agree
to 2.4e-6 on them and LOGIT_TOL is 2e-5; the float8 control moves them by 0.4, and
a scalar set to 1 by 0.02 to 3.
"""
import dataclasses
import hashlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
TINY, MAX_LEN, PAGE = "tiny-falcon-h1", 128, 16
LOGIT_TOL = 2e-5
CONFIG, CELL = "falcon-h1-34b", "falcon-h1-34b.serve_rewrite"


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
    module, cfg = families.load("falcon_h1").program(sizes, MAX_LEN, PAGE)
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


# -- the scan with B/C groups: chunk and step against the recurrence a position at a time -----------
def _scan_inputs(seed, T, H, P, N, G, decay=(-7.0, -3.0)):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (T, H, P))
    B, C = jax.random.normal(ks[1], (T, G, N)), jax.random.normal(ks[2], (T, G, N))
    dt = jnp.exp(jax.random.uniform(ks[3], (T, H), minval=np.log(0.001), maxval=np.log(0.5)))
    g = -jnp.exp(jax.random.uniform(ks[4], (T, H), minval=decay[0], maxval=decay[1]))
    return x, dt, g, B, C, jax.random.normal(ks[5], (H,)), jax.random.normal(ks[6], (H, P, N))


#: (T, heads, head width, state, groups, block)
GROUPED = {"two-groups-of-two": (64, 4, 8, 16, 2, 16), "heads-of-128-over-256": (32, 4, 128, 256, 2, 16),
           "the-published-heads-and-groups": (16, 32, 128, 256, 2, 16), "three-groups-of-an-odd-count": (48, 9, 8, 16, 3, 16),
           "a-group-a-head": (32, 4, 32, 16, 4, 16)}
DECAYS = {"random-decays": (-7.0, -3.0), "every-rate-at-once": (-12.0, 5.0)}


def _close(got, want, state, new, ssd):
    return jnp.abs(got - want).max() < 2e-5 * jnp.abs(want).max() and jnp.abs(new - ssd.lanes(state)).max() < 2e-5 * jnp.abs(state).max()


@pytest.mark.parametrize("decay", list(DECAYS))
@pytest.mark.parametrize("shape", list(GROUPED))
def test_the_grouped_chunk_form_is_the_recurrence(interpreted, shape, decay):
    from tony_tpu.ops import ssd

    T, H, P, N, G, block = GROUPED[shape]
    args = _scan_inputs(1, T, H, P, N, G, DECAYS[decay])
    want, state = ssd.ssd_scan(*args)
    got, new = ssd.ssd_chunk(*args[:6], ssd.lanes(args[6]), block=block)
    assert _close(got, want, state, new, ssd)


@pytest.mark.parametrize("valid", [1, 19, 32, 45])
def test_a_padded_grouped_chunks_state_stops_at_valid(interpreted, valid):
    """The padded last chunk of a prompt, at a state of 256 and heads of 128 in two groups."""
    from tony_tpu.ops import ssd

    args = _scan_inputs(2, 48, 4, 128, 256, 2, (-4.0, 2.0))
    want, state = ssd.ssd_scan(*(a[:valid] for a in args[:5]), *args[5:])
    got, new = ssd.ssd_chunk(*args[:6], ssd.lanes(args[6]), jnp.int32(valid), block=16)
    assert _close(got[:valid], want, state, new, ssd)


@pytest.mark.parametrize("shape", list(GROUPED))
def test_the_grouped_step_form_is_the_recurrence(interpreted, shape):
    """One position a slot, each slot its own state: `ssd_scan` over one position."""
    from tony_tpu.ops import ssd

    _, H, P, N, G, _ = GROUPED[shape]
    S = 3
    x, dt, g, B, C, D, _ = _scan_inputs(4, S, H, P, N, G)
    state = jax.random.normal(jax.random.PRNGKey(9), (S, H, P, N))
    got, new = ssd.ssd_step(x, dt, g, B, C, D, ssd.lanes(state))
    for s in range(S):
        want, after = ssd.ssd_scan(x[s:s + 1], dt[s:s + 1], g[s:s + 1], B[s:s + 1], C[s:s + 1], D, state[s])
        assert jnp.abs(got[s] - want[0]).max() < 1e-6 * jnp.abs(want).max() and jnp.abs(new[s] - ssd.lanes(after)).max() < 1e-6


@pytest.mark.parametrize("form", ["scan", "chunk", "step"])
def test_a_head_reads_its_own_groups_b_and_c(interpreted, form):
    """Head h of H reads pair h // (H / G): heads and groups mirrored together give the mirrored result, the
    groups mirrored alone another one, and one pair given twice is the one-group call."""
    from tony_tpu.ops import ssd

    T, H, P, N = 16, 4, 8, 16
    x, dt, g, B, C, D, state = _scan_inputs(6, T, H, P, N, 2)

    def run(x, dt, g, D, state, B, C):
        if form == "scan":
            return ssd.ssd_scan(x, dt, g, B, C, D, state)[0]
        if form == "chunk":
            return ssd.ssd_chunk(x, dt, g, B, C, D, ssd.lanes(state), block=16)[0]
        return ssd.ssd_step(x, dt, g, B, C, D, jnp.broadcast_to(ssd.lanes(state), (T, N, H * P)))[0]    # every row a slot from the one state

    heads = (x, dt, g, D, state)
    got = run(*heads, B, C)
    mirrored = run(x[:, ::-1], dt[:, ::-1], g[:, ::-1], D[::-1], state[::-1], B[:, ::-1], C[:, ::-1])
    assert jnp.abs(mirrored[:, ::-1] - got).max() < 2e-5 * jnp.abs(got).max()
    assert jnp.abs(run(*heads, B[:, ::-1], C[:, ::-1]) - got).max() > 0.1 * jnp.abs(got).max()
    twice = lambda a: jnp.stack([a[:, 0], a[:, 0]], axis=1)
    assert jnp.abs(run(*heads, twice(B), twice(C)) - run(*heads, B[:, 0], C[:, 0])).max() < 2e-5 * jnp.abs(got).max()


def test_groups_that_do_not_divide_the_heads_are_refused(interpreted):
    from tony_tpu.ops import ssd

    x, dt, g, B, C, D, state = _scan_inputs(7, 16, 4, 8, 16, 3)
    with pytest.raises(ValueError, match="3 groups"):
        ssd.ssd_chunk(x, dt, g, B, C, D, ssd.lanes(state))
    with pytest.raises(ValueError, match="3 groups"):
        ssd.ssd_step(x, dt, g, B, C, D, jnp.broadcast_to(ssd.lanes(state), (16, 16, 32)))


@pytest.mark.parametrize("H,P,N,G,heads", [(128, 64, 128, 1, 16), (32, 128, 256, 2, 16), (4, 32, 16, 2, 2), (9, 8, 16, 3, 3), (4, 8, 16, 1, 4)],
                         ids=["granite-as-it-was", "falcon-h1-34b", "tiny-falcon-h1", "an-odd-count-in-three-groups", "all-heads-in-one-program"])
def test_a_programs_heads_lie_in_one_group(H, P, N, G, heads):
    """What a program of `ssd_chunk` holds: whole heads of ONE B/C group (its heads divide a group's)."""
    from tony_tpu.ops import ssd

    hb = ssd._group(H // G, P, ssd.HEADS)
    assert hb == heads and (H // G) % hb == 0


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
    assert np.abs(want[rows[0]:rows[1]]).max() > 1.0 and want[rows[0]:rows[1]].std() > 0.5   # logits of size one, not a row of zeros


def test_both_mixers_and_the_ffn_move_the_stream(tiny):
    """The draw's point (`assumed.matrix_init`): under the published scalars neither branch is a rounding
    error beside the embedding. The trunk of one layer with a branch's way-out scalar at 0 lies far from it whole."""
    R, sizes = tiny["reference"], tiny["sizes"]
    params = dict(tiny["params"], layers=tiny["params"]["layers"][:1])
    seq = jnp.asarray(_tokens(65, 64), jnp.int32)
    trunk = lambda **change: np.asarray(R.trunk(params, seq, dict(sizes, layers=1, **change), "f32", 32))
    whole = trunk()
    size = np.sqrt((whole ** 2).mean())
    for change in ({"attention_out_multiplier": 0.0}, {"ssm_out_multiplier": 0.0}, {"mlp_multipliers": (sizes["mlp_multipliers"][0], 0.0)}):
        assert np.sqrt(((trunk(**change) - whole) ** 2).mean()) > 0.2 * size, change
    assert 0.5 < size < 4.0


def test_one_layer_agrees_with_the_reference(tiny):
    """A trunk of ONE layer (both mixers and the FFN), rows of size one."""
    R, m, sizes, cfg = tiny["reference"], tiny["module"], tiny["sizes"], tiny["cfg"]
    params = dict(tiny["params"], layers=tiny["params"]["layers"][1:])
    seq = jnp.asarray(_tokens(66, 64), jnp.int32)
    want = np.asarray(R.trunk(params, seq, dict(sizes, layers=1), "f32", 32))
    one = dataclasses.replace(cfg, n_layers=1)
    got = np.asarray(m._chunk(params, seq, m._init_staging(one, 64), jnp.int32(64), one)[0])
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max() and np.abs(want).max() > 0.5


#: every muP scalar of the configuration: (the key, the index in a vector or None, the value it is moved TO)
SCALARS = [("embedding_multiplier", None, 1.0), ("lm_head_multiplier", None, 1.0), ("attention_in_multiplier", None, 0.5),
           ("attention_out_multiplier", None, 1.0), ("key_multiplier", None, 1.0), ("ssm_in_multiplier", None, 1.0),
           ("ssm_out_multiplier", None, 1.0), *[("ssm_multipliers", i, 1.0) for i in range(5)], *[("mlp_multipliers", i, 1.0) for i in range(2)]]


@pytest.mark.parametrize("key,index,to", SCALARS, ids=[k if i is None else f"{k}-{i}" for k, i, _ in SCALARS])
def test_every_scalar_matters_and_is_computed_where_it_is_written(tiny, bench, key, index, to):
    """The program with ONE scalar set to 1 (`attention_in_multiplier`, published as 1, to 0.5) on the seed's
    weights: it fails the tolerance against the reference at the published value, by a wide margin, and agrees
    with the reference given the same changed value: the scalar is computed, in both, and in the same place."""
    sizes, cfg = tiny["sizes"], tiny["cfg"]
    value = to if index is None else tuple(to if i == index else m for i, m in enumerate(sizes[key]))
    seq = _tokens(67, 64)
    got = np.asarray(tiny["module"].forward(tiny["params"], jnp.asarray([seq], jnp.int32), dataclasses.replace(cfg, **{key: value})))[0]
    want = tiny["ref_logits"](seq)
    moved = np.asarray(tiny["reference"].forward(tiny["params"], jnp.asarray(seq, jnp.int32), dict(sizes, **{key: value}), "f32", 32))
    assert np.abs(got - want).max() > 500 * LOGIT_TOL, np.abs(got - want).max()
    assert np.abs(got - moved).max() < LOGIT_TOL * max(1.0, np.abs(moved).max())


def test_the_lower_precision_control_fails_the_tolerance(tiny):
    """The reference with every matrix product's operands rounded to float8: what a program computed
    in a precision below the configuration's would read."""
    seq = _tokens(68, 96)
    ctl = np.asarray(tiny["reference"].forward(tiny["params"], jnp.asarray(seq + [0] * 32, jnp.int32), tiny["sizes"], "fp8", 32))[:96]
    assert np.abs(ctl - tiny["ref_logits"](seq)).max() > 1000 * LOGIT_TOL


def _prefill(tiny, prompt, chunk):
    progs = tiny["module"].serving_programs(tiny["cfg"], "paged")
    staging, logits, pos = progs.init_staging(MAX_LEN), None, 0
    while pos < len(prompt):
        take = min(chunk, len(prompt) - pos)
        pad = progs.prefill_pad(take, chunk, MAX_LEN - pos) if pos + take >= len(prompt) else 0
        toks = jnp.asarray(prompt[pos:pos + take] + [0] * pad, jnp.int32)[None]
        logits, staging = progs.prefill_chunk(tiny["params"], toks, staging, take)
        pos += take
    return staging, np.asarray(logits)[0]


def _admit(tiny, staging, slots, slot, n_prompt):
    m, cfg = tiny["module"], tiny["cfg"]
    n_pages = MAX_LEN // PAGE
    cache = m._init_cache(cfg, slots, MAX_LEN, PAGE, slots * n_pages + 1)
    row = np.arange(1 + slot * n_pages, 1 + (slot + 1) * n_pages).astype(np.int32)
    nc = -(-n_prompt // PAGE)
    fresh = np.zeros(n_pages, np.int32)
    fresh[:nc] = row[:nc]
    return m.insert_prefill(cache, staging, fresh, row, jnp.int32(slot), jnp.int32(n_prompt), jnp.int32(0), jnp.int32(nc))


@pytest.mark.parametrize("prompt_len,chunk", [(5, 32), (29, 32), (32, 32), (49, 32), (77, 32)],
                         ids=["one-short-chunk", "under-a-chunk", "one-whole-chunk", "two-chunks", "three-chunks-across-pages"])
def test_chunked_prefill_then_decode_agree_with_the_reference(tiny, prompt_len, chunk):
    """LOGITS: the last prompt row from the chunked prefill (one chunk, two, three; a padded last chunk whose
    state and tail stop at its last real row), then 6 decode steps a position at a time through EVERY
    layer's pages, carried state and carried convolution tail, crossing a page's edge: every step's logits
    against the reference's full forward of everything so far."""
    m, cfg = tiny["module"], tiny["cfg"]
    prompt = _tokens(prompt_len + chunk, prompt_len)
    staging, last = _prefill(tiny, prompt, chunk)
    assert np.abs(last - tiny["ref_logits"](prompt)[-1]).max() < LOGIT_TOL
    slots, slot = 2, 1
    cache = _admit(tiny, staging, slots, slot, prompt_len)
    assert cache.k.shape[0] == len(cache.state) == cache.tail.shape[0] == cfg.n_layers          # pages AND state AND tail in every layer
    seq, toks = list(prompt), np.zeros(slots, np.int32)
    toks[slot] = int(np.argmax(last))
    for _ in range(6):
        seq.append(int(toks[slot]))
        logits, cache = m.decode_logits(tiny["params"], cache, jnp.asarray(toks), cfg)
        assert np.abs(np.asarray(logits)[slot] - tiny["ref_logits"](seq)[-1]).max() < LOGIT_TOL
        toks[slot] = int(np.argmax(np.asarray(logits)[slot]))
    assert np.asarray(cache.lengths).tolist() == [0, prompt_len + 6]


# -- through the engine ---------------------------------------------------------------------------------
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


def test_the_engine_decodes_the_references_greedy_tokens_and_counts_what_the_rooflines_read(tiny):
    """Three requests over two slots through the normal engine (chunked prefill, admission, decode chunks
    of 4, a slot used again): each answer is the reference's greedy one, no prefix is shared, no routed
    FFN is counted, and the counters `falcon_h1_counts.window_means` reads move: the context a step sees,
    the prefill's rows and the causal pairs its attention sees."""
    before = _counters()
    eng = _engine(tiny)
    prompts = [_tokens(90 + i, n) for i, n in enumerate((40, 21, 67))]
    rids = [eng.submit(p, 9) for p in prompts]
    done = eng.run()
    for rid, p in zip(rids, prompts):
        assert done[rid] == _greedy(tiny, p, 9)
    moved = {k: v - before.get(k, 0) for k, v in _counters().items()}
    assert eng.prefix_hit_tokens == 0 and eng.programs.gather_prefix is None and eng.programs.routed_ffn_form is None
    # the rows as dispatched, a last chunk padded to its bucket: 40 = 32 + 8 (16), 21 (32), 67 = 32 + 32 + 3 (16)
    assert moved["tony_serve_prefill_tokens_total"] == 48 + 32 + 80 and moved["tony_serve_context_tokens_total"] > 0
    # the pairs of the REAL rows: a chunk's rows x the positions before it and its own causal half
    pairs = lambda *chunks: sum(t * pos + t * (t + 1) // 2 for pos, t in chunks)
    assert moved["tony_serve_prefill_pairs_total"] == pairs((0, 32), (32, 8)) + pairs((0, 21)) + pairs((0, 32), (32, 32), (64, 3))
    assert not any(k.startswith("tony_serve_expert") and v for k, v in moved.items())


def test_a_slot_used_again_reads_nothing_of_its_last_tenant(tiny):
    """Pages, state and tail stay in a released slot; the next admission overwrites all of a slot's:
    the same prompt twice, with every layer's cache poisoned in between."""
    eng = _engine(tiny, num_slots=1)
    prompt = _tokens(30, 37)
    first = eng.submit(prompt, 9)
    done = eng.run()
    c = eng.cache
    eng.cache = c._replace(k=jnp.full_like(c.k, 1e4), v=jnp.full_like(c.v, 1e4), state=tuple(jnp.full_like(s, 1e3) for s in c.state),
                           tail=jnp.full_like(c.tail, 1e3))
    again = eng.submit(prompt, 9)
    assert eng.run()[again] == done[first] == _greedy(tiny, prompt, 9)


def test_the_branches_are_named_in_the_programs(tiny):
    """A trace splits a layer between its branches by these scopes (docs/observability.md)."""
    m, cfg = tiny["module"], tiny["cfg"]
    staging = jax.eval_shape(lambda: m._init_staging(cfg, MAX_LEN))
    params = jax.eval_shape(lambda: tiny["params"])
    text = m.prefill_chunk.lower(params, jax.ShapeDtypeStruct((1, 32), jnp.int32), staging, jax.ShapeDtypeStruct((), jnp.int32), cfg).as_text(debug_info=True)
    for scope in ("falcon_h1.attn", "falcon_h1.ssm", "falcon_h1.ffn"):
        assert scope in text, scope


# -- the engines that were there, as they were ------------------------------------------------------
#: sha256 (16 hex) of the lowered text of `tiny-granite-hybrid`'s jitted serving programs, taken on the parent commit
#: (4068d63) by the code of `_lowered_granite_hybrid` below: the ONE-group path of ops/ssd.py is the program it was.
#: This PR edits one file those programs import, ops/ssd.py (B and C may come in groups; with [T, N] the call, its
#: operands, its index maps and its kernels' bodies are what they were), and appends one name to models/registry.py;
#: models/serving.py and models/paged_cache.py are not touched. The families older than granite_hybrid are held by
#: the tables of tests/test_dots3_note.py, tests/test_mistral4.py, tests/test_olmo_hybrid.py and tests/test_granite_hybrid.py.
PARENT_LOWERED_GRANITE_HYBRID = {"prefill_chunk": "0fce7728d40655fb", "insert": "69c05903c8d36adc", "decode_chunk": "a2c311b795fa3b8e"}


def _lowered_granite_hybrid(bench, max_len=128, page=16, chunk=32):
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config("tiny-granite-hybrid"), "serve-1chip")
    m, cfg = families.load("granite_hybrid").program(sizes, max_len, page)
    params = jax.eval_shape(lambda: bench["chipside"].seed_weights(sizes, 7))
    slots, n_pages = 2, max_len // page
    cache = jax.eval_shape(lambda: m._init_cache(cfg, slots, max_len, page, slots * n_pages + 1))
    staging = jax.eval_shape(lambda: m._init_staging(cfg, max_len))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    text = {
        "prefill_chunk": m.prefill_chunk.lower(params, i32(1, chunk), staging, i32(), cfg).as_text(),
        "insert": m.insert_prefill.lower(cache, staging, i32(n_pages), i32(n_pages), i32(), i32(), i32(), i32()).as_text(),
        "decode_chunk": m.decode_steps.lower(params, cache, i32(slots), jax.ShapeDtypeStruct((2,), jnp.uint32), cfg, 4).as_text(),
    }
    return {k: hashlib.sha256(v.encode()).hexdigest()[:16] for k, v in text.items()}


@pytest.mark.parametrize("program", list(PARENT_LOWERED_GRANITE_HYBRID))
def test_the_one_group_family_lowers_to_the_parents_text(bench, interpreted, program):
    assert _lowered_granite_hybrid(bench)[program] == PARENT_LOWERED_GRANITE_HYBRID[program]


def test_a_replica_finds_the_familys_presets():
    from tony_tpu.models import falcon_h1, registry

    presets = registry.presets()
    assert presets["falcon-h1-tiny"] is falcon_h1.PRESETS["falcon-h1-tiny"]
    assert registry.module_of(presets["falcon-h1-tiny"]) is falcon_h1 and {"tiny", "granite-hybrid-tiny", "solar-open2-tiny"} <= set(presets)
    params = jax.eval_shape(lambda: falcon_h1.init(jax.random.PRNGKey(0), falcon_h1.FALCON_H1_TINY))
    assert len(params["layers"]) == 2 and params["lm_head"].shape == params["embed"].shape == (256, 64)
    lp = params["layers"][0]
    assert lp["w_qkv"].shape == (64, (10 + 2 * 2) * 16) and lp["w_in"].shape == (64, 2 * 128 + 2 * 2 * 16) and lp["w_dt"].shape == (64, 4)
    assert lp["conv"].shape == (4, 128 + 64) and lp["y_norm"].shape == (128,) and lp["w_gate"].shape == (64, 160)


def test_the_modules_own_draw_is_the_references(tiny):
    """`tony serve --preset` draws through the module's `init`, the benchmark through the reference's
    `init_weights`: one draw, so that a served preset has the reference's scales."""
    R, m = tiny["reference"], tiny["module"]
    mine = m.init(R.seed_key(7), tiny["cfg"])
    assert jax.tree.structure(mine) == jax.tree.structure(tiny["params"])
    assert all(jax.tree.leaves(jax.tree.map(lambda a, b: bool((a == b).all()), mine, tiny["params"])))


# -- the family's files through the harness ---------------------------------------------------------
def test_the_family_resolves_sizes_program_reference_and_counts(bench):
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config(CONFIG), "serve-1chip")
    assert (sizes["layers"], sizes["vocab"], sizes["d_model"], sizes["d_ff"]) == (9, 130_560, 5120, 21_504)
    assert (sizes["ssm_heads"], sizes["ssm_head_dim"], sizes["ssm_state"], sizes["ssm_groups"], sizes["conv_taps"]) == (32, 128, 256, 2, 4)
    assert (sizes["heads"], sizes["kv_heads"], sizes["head_dim"], sizes["rope_theta"]) == (20, 4, 128, 1e11)
    assert len(sizes["ssm_multipliers"]) == 5 and len(sizes["mlp_multipliers"]) == 2 and sizes["attention_in_multiplier"] == 1.0
    hash(tuple(sorted(sizes.items())))                               # check.py keys its traced programs by the sizes
    module, cfg = families.load("falcon_h1").program(sizes, 3072)
    assert module.__name__ == "tony_tpu.models.falcon_h1" and cfg.d_inner == 4096 and cfg.conv_channels == 5120 and cfg.n_layers == 9
    assert cfg.ssm_multipliers == sizes["ssm_multipliers"] and cfg.key_multiplier == sizes["key_multiplier"] and cfg.max_seq == 3072
    assert families.reference(sizes).__name__ == "families.falcon_h1_reference" and families.reference(sizes).CONTROL == "fp8"
    assert families.counts(sizes).__name__ == "families.falcon_h1_counts"


def test_the_configuration_holds_every_published_key_at_its_published_value(bench):
    """Against the catalog's row where the catalog is installed; the two cut keys
    carry the source's value beside the deployment's."""
    cfg = bench["spec"].config(CONFIG)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("the catalog of architectures is not installed here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Falcon-H1-34B-Instruct")
    cut = ["num_hidden_layers", "vocab_size"]
    assert cfg["source"] == row["source_url"] and sorted(cfg["reduced"]) == cut
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key]["source"] == value, key
        else:
            assert cfg[key] == value, key
    assert [cfg[k]["serve-1chip"] for k in cut] == [9, 130_560]
    assert "eight pipeline stages of nine" in cfg["deployments"]["serve-1chip"] and "ONE of the two vocabulary matrices" in cfg["reduced"]["vocab_size"]
    assert all(set(entry) == {"value", "why"} and len(entry["why"]) > 40 for entry in cfg["assumed"].values())
    tiny = bench["spec"].config(TINY)
    scalars = [k for k in cfg if k.endswith("_multiplier") or k.endswith("_multipliers")]
    assert len(scalars) == 9 and all(tiny[k] == cfg[k] for k in scalars) and tiny["mamba_n_groups"] == 2                  # the same scalars at the tiny size


@pytest.mark.parametrize("change,error,match", [
    ({"attn_layer_indices": [0, 8]}, ValueError, "attn_layer_indices"),
    ({"mamba_norm_before_gate": True}, ValueError, "mamba_norm_before_gate"),
    ({"tie_word_embeddings": True}, ValueError, "tie_word_embeddings"),
    ({"sliding_window": 4096}, KeyError, "does not know"),
    ({"mamba_d_ssm": 10240}, ValueError, "inner width"),
    ({"mamba_n_groups": 3}, ValueError, "divides"),
    ({"ssm_multipliers": [0.5, 0.5]}, ValueError, "ssm_multipliers"),
    ({"assumed": {}}, KeyError, "assumed"),
], ids=["attention-in-some-layers", "norm-before-gate", "a-tied-head", "an-unknown-key", "expand-x-hidden", "groups-that-do-not-divide",
        "a-short-vector", "nothing-assumed"])
def test_the_family_refuses_what_it_does_not_know(bench, change, error, match):
    cfg = {**bench["spec"].config(CONFIG), **change}
    with pytest.raises(error, match=match):
        bench["spec"].model_sizes(cfg, "serve-1chip")


@pytest.mark.parametrize("choice", ["block", "rope", "in_proj_order", "ssm_output", "dt_limits", "mlp_multipliers_order", "state_dtype", "ssm_init",
                                    "matrix_init"])
def test_an_assumed_choice_is_one_value(bench, choice):
    cfg = bench["spec"].config(CONFIG)
    cfg = {**cfg, "assumed": {**cfg["assumed"], choice: {"value": "another", "why": "a guess"}}}
    with pytest.raises(ValueError, match=choice):
        bench["spec"].model_sizes(cfg, "serve-1chip")


def test_the_one_group_family_still_refuses_what_its_configuration_does_not_publish(bench):
    """Groups are in ops/ssd.py now; granite_hybrid's family computes ITS configuration's one group only (its
    gated norm spans the whole inner width). Its message still calls one group a law ("here"): the file is the
    benchmark's, and a PR that is not a `benchmark` one edits none of those (CHANGES.md, PR 59)."""
    cfg = {**bench["spec"].config("granite-4.0-h-small"), "mamba_n_groups": 2}
    with pytest.raises(ValueError, match="one group"):
        bench["spec"].model_sizes(cfg, "serve-1chip")


def test_a_checkout_without_the_model_module_has_no_such_family(bench, monkeypatch):
    """The benchmark's files laid over a parent commit: run.py's own process says
    so and exits 2 before any launch."""
    fam = bench["families"].load("falcon_h1")
    monkeypatch.setattr(fam, "PROGRAM", os.path.join(ROOT, "tony_tpu", "models", "no_such_model.py"))
    with pytest.raises(bench["families"].NoFamily, match="from the commit"):
        bench["spec"].model_sizes(bench["spec"].config(CONFIG), "serve-1chip")


def test_the_counts_on_hand_worked_sizes(bench):
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config(CONFIG), "serve-1chip")
    C, engine = families.counts(sizes), spec.workload(CELL)["engine"]
    assert C.state_bytes(sizes) == 4 * 32 * 128 * 256 == 4_194_304 and C.step_ops(sizes) == 5 * 32 * 128 * 256      # 4.19 MB a layer and slot
    assert C.layer_params(sizes) == 31_457_280 + (5120 * 9248 + 4096 * 5120 + 5 * 5120 + 3 * 32 + 4096) + 3 * 5120 * 21_504 == 430_109_792
    assert C.total_params(sizes) == 9 * 430_109_792 + 2 * 130_560 * 5120 == 5_207_922_528                            # 5.208 B held, 10.42 GB
    assert 72 * C.layer_params(sizes) + 2 * 261_120 * 5120 == 33_641_773_824                                          # the name's 34 B
    means = {"live_slots": 40.0, "context_per_slot": 1100.0, "prefill_rows_per_chunk": 512.0, "prefill_pairs_per_chunk": 512 * 256 + 512 * 513 // 2}
    assert C.ssd_decode_call(sizes, engine, means) == (5 * 32 * 128 * 256 * 40 * 9, 2 * 4_194_304 * 40 * 9)          # 3.0 GB a step at 40 live
    ops, nbytes = C.ssd_prefill_call(sizes, engine, means)
    assert ops == 9 * 512 * (2 * 2 * 128 * 256 + 32 * (2 * 128 * 128 + 4 * 256 * 128)) and nbytes == 9 * (2 * 512 * (2 * 4096 + 2 * 512) + 2 * 4_194_304)
    assert C.attn_decode_call(sizes, engine, means) == (4 * 20 * 128 * 40 * 1100 * 9, 18_432 * 40 * 1100)            # 18,432 B a position over nine layers
    ops, nbytes = C.attn_prefill_call(sizes, engine, means)
    assert ops == 4 * 20 * 128 * means["prefill_pairs_per_chunk"] * 9 and nbytes == 9 * 2 * 128 * (2 * 4 * (means["prefill_pairs_per_chunk"] / 512 + 256) + 2 * 20 * 512)
    assert re.search(C.ssd_decode_operands(sizes, engine), "(f32[48,1,4096]{2,1,0}, f32[48,256,4096]{2,1,0:T(8,128)}) custom-call")
    assert re.search(C.ssd_prefill_operands(sizes, engine), "(bf16[1024,4096]{1,0}, f32[256,4096]{1,0:T(8,128)}) custom-call")
    assert not re.search(C.ssd_prefill_operands(sizes, engine), "f32[48,256,4096]")                                   # not the slots' state
    assert re.search(C.attn_decode_operands(sizes, engine), "bf16[9,577,4,256,128]{4,3,2,1,0}") and not re.search(C.attn_decode_operands(sizes, engine), "bf16[9,1,4,3072,128]")
    assert re.search(C.attn_prefill_operands(sizes, engine), "bf16[9,1,4,3072,128]") and not re.search(C.attn_prefill_operands(sizes, engine), "bf16[9,577,4,256,128]")
    assert C.ssd_decode_calls(sizes, engine) == ("decode_steps", 8) == C.attn_decode_calls(sizes, engine)
    assert C.ssd_prefill_calls(sizes, engine) == ("prefill_chunk", 1) == C.attn_prefill_calls(sizes, engine)


def test_window_means_from_the_replicas_counters(bench):
    sizes = bench["spec"].model_sizes(bench["spec"].config(CONFIG), "serve-1chip")
    C = bench["families"].counts(sizes)
    deltas = {"tony_serve_engine_chunks_total": 10, "tony_serve_decode_slots_total": 400, "tony_serve_context_tokens_total": 400 * 8 * 1100,
              "tony_serve_prefill_tokens_total": 7 * 1024, "tony_serve_prefill_chunks_total": 7, "tony_serve_prefill_pairs_total": 7 * 600_000}
    means = C.window_means(lambda name, where=None: deltas.get(name), {"decode_chunk": 8})
    assert means == {"live_slots": 40.0, "context_per_slot": 1100.0, "prefill_rows_per_chunk": 1024.0, "prefill_pairs_per_chunk": 600_000.0}
    assert C.window_means(lambda name, where=None: None, {}) is None                   # a program without the counters


def test_the_cell_is_the_issues(bench):
    spec = bench["spec"]
    w, b = spec.workload(CELL), spec.benchmark()
    t, e = w["traffic"], w["engine"]
    assert w["kind"] == "serve" and w["deployment"] == "serve-1chip" and w["chips"] == 1 and w["config"] == CONFIG
    assert t["arrivals"] == {"process": "closed", "clients": e["slots"], "ramp_s": 8.0} and 32 <= e["slots"] <= 48     # callers = slots, the most that fit
    assert "sessions" not in t and "prefix" not in t and t["draw_seed"] == 59
    assert t["prompt_len"] == {"dist": "lognormal", "median": 768, "sigma": 0.7, "min": 128, "max": 2048}
    assert t["answer_len"] == {"dist": "lognormal", "median": 512, "sigma": 0.5, "min": 64, "max": 1024}
    assert {k: e[k] for k in ("max_len", "page_len", "prefill_chunk", "decode_chunk")} == {"max_len": 3072, "page_len": 256, "prefill_chunk": 1024, "decode_chunk": 8}
    assert e["num_pages"] == e["slots"] * (e["max_len"] // e["page_len"]) + 1 and 2048 + 1024 <= e["max_len"]        # every slot's pages; the longest request fits
    assert w["check"]["samples"] == 2 and 0 < w["check"]["worst_gap_limit"] and "control" in w["check"]["why"]
    listed = {m["name"] for m in spec.cell_metrics(b, CELL, "per_layer")}
    rooflines = {"ssd_decode_roofline_pct.serve": ("ssd_decode", "ssd_step"), "ssd_prefill_roofline_pct.serve": ("ssd_prefill", "ssd_chunk"),
                 "attn_decode_roofline_pct.serve": ("attn_decode", "tpu_custom_call"), "attn_prefill_roofline_pct.serve": ("attn_prefill", "chunk_prefill_attention")}
    assert set(rooflines) | {"launch_s", "decode_step_ms.serve_tput", "slots_active_mean.serve", "host_gap_pct.serve_tput", "weights_s"} <= listed
    assert not {m for m in listed if m.startswith(("moe_", "held_share", "expert_rows", "prefix_hit", "delta_", "kda_"))}
    assert {m["name"] for m in spec.cell_metrics(b, CELL, "end_to_end")} == {"serve_out_tok_s", "setup_s"}
    for name, (kernel, match) in rooflines.items():
        m = next(m for m in b["per_layer"] if m["name"] == name)
        assert CELL in m["workloads"] and spec.metric(name)["reader"] == "family_roofline" and spec.metric(name)["args"] == {"kernel": kernel, "match": match}
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"] and entry["source"] == spec.config(CONFIG)["source"]
    assert entry["file"] == "benchmark/configs/falcon-h1-34b.json" and len(b["workloads"]) >= 12 and sum(c["chips"] == 4 for c in b["workloads"]) == 1
    assert b["workloads"][11]["name"] == CELL and b["configs"][8]["name"] == CONFIG                                    # appended behind the eleven cells of eight configurations


# -- the family's rehearsal (benchmark/tests/test_falcon_h1_rehearsal.py), run with the suite
def _rehearsal():
    import importlib.util

    spec_ = importlib.util.spec_from_file_location("falcon_h1_rehearsal", os.path.join(BENCH, "tests", "test_falcon_h1_rehearsal.py"))
    module = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(module)
    return module


test_the_rehearsal_cell_runs_end_to_end_on_the_cpu = _rehearsal().test_the_rehearsal_cell_runs_end_to_end_on_the_cpu
test_the_control_lies_far_from_the_reference_and_the_program_on_it = _rehearsal().test_the_control_lies_far_from_the_reference_and_the_program_on_it
