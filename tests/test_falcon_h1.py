"""The falcon_h1 family on the CPU at a tiny size (`tiny-falcon-h1`: hidden 64, two
layers, each a Mamba-2 mixer of 4 heads of 32 over a state of 16 in 2 B/C groups
beside rotary attention of 10 / 2 heads of 16 (FIVE query heads a kv head, as
published) on one normed input, an FFN of 160, a convolution of 4 taps with a bias,
the published muP scalars, float32; pages of 16 positions): the program against
the family's plain reference (forward; prefill chunks then decode through the
engine's pool, state and tail), every scalar shown to matter, and the engine. The
grouped scan's forms are tests/test_falcon_h1_scan.py; the engines that were
there, the family's files through the benchmark's harness and its rehearsal are
tests/test_falcon_h1_family.py.

Tolerances. On logits: the seeded model's are of size ONE
(the configuration's `assumed.matrix_init`: every scalar cancels against its
matrix in the draw; 3.8 at most here); program and reference, both float32, agree
to 2.4e-6 on them and LOGIT_TOL is 2e-5; the float8 control moves them by 0.4, and
a scalar set to 1 by 0.02 to 3.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

TINY, MAX_LEN, PAGE = "tiny-falcon-h1", 128, 16
LOGIT_TOL = 2e-5


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


def test_the_modules_own_draw_is_the_references(tiny):
    """`tony serve --preset` draws through the module's `init`, the benchmark through the reference's
    `init_weights`: one draw, so that a served preset has the reference's scales."""
    R, m = tiny["reference"], tiny["module"]
    mine = m.init(R.seed_key(7), tiny["cfg"])
    assert jax.tree.structure(mine) == jax.tree.structure(tiny["params"])
    assert all(jax.tree.leaves(jax.tree.map(lambda a, b: bool((a == b).all()), mine, tiny["params"])))
