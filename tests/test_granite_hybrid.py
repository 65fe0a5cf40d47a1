"""The granite_hybrid family on the CPU at a tiny size (`tiny-granite-hybrid`: hidden
64, two periods of [mamba x 2, attention, mamba], 4 state-space heads of 32 over a
state of 16, 4 / 2 attention heads of 16, 8 experts of 32 top-3 of which 4 are
held, a shared expert of 48, a convolution of 4 taps with a bias, float32; pages
of 16 positions): the state-space scan's chunk and step forms against the
position-at-a-time recurrence, the convolution with a bias against the literal
one, the program against the family's plain reference (forward; prefill chunks
then decode through the engine's cache), the two shares of a layer against the
uncut reference, the engines that were there as they were, and the family's
files through the benchmark's harness.

Tolerances. The chunk form differs from the recurrence in the order of its sums
and in taking a decay as exp of a difference of running sums: 2e-5 of the
largest output is ten times what was seen (3e-6) whatever the decays; the step
form is the recurrence's own arithmetic (0 seen). A state kept in bfloat16 moves
the same outputs by 1e-2 and fails (`test_a_bfloat16_state_fails_the_tolerance`).
On logits: the seeded model's are SMALL (0.02 at most at this size: a random tied
head divided by `logits_scaling`, under an embedding drawn so that no position
predicts its own token; the configuration's `assumed.embed_init`); program and
reference, both float32, agree to 3e-8 on them and LOGIT_TOL is 1e-6; the float8
control moves them by 8e-3.
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
TINY, MAX_LEN, PAGE = "tiny-granite-hybrid", 128, 16
LOGIT_TOL = 1e-6
CONFIG, CELL = "granite-4.0-h-small", "granite-4.0-h-small.serve_assist"


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
    module, cfg = families.load("granite_hybrid").program(sizes, MAX_LEN, PAGE)
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


# -- the scan: chunk and step against the recurrence a position at a time ----------------------------
def _scan_inputs(seed, T, H, P, N, decay=(-7.0, -3.0), dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (T, H, P)).astype(dtype)
    B, C = jax.random.normal(ks[1], (T, N)).astype(dtype), jax.random.normal(ks[2], (T, N)).astype(dtype)
    dt = jnp.exp(jax.random.uniform(ks[3], (T, H), minval=np.log(0.001), maxval=np.log(0.5)))
    g = -jnp.exp(jax.random.uniform(ks[4], (T, H), minval=decay[0], maxval=decay[1]))
    return x, dt, g, B, C, jax.random.normal(ks[5], (H,)), jax.random.normal(ks[6], (H, P, N))


SCAN_CASES = {
    "random-decays": dict(),
    "decays-near-0": dict(decay=(0.0, 5.0)),                      # g down to -148 a token: a state wiped at a token
    "decays-near-1": dict(decay=(-12.0, -9.0)),
    "every-rate-at-once": dict(decay=(-12.0, 5.0)),
}
SHAPES = {"four-blocks": (64, 4, 8, 16, 16), "heads-of-64-over-128": (32, 2, 64, 128, 16), "an-odd-head-count": (48, 3, 8, 16, 16),
          "one-block": (16, 4, 32, 16, 128)}


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("case", list(SCAN_CASES))
def test_the_chunk_form_is_the_recurrence(interpreted, case, shape):
    from tony_tpu.ops import ssd

    T, H, P, N, block = SHAPES[shape]
    args = _scan_inputs(1, T, H, P, N, **SCAN_CASES[case])
    want, state = ssd.ssd_scan(*args)
    got, new = ssd.ssd_chunk(*args[:6], ssd.lanes(args[6]), block=block)
    assert jnp.abs(got - want).max() < 2e-5 * jnp.abs(want).max() and jnp.abs(new - ssd.lanes(state)).max() < 2e-5 * jnp.abs(state).max()


@pytest.mark.parametrize("valid", [1, 11, 16, 37, 64])
def test_a_padded_chunks_state_stops_at_valid(interpreted, valid):
    """Rows past `valid` neither decay nor write: the state is the recurrence's
    after `valid` positions, and the rows before it read what they read unpadded."""
    from tony_tpu.ops import ssd

    args = _scan_inputs(2, 64, 4, 8, 16, decay=(-4.0, 2.0))
    want, state = ssd.ssd_scan(*(a[:valid] for a in args[:5]), *args[5:])
    got, new = ssd.ssd_chunk(*args[:6], ssd.lanes(args[6]), jnp.int32(valid), block=16)
    assert jnp.abs(got[:valid] - want).max() < 2e-5 * jnp.abs(want).max() and jnp.abs(new - ssd.lanes(state)).max() < 2e-5 * jnp.abs(state).max()


@pytest.mark.parametrize("cut", [16, 32, 48])
def test_a_chunk_boundary_inside_a_prompt_carries_the_state(interpreted, cut):
    """Two chunks, the second from the first's state: the one recurrence."""
    from tony_tpu.ops import ssd

    args = _scan_inputs(3, 64, 4, 8, 16)
    want, state = ssd.ssd_scan(*args)
    first, mid = ssd.ssd_chunk(*(a[:cut] for a in args[:5]), args[5], ssd.lanes(args[6]), block=16)
    second, new = ssd.ssd_chunk(*(a[cut:] for a in args[:5]), args[5], mid, block=16)
    got = jnp.concatenate([first, second])
    assert jnp.abs(got - want).max() < 2e-5 * jnp.abs(want).max() and jnp.abs(new - ssd.lanes(state)).max() < 2e-5 * jnp.abs(state).max()


@pytest.mark.parametrize("case", list(SCAN_CASES))
def test_the_step_form_is_the_recurrence(interpreted, case):
    """One position a slot, each slot its own state: `ssd_scan` over one position."""
    from tony_tpu.ops import ssd

    S, H, P, N = 3, 4, 8, 16
    x, dt, g, B, C, D, _ = _scan_inputs(4, S, H, P, N, **SCAN_CASES[case])
    state = jax.random.normal(jax.random.PRNGKey(9), (S, H, P, N))
    got, new = ssd.ssd_step(x, dt, g, B, C, D, ssd.lanes(state))
    for s in range(S):
        want, after = ssd.ssd_scan(x[s:s + 1], dt[s:s + 1], g[s:s + 1], B[s:s + 1], C[s:s + 1], D, state[s])
        assert jnp.abs(got[s] - want[0]).max() < 1e-6 * jnp.abs(want).max() and jnp.abs(new[s] - ssd.lanes(after)).max() < 1e-6


def test_a_bfloat16_state_fails_the_tolerance(interpreted):
    """What the float32 state is for: the same chunk from a state rounded to
    bfloat16 at every block's edge lies a thousand tolerances away."""
    from tony_tpu.ops import ssd

    args = _scan_inputs(5, 64, 4, 8, 16, decay=(-9.0, -6.0))
    want, _ = ssd.ssd_scan(*args)
    state, rows = ssd.lanes(args[6]), []
    for at in range(0, 64, 16):
        y, state = ssd.ssd_chunk(*(a[at:at + 16] for a in args[:5]), args[5], state.astype(jnp.bfloat16).astype(jnp.float32), block=16)
        rows.append(y)
    assert jnp.abs(jnp.concatenate(rows) - want).max() > 1e-3 * jnp.abs(want).max()


# -- the convolution with a bias ------------------------------------------------------------------------
def _literal_conv(u, tail, w, b):
    xp = np.concatenate([np.asarray(tail, np.float64), np.asarray(u, np.float64)])
    acc = sum(np.asarray(w, np.float64)[j] * xp[j:j + u.shape[0]] for j in range(w.shape[0])) + np.asarray(b, np.float64)
    return acc / (1 + np.exp(-acc))


@pytest.mark.parametrize("rows,valid", [(16, None), (32, 32), (32, 19), (64, 2)])
def test_the_convolution_with_a_bias_is_the_literal_one(interpreted, rows, valid):
    from tony_tpu.ops.delta_rule import short_conv_chunk, short_conv_step

    ks = jax.random.split(jax.random.PRNGKey(rows), 4)
    C, taps = 160, 4
    u, tail = jax.random.normal(ks[0], (rows, C)), jax.random.normal(ks[1], (taps - 1, C))
    w, b = jax.random.normal(ks[2], (taps, C)), jax.random.normal(ks[3], (C,))
    y, kept = short_conv_chunk(u, tail, w, None if valid is None else jnp.int32(valid), b)
    assert np.abs(np.asarray(y) - _literal_conv(u, tail, w, b)).max() < 1e-5
    upto = rows if valid is None else valid
    assert np.array_equal(np.asarray(kept), np.concatenate([np.asarray(tail), np.asarray(u)[:upto]])[-(taps - 1):])
    # a position at a time from the same tail: the step form, slot by slot
    t, out = jnp.stack([tail, tail]), []
    for i in range(4):
        o, t = short_conv_step(jnp.stack([u[i], u[i]]), t, w, b)
        out.append(o[1])
    assert np.abs(np.asarray(jnp.stack(out)) - _literal_conv(u, tail, w, b)[:4]).max() < 1e-5
    assert np.abs(np.asarray(short_conv_chunk(u, tail, w)[0]) - _literal_conv(u, tail, w, 0 * b)).max() < 1e-5   # and without one, as before


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
    assert np.abs(want[rows[0]:rows[1]]).max() > 0.005   # logits of size 0.01-0.02, not a row of zeros


@pytest.mark.parametrize("kind", ["mamba", "attention"])
def test_one_layer_of_each_kind_agrees_with_the_reference(tiny, kind):
    """A trunk of ONE layer of the kind (mixer and routed FFN), rows of size 0.8."""
    R, m, sizes, cfg = tiny["reference"], tiny["module"], tiny["sizes"], tiny["cfg"]
    at = sizes["layer_types"].index(kind)
    params = dict(tiny["params"], layers=[tiny["params"]["layers"][at]], **{k: tiny["params"][k][at:at + 1] for k in R.BANKS})
    seq = jnp.asarray(_tokens(65, 64), jnp.int32)
    want = np.asarray(R.trunk(params, seq, dict(sizes, layer_types=(kind,), layers=1), "f32", 32)[0])
    one = dataclasses.replace(cfg, layer_types=(kind,))
    got = np.asarray(m._chunk(params, seq, m._init_staging(one, 64), jnp.int32(64), one)[0])
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max() and np.abs(want).max() > 0.5


def test_the_shares_add_up_to_the_uncut_layer(tiny, bench):
    """The routed parts that shares (0, 4) and (4, 4) compute, with the shared
    expert counted once, are what the uncut 8-expert reference gives for the
    whole FFN: program and reference alike."""
    from tony_tpu.parallel.expert import MoEConfig, held_expert_ffn

    R, sizes = tiny["reference"], tiny["sizes"]
    whole = bench["chipside"].seed_weights(dict(sizes, held=(0, 8)), 3)
    lp, banks = whole["layers"][1], tuple(whole[k] for k in R.BANKS)
    h = jax.random.normal(jax.random.PRNGKey(0), (40, sizes["d_model"]))
    uncut, _ = R.routed_ffn_and_slack(h, lp, banks, sizes, held=(0, 8), layer=1)
    parts, got = [], []
    for first in (0, 4):
        own = tuple(b[:, first:first + 4] for b in banks)
        parts.append(R.routed_ffn_and_slack(h, lp, own, sizes, held=(first, 4), shared=False, layer=1)[0])
        got.append(held_expert_ffn(h, lp["router"], None, *own, jnp.int32(1), MoEConfig(num_experts=8, top_k=3, held=(first, 4)))[0])
    shared = R.routed_ffn_and_slack(h, lp, banks, sizes, held=(0, 8), layer=1)[0] - R.routed_ffn_and_slack(h, lp, banks, sizes, held=(0, 8), shared=False, layer=1)[0]
    assert np.abs(np.asarray(parts[0] + parts[1] + shared - uncut)).max() < 1e-5 and np.abs(np.asarray(uncut)).max() > 0.5
    assert np.abs(np.asarray(got[0] + got[1] + shared - uncut)).max() < 1e-5
    assert np.abs(np.asarray(parts[0])).max() > 0.1 and np.abs(np.asarray(parts[1])).max() > 0.1     # neither share is empty


def test_the_gates_are_the_softmax_over_the_chosen_logits(tiny):
    """The reference's gate (top-k of the logits, softmax over those) is the
    program's shared routing (softmax over all, top-k renormalised)."""
    from tony_tpu.parallel.expert import MoEConfig, _gating

    R, sizes = tiny["reference"], tiny["sizes"]
    lp = tiny["params"]["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(1), (24, sizes["d_model"]))
    gates, chosen = R._route(jnp.einsum("td,de->te", h, lp["router"], precision="highest"), sizes)
    vals, idx, _, _ = _gating(h[None], lp["router"], MoEConfig(num_experts=8, top_k=3, held=(0, 4)))
    dense = np.zeros((24, 8), np.float32)
    np.put_along_axis(dense, np.asarray(idx)[0], np.asarray(vals)[0], axis=1)
    assert np.abs(dense - np.asarray(gates)).max() < 1e-6 and np.asarray(chosen).sum(axis=1).tolist() == [3] * 24


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


@pytest.mark.parametrize("prompt_len,chunk", [(5, 32), (29, 32), (49, 32), (77, 32)],
                         ids=["one-short-chunk", "under-a-chunk", "chunks-do-not-divide", "long-across-pages"])
def test_chunked_prefill_then_decode_agree_with_the_reference(tiny, prompt_len, chunk):
    """LOGITS: the last prompt row from the chunked prefill (a padded last chunk
    whose state and tail stop at its last real row), then 6 decode steps a
    position at a time through the paged pool, the carried state and the carried
    convolution tail, crossing a page's edge: every step's logits against the
    reference's full forward of everything so far."""
    m, cfg = tiny["module"], tiny["cfg"]
    prompt = _tokens(prompt_len + chunk, prompt_len)
    staging, last = _prefill(tiny, prompt, chunk)
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


def test_the_engine_decodes_the_references_greedy_tokens_and_counts_its_experts(tiny):
    """Three requests over two slots through the normal engine (chunked prefill,
    admission, decode chunks of 4, a slot used again): each answer is the
    reference's greedy one, and the four expert counters move with the chunks:
    of the choices 3 a row and layer, about half land on the 4 held of 8."""
    before = _counters()
    eng = _engine(tiny)
    prompts = [_tokens(90 + i, n) for i, n in enumerate((40, 21, 67))]
    rids = [eng.submit(p, 9) for p in prompts]
    done = eng.run()
    for rid, p in zip(rids, prompts):
        assert done[rid] == _greedy(tiny, p, 9)
    moved = {k: v - before.get(k, 0) for k, v in _counters().items()}
    rows, most, choices, touched = (moved[f"tony_serve_{k}_total"] for k in ("expert_rows", "expert_rows_max", "expert_choices", "experts_touched"))
    assert choices % (3 * 8) == 0 and 0.25 * choices < rows < 0.75 * choices and 0 < most <= rows and 0 < touched <= rows
    assert eng.prefix_hit_tokens == 0 and eng.programs.gather_prefix is None and eng.programs.prefix_usable is None


@pytest.mark.parametrize("case", ["the-tiny-engine-is-staged", "a-chunk-by-its-slots-a-prefill-by-its-rows", "the-served-widths"])
def test_the_engine_counts_its_routed_ffn_programs_by_form(tiny, case):
    """`tony_serve_routed_ffn_programs_total{form}`: a decode chunk is counted under the form its slots give,
    a prefill chunk under the form its padded rows give (parallel/expert.held_ffn_form through
    `ServingPrograms.routed_ffn_form`): float32 rows take `ragged_dot`, so the tiny engine is staged
    throughout; at the served widths in bfloat16 the decode batch is in the kernel and a 2048-row chunk staged."""
    import dataclasses

    from tony_tpu.models import granite_hybrid as GH

    name = "tony_serve_routed_ffn_programs_total"
    if case == "the-served-widths":
        cfg = dataclasses.replace(tiny["cfg"], d_model=4096, d_expert=768, dtype="bfloat16")
        form = GH.serving_programs(cfg, "paged").routed_ffn_form
        assert [form(rows) for rows in (64, 512, 1024, 2048)] == ["in_kernel", "in_kernel", "staged", "staged"]
        assert GH.serving_programs(tiny["cfg"], "paged").routed_ffn_form(2) == "staged"
        return
    eng = _engine(tiny)
    if case == "a-chunk-by-its-slots-a-prefill-by-its-rows":
        eng.programs = eng.programs._replace(routed_ffn_form=lambda rows: "in_kernel" if rows == 2 else "staged")
    before = _counters()
    eng.submit(_tokens(7, 40), 9)                                       # two prefill chunks (32 + 8 padded to 16), then chunks of 4
    eng.run()
    moved = {k: v - before.get(k, 0) for k, v in _counters().items()}
    chunks, prefills = moved["tony_serve_engine_chunks_total"], moved["tony_serve_prefill_chunks_total{dense}"]
    assert chunks >= 2 and prefills == 2
    if case == "the-tiny-engine-is-staged":
        assert moved[name + "{staged}"] == chunks + prefills and moved.get(name + "{in_kernel}", 0) == 0
    else:
        assert moved[name + "{in_kernel}"] == chunks and moved[name + "{staged}"] == prefills


def test_a_slot_used_again_reads_nothing_of_its_last_tenant(tiny):
    """State and tail stay in a released slot; the next admission overwrites all
    of a slot's: the same prompt twice, with the cache poisoned in between."""
    eng = _engine(tiny, num_slots=1)
    prompt = _tokens(30, 37)
    first = eng.submit(prompt, 9)
    done = eng.run()
    c = eng.cache
    eng.cache = c._replace(k=jnp.full_like(c.k, 1e4), v=jnp.full_like(c.v, 1e4), state=tuple(jnp.full_like(s, 1e3) for s in c.state),
                           tail=jnp.full_like(c.tail, 1e3))
    again = eng.submit(prompt, 9)
    assert eng.run()[again] == done[first]


# -- the engines that were there, as they were ------------------------------------------------------
#: sha256 (16 hex) of the lowered text of `tiny-olmo-hybrid`'s jitted serving programs, taken on the parent commit
#: (920d5c3) by the code of `_lowered_olmo_hybrid` below. The five families older than it are held by
#: tests/test_dots3_note.py's, tests/test_mistral4.py's and tests/test_olmo_hybrid.py's tables, whose hashes this PR
#: found as they stood. This PR edits ONE file those programs import: ops/delta_rule.py (`short_conv_chunk` and
#: `short_conv_step` gain `bias=None`; with None the call, its operands and its kernel's body are what they were), and
#: appends one line to models/registry.py; models/serving.py is not touched.
#: PR 58 gives a program of `delta_chunk` several heads (ops/delta_rule.py): `prefill_chunk`, the one program that calls it, a8070b28081f1aad
#: until then; the other three stand as they stood
PARENT_LOWERED_OLMO_HYBRID = {"prefill_chunk": "d5b15725174aa157", "insert": "467f8fef5bdd71ae", "gather_prefix": "39af1ed27e7717dc",
                              "decode_chunk": "8ef8c3cb33512f03"}


def _lowered_olmo_hybrid(bench, max_len=128, page=16, chunk=32):
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config("tiny-olmo-hybrid"), "serve-1chip")
    m, cfg = families.load("olmo_hybrid").program(sizes, max_len, page, 8)
    params = jax.eval_shape(lambda: bench["chipside"].seed_weights(sizes, 7))
    slots, n_pages = 2, max_len // page
    cache = jax.eval_shape(lambda: m._init_cache(cfg, slots, max_len, page, slots * n_pages + 1))
    staging = jax.eval_shape(lambda: m._init_staging(cfg, max_len))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    text = {
        "prefill_chunk": m.prefill_chunk.lower(params, i32(1, chunk), staging, i32(), cfg).as_text(),
        "insert": m.insert_prefill.lower(cache, staging, i32(n_pages), i32(n_pages), i32(), i32(), i32(), i32(), i32()).as_text(),
        "gather_prefix": m.gather_prefix.lower(staging, cache, i32(n_pages), i32(), i32()).as_text(),
        "decode_chunk": m.decode_steps.lower(params, cache, i32(slots), jax.ShapeDtypeStruct((2,), jnp.uint32), cfg, 4).as_text(),
    }
    return {k: hashlib.sha256(v.encode()).hexdigest()[:16] for k, v in text.items()}


@pytest.mark.parametrize("program", list(PARENT_LOWERED_OLMO_HYBRID))
def test_the_newest_family_before_this_one_lowers_to_the_parents_text(bench, interpreted, program):
    assert _lowered_olmo_hybrid(bench)[program] == PARENT_LOWERED_OLMO_HYBRID[program]


def test_a_replica_finds_the_familys_presets():
    from tony_tpu.models import granite_hybrid, registry

    presets = registry.presets()
    assert presets["granite-hybrid-tiny"] is granite_hybrid.PRESETS["granite-hybrid-tiny"]
    assert registry.module_of(presets["granite-hybrid-tiny"]) is granite_hybrid and {"tiny", "sala-tiny", "mistral4-tiny", "olmo-hybrid-tiny"} <= set(presets)
    params = jax.eval_shape(lambda: granite_hybrid.init(jax.random.PRNGKey(0), granite_hybrid.GRANITE_HYBRID_TINY))
    assert len(params["layers"]) == 8 and "lm_head" not in params and params["we_gate"].shape == (8, 4, 64, 32)
    assert params["layers"][0]["w_in"].shape == (64, 128 + 160) and params["layers"][2]["w_qkv"].shape == (64, 128)


# -- the family's files through the harness ---------------------------------------------------------
def test_the_family_resolves_sizes_program_reference_and_counts(bench):
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config(CONFIG), "serve-1chip")
    assert sizes["layers"] == 10 and sizes["layer_types"] == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert (sizes["num_experts"], sizes["held"], sizes["top_k"], sizes["vocab"]) == (72, (0, 36), 10, 50_176)
    assert (sizes["ssm_heads"], sizes["ssm_head_dim"], sizes["ssm_state"], sizes["conv_taps"]) == (128, 64, 128, 4)
    assert (sizes["heads"], sizes["kv_heads"], sizes["head_dim"], sizes["d_expert"], sizes["d_shared"]) == (32, 8, 128, 768, 1536)
    hash(tuple(sorted(sizes.items())))                               # check.py keys its traced programs by the sizes
    module, cfg = families.load("granite_hybrid").program(sizes, 3072)
    assert module.__name__ == "tony_tpu.models.granite_hybrid" and cfg.d_inner == 8192 and cfg.conv_channels == 8448
    assert cfg.moe.held == (0, 36) and cfg.moe.scoring == "softmax" and cfg.count("mamba") == 9 and cfg.attention_multiplier == 1 / 128
    assert families.reference(sizes).__name__ == "families.granite_hybrid_reference" and families.reference(sizes).CONTROL == "fp8"
    assert families.counts(sizes).__name__ == "families.granite_hybrid_counts"


def test_the_configuration_holds_every_published_key_at_its_published_value(bench):
    """Against the catalog's row where the catalog is installed; the four cut keys
    carry the source's value beside the deployment's."""
    cfg = bench["spec"].config(CONFIG)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("the catalog of architectures is not installed here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "granite-4.0-h-small")
    cut = ["layer_types", "num_hidden_layers", "num_local_experts", "vocab_size"]
    assert cfg["source"] == row["source_url"] and sorted(cfg["reduced"]) == cut
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key]["source"] == value, key
        else:
            assert cfg[key] == value, key
    assert [cfg[k]["serve-1chip"] for k in cut] == [row["config"]["layer_types"][:10], 10, 36, 50_176]
    assert "four pipeline stages" in cfg["deployments"]["serve-1chip"] and "PAIR" in cfg["deployments"]["serve-1chip"]
    assert cfg["assumed"]["head_dim"]["value"] == 128 and cfg["assumed"]["state_dtype"]["value"] == "float32"


@pytest.mark.parametrize("change,error,match", [
    ({"position_embedding_type": "rope"}, ValueError, "position_embedding_type"),
    ({"mamba_conv_bias": False}, ValueError, "mamba_conv_bias"),
    ({"tie_word_embeddings": False}, ValueError, "tie_word_embeddings"),
    ({"sliding_window": 4096}, KeyError, "does not know"),
    ({"mamba_n_groups": 8}, ValueError, "one group"),
    ({"mamba_expand": 4}, ValueError, "inner width"),
    ({"assumed": {}}, KeyError, "assumed"),
], ids=["a-rope", "no-conv-bias", "an-untied-head", "an-unknown-key", "grouped-b-and-c", "another-inner-width", "nothing-assumed"])
def test_the_family_refuses_what_it_does_not_know(bench, change, error, match):
    cfg = {**bench["spec"].config(CONFIG), **change}
    with pytest.raises(error, match=match):
        bench["spec"].model_sizes(cfg, "serve-1chip")


def test_an_assumed_choice_is_one_value(bench):
    cfg = bench["spec"].config(CONFIG)
    cfg = {**cfg, "assumed": {**cfg["assumed"], "ssm_output": {"value": "rmsnorm_then_gate", "why": "a guess"}}}
    with pytest.raises(ValueError, match="ssm_output"):
        bench["spec"].model_sizes(cfg, "serve-1chip")


def test_a_checkout_without_the_model_module_has_no_such_family(bench, monkeypatch):
    """The benchmark's files laid over a parent commit: run.py's own process says
    so and exits 2 before any launch."""
    fam = bench["families"].load("granite_hybrid")
    monkeypatch.setattr(fam, "PROGRAM", os.path.join(ROOT, "tony_tpu", "models", "no_such_model.py"))
    with pytest.raises(bench["families"].NoFamily, match="from the commit"):
        bench["spec"].model_sizes(bench["spec"].config(CONFIG), "serve-1chip")


def test_the_counts_on_hand_worked_sizes(bench):
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config(CONFIG), "serve-1chip")
    C, engine = families.counts(sizes), spec.workload(CELL)["engine"]
    assert C.state_bytes(sizes) == 4 * 128 * 64 * 128 == 4_194_304 and C.step_ops(sizes) == 5 * 128 * 64 * 128     # 4.19 MB a layer and slot
    assert C.expert_params(sizes) == 4096 * 1536 + 768 * 4096 == 9_437_184
    assert C.mixer_params(sizes, "mamba") == 4096 * 16_768 + 8192 * 4096 + 4 * 8448 + 8448 + 3 * 128 + 8192 == 102_286_976
    assert C.mixer_params(sizes, "attention") == 4096 * 6144 + 4096 * 4096 == 41_943_040
    assert C.layer_params(sizes, "mamba") == 102_286_976 + 18_874_368 + 294_912 + 36 * 9_437_184 == 461_194_880
    assert C.total_params(sizes) == 9 * 461_194_880 + 400_850_944 + 50_176 * 4096 == 4_757_125_760                # 4.757 B held
    means = {"live_slots": 60.0, "held_rows_per_step": 3000.0, "touched_per_step": 350.0, "prefill_rows_per_chunk": 512.0}
    assert C.ssd_decode_call(sizes, engine, means) == (5 * 128 * 64 * 128 * 60 * 9, 2 * 4_194_304 * 60 * 9)       # 4.5 GB a step
    ops, nbytes = C.ssd_prefill_call(sizes, engine, means)
    assert ops == 9 * 512 * (2 * 128 * 128 + 128 * (2 * 128 * 64 + 4 * 128 * 64)) and nbytes == 9 * (2 * 512 * (2 * 8192 + 256) + 2 * 4_194_304)
    ops, nbytes = C.moe_decode_call(sizes, engine, means)
    assert (ops, nbytes) == (2 * 9_437_184 * 3000, 2 * (350 * 9_437_184 + 2 * 3000 * 4096))                       # the slabs COUNTED, not expected
    ops, nbytes = C.moe_prefill_call(sizes, engine, means)
    assert ops == 2 * 9_437_184 * 2560 * 10 and nbytes == 2 * 10 * (36 * 9_437_184 + 2 * 2560 * 4096)
    assert re.search(C.ssd_decode_operands(sizes, engine), "(f32[64,1,8192]{2,1,0}, f32[64,128,8192]{2,1,0:T(8,128)}) custom-call")
    assert re.search(C.ssd_prefill_operands(sizes, engine), "(bf16[2048,8192]{1,0}, f32[128,8192]{1,0:T(8,128)}) custom-call")
    assert not re.search(C.ssd_prefill_operands(sizes, engine), "f32[64,128,8192]")                                 # not the slots' state
    assert re.search(C.moe_decode_operands(sizes, engine), "bf16[10,36,4096,768]{3,2,1,0}") and re.search(C.moe_prefill_operands(sizes, engine), "bf16[10,36,768,4096]")
    assert C.ssd_decode_calls(sizes, engine) == ("decode_steps", 8) == C.moe_decode_calls(sizes, engine)
    assert C.ssd_prefill_calls(sizes, engine) == ("prefill_chunk", 1) == C.moe_prefill_calls(sizes, engine)


def test_window_means_from_the_replicas_counters(bench):
    sizes = bench["spec"].model_sizes(bench["spec"].config(CONFIG), "serve-1chip")
    C = bench["families"].counts(sizes)
    deltas = {"tony_serve_engine_chunks_total": 10, "tony_serve_decode_slots_total": 600, "tony_serve_expert_rows_total": 10 * 8 * 3000,
              "tony_serve_experts_touched_total": 10 * 8 * 355, "tony_serve_prefill_tokens_total": 7 * 1024, "tony_serve_prefill_chunks_total": 7}
    means = C.window_means(lambda name, where=None: deltas.get(name), {"decode_chunk": 8})
    assert means == {"live_slots": 60.0, "held_rows_per_step": 3000.0, "touched_per_step": 355.0, "prefill_rows_per_chunk": 1024.0}
    assert C.window_means(lambda name, where=None: None, {}) is None                   # a program without the counters


def test_the_cell_is_the_issues(bench):
    spec = bench["spec"]
    w, b = spec.workload(CELL), spec.benchmark()
    t, e = w["traffic"], w["engine"]
    assert w["kind"] == "serve" and w["deployment"] == "serve-1chip" and w["chips"] == 1 and w["config"] == CONFIG
    assert t["arrivals"] == {"process": "closed", "clients": e["slots"], "ramp_s": 8.0} and e["slots"] in (64, 48)  # 48: the issue's named fallback
    assert "sessions" not in t and "prefix" not in t and t["draw_seed"] == 53
    assert t["prompt_len"] == {"dist": "lognormal", "median": 512, "sigma": 0.8, "min": 64, "max": 2048}
    assert t["answer_len"] == {"dist": "lognormal", "median": 256, "sigma": 0.5, "min": 32, "max": 1024}
    assert {k: e[k] for k in ("max_len", "page_len", "prefill_chunk", "decode_chunk")} == {"max_len": 3072, "page_len": 256, "prefill_chunk": 2048, "decode_chunk": 8}
    assert e["num_pages"] == e["slots"] * (e["max_len"] // e["page_len"]) + 1 and 2048 + 1024 <= e["max_len"]       # every slot's pages; the longest request fits
    listed = {m["name"] for m in spec.cell_metrics(b, CELL, "per_layer")}
    assert {"ssd_decode_roofline_pct.serve", "ssd_prefill_roofline_pct.serve", "moe_decode_roofline_pct.serve", "moe_prefill_roofline_pct.serve",
            "expert_rows_max_over_mean.serve", "held_share_pct.serve", "launch_s", "decode_step_ms.serve_tput", "slots_active_mean.serve"} <= listed
    assert "prefix_hit_pct.serve" not in listed and "delta_decode_roofline_pct.serve" not in listed
    assert {m["name"] for m in spec.cell_metrics(b, CELL, "end_to_end")} == {"serve_out_tok_s", "setup_s"}
    for name, kernel, match in (("ssd_decode_roofline_pct.serve", "ssd_decode", "ssd_step"), ("ssd_prefill_roofline_pct.serve", "ssd_prefill", "ssd_chunk")):
        m = next(m for m in b["per_layer"] if m["name"] == name)
        # the cell that brought the metric is its first; a later family with the kernel joins behind it (falcon-h1-34b, PR 59)
        assert m["workloads"][0] == CELL and spec.metric(name)["reader"] == "family_roofline" and spec.metric(name)["args"] == {"kernel": kernel, "match": match}
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "layer_types", "num_local_experts", "vocab_size"]
    assert entry["file"] == "benchmark/configs/granite-4.0-h-small.json" and len(b["workloads"]) >= 10 and sum(c["chips"] == 4 for c in b["workloads"]) == 1


# -- the family's rehearsal (benchmark/tests/test_granite_hybrid_rehearsal.py), run with the suite
def _rehearsal():
    import importlib.util

    spec_ = importlib.util.spec_from_file_location("granite_hybrid_rehearsal", os.path.join(BENCH, "tests", "test_granite_hybrid_rehearsal.py"))
    module = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(module)
    return module


test_the_rehearsal_cell_runs_end_to_end_on_the_cpu = _rehearsal().test_the_rehearsal_cell_runs_end_to_end_on_the_cpu
test_the_control_lies_far_from_the_reference_and_the_program_on_it = _rehearsal().test_the_control_lies_far_from_the_reference_and_the_program_on_it
