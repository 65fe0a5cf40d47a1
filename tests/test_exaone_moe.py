"""The exaone_moe family on the CPU at a tiny size (`tiny-exaone-moe`: hidden
128, 8 heads of 16 over 2 kv heads, 1 dense + 4 routed layers of kinds window,
window, window, full, window; window 8; 8 experts of which 4 held, top-2;
float32): the program against the family's plain reference, the share against
the whole, the cache of each kind of layer, the engines that were there as they
were, and the family's files through the benchmark's harness.

Tolerances: program and reference both compute in float32 here and differ in
the order of their sums only (online against whole softmax, sorted rows against
every expert masked): 2e-5 on logits of size 1-4 is ten times what was seen
(3e-6) and a thousandth of what one wrongly chosen expert moves.
"""
import dataclasses
import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
TINY, MAX_LEN, PAGE = "tiny-exaone-moe", 128, 16
LOGIT_TOL = 2e-5


@pytest.fixture(scope="module")
def tiny(bench, monkeypatch_module):
    monkeypatch_module.setenv("TONY_PALLAS_INTERPRET", "1")
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config(TINY), "serve-1chip")
    module, cfg = families.load("exaone_moe").program(sizes, MAX_LEN)
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
    seq = _tokens(64, 64)
    got = np.asarray(tiny["module"].forward(tiny["params"], jnp.asarray([seq], jnp.int32), tiny["cfg"]))[0]
    return got, tiny["ref_logits"](seq)


@pytest.mark.parametrize("rows", [(0, 8), (4, 24), (24, 64)], ids=["inside-the-window", "across-its-edge", "past-it"])
def test_forward_agrees_with_the_reference(one_forward, rows):
    got, want = one_forward
    assert np.abs(got[rows[0]:rows[1]] - want[rows[0]:rows[1]]).max() < LOGIT_TOL
    assert np.abs(want[rows[0]:rows[1]]).max() > 0.5   # logits of size 1-4, not a row of zeros


def test_the_prediction_module_agrees_with_the_reference(bench, tiny):
    """`mtp_logits` is in the model and not in the replica: its weights, its
    forward pass and the reference's function of the same name, row by row."""
    module, reference = tiny["module"], tiny["reference"]
    sizes = {**tiny["sizes"], "mtp_layers": 1}
    cfg = dataclasses.replace(tiny["cfg"], mtp_layers=1)
    params = bench["chipside"].seed_weights(sizes, 11)
    assert set(params["mtp"]) == {"proj", "hidden_norm", "embed_norm", "layers"} and params["mtp"]["proj"].shape == (1, 256, 128)
    seq = jnp.asarray(_tokens(3, 32), jnp.int32)
    got = module.mtp_logits(params, module.hidden_states(params, seq, cfg), seq, cfg)
    want = reference.mtp_logits(params, reference.hidden(params, seq, sizes, "f32", 32), seq, sizes)
    assert got.shape == want.shape == (1, 31, 256)
    # row 30 reads the token past the end (token 0 wrapped round) in both: the caller's to drop
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < LOGIT_TOL and np.abs(np.asarray(want)).max() > 0.5


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


@pytest.mark.parametrize("prompt_len,chunk", [(5, 16), (37, 16), (77, 32), (14, 32)],
                         ids=["shorter-than-the-window", "chunks-do-not-divide", "long-across-pages", "one-chunk-to-a-page-edge"])
def test_chunked_prefill_then_paged_decode_agree_with_the_reference(tiny, prompt_len, chunk):
    """Logits' consequences, step by step: the last prompt position from the
    chunked prefill (logits), then 12 decode steps through the full layer's
    pages and the window layers' rings, crossing a page's edge (16) and the
    ring's end (24): each step's greedy token is the reference's argmax over its
    full forward of everything so far, and stands no lower than its best."""
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


# -- the share ------------------------------------------------------------------------------------
@pytest.fixture(scope="module")
def whole_layer(bench, tiny):
    """One routed layer's weights with ALL 8 experts, and normed rows to feed it."""
    sizes = {**tiny["sizes"], "held": (0, 8)}
    params = bench["chipside"].seed_weights(sizes, 13)
    lp = {k: v[2] for k, v in params["layers"].items()}
    h = jax.random.normal(jax.random.PRNGKey(5), (24, sizes["d_model"]), jnp.float32)
    return sizes, lp, h


def test_the_shares_add_up_to_the_whole_layer(tiny, whole_layer):
    """Two replicas hold experts 0-3 and 4-7. Each routes over all 8, normalises
    over both chosen and adds its own experts' part; the shared expert is
    computed by both alike and counted once. Together: the uncut reference's
    layer output."""
    from tony_tpu.models.exaone_moe import _swiglu
    from tony_tpu.parallel.expert import held_expert_ffn

    sizes, lp, h = whole_layer
    want = np.asarray(tiny["reference"].routed_ffn(h, lp, sizes, held=(0, 8)))
    parts, rows = [], []
    for first in (0, 4):
        moe = dataclasses.replace(tiny["cfg"], held=(first, 4)).moe
        banks = tuple(lp[k][None, first:first + 4] for k in ("we_gate", "we_up", "we_down"))
        y, r = held_expert_ffn(h, lp["router"], lp["router_bias"], *banks, jnp.int32(0), moe)
        parts.append(np.asarray(y))
        rows.append(np.asarray(r))
        # a share alone is what the reference gives for that share
        alone = np.asarray(tiny["reference"].routed_ffn(h, {**lp, **{k: lp[k][first:first + 4] for k in ("we_gate", "we_up", "we_down")}},
                                                         sizes, held=(first, 4), shared=False))
        assert np.abs(parts[-1] - alone).max() < 1e-5
    shared = np.asarray(_swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"]))
    assert np.abs(parts[0] + parts[1] + shared - want).max() < 1e-5 and np.abs(want).max() > 0.5
    assert np.abs(parts[0]).max() > 0.1 and np.abs(parts[1]).max() > 0.1          # neither share is empty
    assert rows[0].sum() + rows[1].sum() == 24 * 2                                 # every choice lands on one of the two


def test_the_bias_chooses_and_the_score_weighs():
    """Scores 0.9 / 0.8 / 0.7 / 0.6 with a bias that lifts the last over the
    second: the chosen two are experts 0 and 3 (the order of s + b), weighted
    by their scores alone (2.5 x 0.9 / 1.5 and 2.5 x 0.6 / 1.5), not by s + b
    and not the two largest scores."""
    from tony_tpu.parallel.expert import MoEConfig, _gating

    s = np.array([0.9, 0.8, 0.7, 0.6])
    logits = jnp.asarray(np.log(s / (1 - s)), jnp.float32)[None, None]          # the router is the identity
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.25], jnp.float32)
    cfg = MoEConfig(num_experts=4, top_k=2, scoring="sigmoid", routed_scale=2.5)
    vals, idx, _, _ = _gating(logits, jnp.eye(4, dtype=jnp.float32), cfg, None, bias)
    assert sorted(np.asarray(idx)[0, 0].tolist()) == [0, 3]
    got = dict(zip(np.asarray(idx)[0, 0].tolist(), np.asarray(vals)[0, 0].tolist()))
    assert got[0] == pytest.approx(2.5 * 0.9 / 1.5, rel=1e-5) and got[3] == pytest.approx(2.5 * 0.6 / 1.5, rel=1e-5)
    # without the bias, the two largest scores
    _, idx0, _, _ = _gating(logits, jnp.eye(4, dtype=jnp.float32), cfg, None, None)
    assert sorted(np.asarray(idx0)[0, 0].tolist()) == [0, 1]
    # the Mixtral form is what it was: softmax, top-k, renormalised to one
    v, i, _, _ = _gating(logits, jnp.eye(4, dtype=jnp.float32), MoEConfig(num_experts=4, top_k=2), None)
    p = np.exp(np.asarray(logits)[0, 0]) / np.exp(np.asarray(logits)[0, 0]).sum()
    assert np.asarray(i)[0, 0].tolist() == [0, 1] and np.allclose(np.asarray(v)[0, 0], p[:2] / p[:2].sum(), rtol=1e-5)


@pytest.mark.parametrize("case,scores,held,want", [
    ("a-held-expert-just-inside", [0.9, 0.702, 0.7, 0.1], (0, 2), 0.002),     # expert 1 is chosen, 0.002 above expert 2
    ("a-held-expert-just-outside", [0.9, 0.702, 0.7, 0.1], (2, 2), 0.002),    # expert 2 is left out, 0.002 below expert 1
    ("the-tie-is-among-absent-experts", [0.702, 0.1, 0.9, 0.7], (1, 2), 0.2),   # held 1 and 2 lie 0.602 below and 0.2 above it
], ids=lambda v: v if isinstance(v, str) else None)
def test_the_slack_is_the_nearest_held_experts_distance_from_the_edge(bench, case, scores, held, want):
    reference = bench["families"].reference({"module": "exaone_moe"})
    s = jnp.asarray([scores], jnp.float32)
    chosen = reference._choose(s, jnp.zeros(4), {"top_k": 2})
    assert float(reference.held_slack(s, jnp.zeros(4), chosen, held)[0]) == pytest.approx(want, abs=1e-6)
    # the bias moves the edge as it moves the choice: lifting the last expert by 0.65 puts it 0.048 above expert 1
    lifted = jnp.asarray([0.0, 0.0, 0.0, 0.65], jnp.float32)
    chosen = reference._choose(s, lifted, {"top_k": 2})
    if case == "a-held-expert-just-inside":
        assert float(reference.held_slack(s, lifted, chosen, held)[0]) == pytest.approx(0.048, abs=1e-6)


def test_the_reference_states_no_logits_where_a_bfloat16_program_may_route_otherwise(tiny):
    """`forward` for a program that computes in bfloat16: a row of zeros where a
    held expert lies within TIE_MARGIN of the edge of the chosen set in some
    routed layer, the float32 configuration's row everywhere else; the control's
    precision and a float32 program get every row."""
    reference, sizes, params = tiny["reference"], tiny["sizes"], tiny["params"]
    seq = jnp.asarray(_tokens(21, 96), jnp.int32)
    plain = np.asarray(reference.forward(params, seq, sizes, "f32", 32))
    _, slack = reference.trunk(params, seq, sizes, "f32", 32)
    slack = np.asarray(slack)
    assert np.isfinite(slack).all() and (slack > 0).all() and np.abs(plain).max(axis=1).min() > 0.5
    margin = float(np.sort(slack)[10])          # a margin that takes ten of these positions out
    old = reference.TIE_MARGIN["bfloat16"]
    try:
        reference.TIE_MARGIN["bfloat16"] = margin
        stated = np.asarray(reference.forward(params, seq, {**sizes, "dtype": "bfloat16"}, "f32", 32))
        control = np.asarray(reference.forward(params, seq, {**sizes, "dtype": "bfloat16"}, "fp8", 32))
    finally:
        reference.TIE_MARGIN["bfloat16"] = old
    blank = slack < margin
    assert blank.sum() == 10 and not stated[blank].any() and np.array_equal(stated[~blank], plain[~blank])
    assert np.abs(control).max(axis=1).min() > 0.5          # the control's own rows are all there: its choices are compared
    assert 0 < old < 0.05 and set(reference.TIE_MARGIN) == {"bfloat16"}


def test_the_decode_ffn_multiplies_the_held_and_chosen_experts_only(tiny):
    """No array of the decode step's routed FFN has a row for every expert at
    the expert's width: [rows, experts, width] is what computing every expert
    and masking makes (generate._ffn_with_cache). Rows are sorted by held
    expert and multiplied a group at a time; a choice of an absent expert has
    no row."""
    from tony_tpu.parallel.expert import held_expert_ffn

    cfg, lp = tiny["cfg"], {k: v[1] for k, v in tiny["params"]["layers"].items()}
    banks = tuple(tiny["params"]["layers"][k] for k in ("we_gate", "we_up", "we_down"))
    h = jax.random.normal(jax.random.PRNGKey(1), (6, cfg.d_model), jnp.float32)
    fn = lambda h: held_expert_ffn(h, lp["router"], lp["router_bias"], *banks, jnp.int32(1), cfg.moe)
    shapes = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            shapes.update(tuple(v.aval.shape) for v in eqn.outvars if hasattr(v.aval, "shape"))
            for sub in jax.core.jaxprs_in_params(eqn.params) if hasattr(jax.core, "jaxprs_in_params") else []:
                walk(sub)
            for p in eqn.params.values():
                if hasattr(p, "jaxpr"):
                    walk(p.jaxpr)

    walk(jax.make_jaxpr(fn)(h).jaxpr)
    wide = [s for s in shapes if len(s) >= 3 and s[-1] == cfg.d_expert and s[-2] in (cfg.num_experts, cfg.held[1]) and 6 in s[:-2]]
    assert not wide, wide
    assert (6 * cfg.top_k, cfg.d_expert) in shapes                 # the sorted rows at the expert's width: one row a choice
    y, rows = fn(h)
    assert rows.shape == (4,) and 0 < int(rows.sum()) <= 6 * cfg.top_k


# -- the cache of each kind of layer ---------------------------------------------------------------
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


def test_a_window_layers_memory_does_not_grow_with_max_len(tiny):
    """Four window layers keep window + 16 positions a slot at any max_len; the
    pool is over the one full layer only."""
    short, long = _engine(tiny, max_len=64), _engine(tiny, max_len=128)
    assert short.cache.wk.shape == long.cache.wk.shape == (4, 2, 2, 8 + 16, 16)
    assert short.cache.k.shape[0] == long.cache.k.shape[0] == 1
    assert short.cache.k.shape[1] < long.cache.k.shape[1]          # the full layer's pool does follow max_len
    vis = tiny["module"].serving_programs(tiny["cfg"], "paged").visible_tokens(np.array([5, 8, 100]))
    assert np.allclose(vis, [(4 * 5 + 5) / 5, (4 * 8 + 8) / 5, (4 * 8 + 100) / 5])


def test_a_slot_used_again_reads_nothing_of_its_last_tenant(tiny):
    """The same request before and after other requests have been through both
    slots, and with every row of the rings and the pool set to 1e4 in between
    (a masked row's weight is exactly 0, so a large finite value shows a read
    and a NaN would show a masked one): the same tokens, and they are the
    reference's greedy choice."""
    eng = _engine(tiny)
    prompt = _tokens(50, 50)
    first = eng.submit(prompt, 9)
    assert eng.run()[first] == _greedy(tiny, prompt, 9)
    churn = [eng.submit(_tokens(60 + i, n), 6) for i, n in enumerate((90, 41, 5, 33))]
    done = eng.run()
    assert all(len(done[r]) == 6 for r in churn)
    eng.cache = eng.cache._replace(**{f: jnp.full_like(getattr(eng.cache, f), 1e4) for f in ("k", "v", "wk", "wv")})
    again = eng.submit(prompt, 9)
    assert eng.run()[again] == done[first]


def test_the_engine_counts_expert_rows_and_visible_positions(tiny):
    from tony_tpu.obs import metrics as M

    def totals():
        return {m["name"]: sum(s["value"] for s in m["samples"]) for m in M.REGISTRY.snapshot()
                if m["name"].startswith("tony_serve_") and m["samples"] and "value" in m["samples"][0]}

    before = totals()
    eng = _engine(tiny)
    rid = eng.submit(_tokens(9, 30), 9)
    eng.run()
    delta = {k: v - before.get(k, 0) for k, v in totals().items()}
    # admission emits token 1; two chunks of 4 steps at contexts 31..38 emit the rest
    contexts = np.arange(31, 39)
    assert delta["tony_serve_context_tokens_total"] == contexts.sum()
    # a layer in five sees the context, four see the window's 8; the engine adds a chunk's sum as a whole number
    assert delta["tony_serve_visible_tokens_total"] == sum(int(((4 * 8 + c) / 5).sum()) for c in (contexts[:4], contexts[4:]))
    assert delta["tony_serve_expert_choices_total"] == 8 * 4 * 2
    assert 0 < delta["tony_serve_expert_rows_max_total"] <= delta["tony_serve_expert_rows_total"] <= 8 * 4 * 2
    assert delta["tony_serve_experts_touched_total"] == delta["tony_serve_expert_rows_total"]      # one slot: a row an expert
    assert len(eng.done[rid]) == 9


# -- the engines that were there, as they were ------------------------------------------------------
SALA_GOLDEN = [   # taken on the parent commit (3e4eb82), tiny-minicpm-sala, seed 7: before the seam's fourth output
    [240, 238, 158, 175, 1, 83, 243, 68, 146], [163, 68, 146, 100, 172, 22, 77, 27, 32, 149],
    [93, 102, 187, 153, 220, 38, 214, 64, 192, 229, 172], [188, 167, 42, 237, 2, 39, 202, 244, 22, 237, 2, 39],
    [44, 174, 178, 254, 56, 229, 172, 22, 237, 163, 68, 146, 100]]


def test_the_minicpm_sala_engine_answers_as_before(bench, monkeypatch):
    """The llama engine's golden tokens are held by tests/test_minicpm_sala.py
    (three engines); these are the other servable family's."""
    monkeypatch.setenv("TONY_PALLAS_INTERPRET", "1")
    from tony_tpu.models.serving import ContinuousBatcher

    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config("tiny-minicpm-sala"), "serve-1chip")
    _, cfg = families.load("minicpm_sala").program(sizes, 128)
    params = bench["chipside"].seed_weights(sizes, 7)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, sizes["vocab"], n).tolist() for n in (5, 70, 40, 17, 90)]
    eng = ContinuousBatcher(params, cfg, num_slots=2, max_len=128, decode_chunk=4, kv="paged", page_len=8, prefill_chunk=32)
    rids = [eng.submit(p, 9 + i) for i, p in enumerate(prompts)]
    done = eng.run()
    assert [done[r] for r in rids] == SALA_GOLDEN


def test_a_replica_finds_the_familys_presets():
    from tony_tpu.models import exaone_moe, registry

    presets = registry.presets()
    assert presets["exaone-moe-tiny"] is exaone_moe.PRESETS["exaone-moe-tiny"]
    assert registry.module_of(presets["exaone-moe-tiny"]) is exaone_moe and "tiny" in presets and "sala-tiny" in presets
    cfg = exaone_moe.ExaoneMoeConfig()
    assert (cfg.n_layers, cfg.head_dim * cfg.n_heads, cfg.d_model, cfg.count("full_attention")) == (48, 8192, 6144, 12)
    assert cfg.layer_types[:4] == ("sliding_attention",) * 3 + ("full_attention",) and cfg.window == 128
    with pytest.raises(ValueError, match="held"):
        exaone_moe.ExaoneMoeConfig(held=(120, 16))
    with pytest.raises(ValueError, match="kv='paged'"):
        exaone_moe.serving_programs(exaone_moe.EXAONE_MOE_TINY, "dense")


# -- the family's files, through the harness -------------------------------------------------------
def test_the_family_resolves_sizes_program_reference_and_counts(bench):
    spec, families = bench["spec"], bench["families"]
    cfg = spec.config("k-exaone-236b")
    sizes = spec.model_sizes(cfg, "serve-1chip")
    assert (sizes["layers"], sizes["vocab"], sizes["d_model"], sizes["d_ff"], sizes["d_expert"]) == (5, 19200, 6144, 18432, 2048)
    assert (sizes["num_experts"], sizes["held"], sizes["top_k"], sizes["routed_scale"], sizes["shared_experts"]) == (
        128, (0, 16), 8, 2.5, 1)
    assert sizes["windows"] == (128, 128, 128, 0, 128) and sizes["dense_layers"] == 1 and sizes["mtp_layers"] == 0
    source = spec.model_sizes(cfg, "source")
    assert (source["layers"], source["vocab"], source["held"], source["mtp_layers"]) == (48, 153600, (0, 128), 1)
    assert source["windows"] == (128, 128, 128, 0) * 12
    hash(tuple(sorted(sizes.items())))                               # check.py keys its traced programs by the sizes
    fam = families.load("exaone_moe")
    module, pcfg = fam.program(sizes, 6144)
    assert module.__name__ == "tony_tpu.models.exaone_moe" and callable(module.forward) and callable(module.mtp_logits)
    assert (pcfg.n_layers, pcfg.head_dim, pcfg.held, pcfg.moe.scoring, pcfg.moe.held, pcfg.dtype) == (
        5, 128, (0, 16), "sigmoid", (0, 16), "bfloat16")
    ref = families.reference(sizes)
    assert all(callable(getattr(ref, f)) for f in ("seed_key", "init_weights", "forward", "nll", "mtp_logits", "routed_ffn"))
    assert ref.CONTROL == "fp8" and ref.GRAD_LEAVES == () and "tony_tpu" not in open(ref.__file__).read().split('"""')[2]
    assert callable(fam.serve_install) and families.counts(sizes).__name__ == "families.exaone_moe_counts"


@pytest.mark.parametrize("change,error,match", [
    (lambda c: {**c, "rope_scaling": None}, KeyError, "rope_scaling"),
    (lambda c: {**c, "assumed": {k: v for k, v in c["assumed"].items() if k != "rope"}}, KeyError, "rope"),
    (lambda c: {**c, "assumed": {**c["assumed"], "router_bias": {"value": "weighs", "why": ""}}}, ValueError, "router_bias"),
    (lambda c: {**c, "n_group": 8}, ValueError, "n_group"),
    (lambda c: {**c, "scoring_func": "softmax"}, ValueError, "scoring_func"),
    (lambda c: {**c, "sliding_windows": {**c["sliding_windows"], "serve-1chip": [128] * 5}}, ValueError, "sliding_windows"),
    (lambda c: {**c, "mlp_layer_types": {**c["mlp_layer_types"], "serve-1chip": ["sparse"] * 5}}, ValueError, "mlp_layer_types"),
], ids=["unknown-key", "missing-assumed", "another-bias", "groups", "softmax-scores", "windows-not-the-kinds", "no-leading-dense-layer"])
def test_the_family_refuses_what_it_does_not_know(bench, change, error, match):
    spec, families = bench["spec"], bench["families"]
    with pytest.raises(error, match=match):
        families.load("exaone_moe").sizes(change(spec.config("k-exaone-236b")), "serve-1chip")


def test_a_checkout_without_the_model_module_has_no_such_family(bench, monkeypatch):
    """The benchmark's files laid over a parent commit: `sizes` raises NoFamily,
    which run.py turns into exit 2 before any launch."""
    spec, families = bench["spec"], bench["families"]
    fam = families.load("exaone_moe")
    monkeypatch.setattr(fam, "PROGRAM", os.path.join(ROOT, "tony_tpu", "models", "no_such_model.py"))
    with pytest.raises(families.NoFamily, match="exaone_moe"):
        fam.sizes(spec.config("k-exaone-236b"), "serve-1chip")


def test_the_counts_on_hand_worked_sizes(bench):
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config("k-exaone-236b"), "serve-1chip")
    C, engine = families.counts(sizes), spec.workload("k-exaone-236b.serve_reason")["engine"]
    slab = 3 * 6144 * 2048
    attn = 2 * 6144 * 8192 + 2 * 6144 * 1024
    assert C.expert_params(sizes) == slab == 37_748_736 and C.attention_params(sizes) == attn == 113_246_208
    # the dense layer 453.0 M, a routed layer 151.8 M beside its 16 experts, embedding and head 235.9 M: 7.43 GB in bf16
    assert C.total_params(sizes) == (attn + 3 * 6144 * 18432) + 4 * (attn + 17 * slab + 6144 * 128) + 2 * 19200 * 6144
    assert round(2 * C.total_params(sizes) / 1e9, 2) == 7.42      # 7.43 with the routers in float32
    means = {"live_slots": 256.0, "visible_per_slot": (4 * 128 + 1400) / 5, "held_rows_per_step": 4 * 256.0,
             "prefill_rows_per_chunk": 512.0}
    ops, nbytes = C.moe_decode_call(sizes, engine, means)
    touched = 16 * (1 - (1 - 8 / 128) ** 256)
    assert ops == 2 * slab * 1024 and nbytes == pytest.approx(2 * (4 * touched * slab + 2 * 1024 * 6144)) and touched > 15.999
    ops, nbytes = C.moe_prefill_call(sizes, engine, means)
    assert ops == 2 * slab * 512 * 4 and nbytes == 2 * 4 * (16 * slab + 2 * 512 * 6144)       # 512 rows x 8 x 16/128 land here
    ops, nbytes = C.attn_decode_call(sizes, engine, means)
    assert nbytes == 2 * 2 * 8 * 128 * 256 * (4 * 128 + 1400) and ops == 4 * 64 * 128 * 256 * (4 * 128 + 1400)
    assert C.moe_decode_calls(sizes, engine) == ("decode_steps", 8) and C.moe_prefill_calls(sizes, engine) == ("prefill_chunk", 1)
    import re
    bank = re.compile(C.moe_decode_operands(sizes, engine))
    assert bank.search("bf16[4,16,6144,2048]") and bank.search("bf16[16,2048,6144]{2,1,0}") and not bank.search("bf16[4,6144,8192]")
    kv = re.compile(C.attn_decode_operands(sizes, engine))
    assert kv.search("bf16[1,4097,8,256,128]") and kv.search("bf16[4,256,8,144,128]") and not kv.search("bf16[5,256,8,8,128]")
    deltas = {"tony_serve_engine_chunks_total": 10, "tony_serve_decode_slots_total": 2500, "tony_serve_visible_tokens_total": 2500 * 8 * 300,
              "tony_serve_expert_rows_total": 10 * 8 * 1000, "tony_serve_prefill_tokens_total": 6144, "tony_serve_prefill_chunks_total": 12}
    assert C.window_means(lambda name, where=None: deltas.get(name), engine) == {
        "live_slots": 250.0, "visible_per_slot": 300.0, "held_rows_per_step": 1000.0, "prefill_rows_per_chunk": 512.0}
    assert C.window_means(lambda name, where=None: None, engine) is None


def test_the_roofline_reader_by_hand(bench, monkeypatch):
    """family_roofline: the family's means, executions a compiled variant at a time, the shared roofline."""
    spec = bench["spec"]
    from readers import family_roofline as FR
    from readers import serve_roofline as SR

    # two variants of the prefill program: 3 whole runs of 10 ms and one cut to 5; 2 runs of 30 ms
    mods = {"jit_prefill_chunk(1)": [0.010, 0.010, 0.010, 0.005], "jit_prefill_chunk(2)": [0.030, 0.030], "jit_decode_steps(3)": [0.1]}
    assert FR.executions(mods, "prefill_chunk") == pytest.approx(3.5 + 2.0) and FR.executions(mods, "decode_steps") == 1.0
    w = spec.workload("k-exaone-236b.serve_reason")
    run = types.SimpleNamespace(sizes=spec.model_sizes(spec.config("k-exaone-236b"), "serve-1chip"), w=w, peaks=spec.load_json("peaks.json"))

    def snap(scale):
        names = {"tony_serve_engine_chunks_total": 10, "tony_serve_decode_slots_total": 2560, "tony_serve_visible_tokens_total": 2560 * 8 * 300,
                 "tony_serve_expert_rows_total": 10 * 8 * 1024, "tony_serve_prefill_tokens_total": 6144, "tony_serve_prefill_chunks_total": 12}
        return {"metrics": [{"name": k, "samples": [{"labels": {}, "value": v * scale}]} for k, v in names.items()]}

    ctx = {"run": run, "device": {"platform": "tpu", "kind": "TPU v5 lite"}, "trace": {"modules": {"jit_decode_steps(3)": [0.1, 0.1]}},
           "drive": {"snap0": snap(1), "snap1": snap(2)}}
    monkeypatch.setattr(SR, "_device_seconds", lambda ctx, patterns: 0.032)
    got = FR.read(ctx, kernel="moe_decode", match="moe_swiglu_decode")
    slab, touched = 3 * 6144 * 2048, 16 * (1 - (1 - 8 / 128) ** 256)
    least = 2 * (4 * touched * slab + 2 * 1024 * 6144) / 819e9            # the HBM bound: 5.9 ms a step
    assert got == pytest.approx(100 * least * 8 * 2 / 0.032)
    assert FR.read({**ctx, "trace": None}, kernel="moe_decode", match="x") is None
    assert FR.read({**ctx, "drive": {"snap0": None, "snap1": None}}, kernel="moe_decode", match="x") is None   # no counters: nothing
    llama = types.SimpleNamespace(sizes=spec.model_sizes(spec.config("mistral-7b"), "serve-1chip"), w=w, peaks=run.peaks)
    assert FR.read({**ctx, "run": llama}, kernel="moe_decode", match="x") is None


def test_the_cells_entries_and_files(bench):
    spec = bench["spec"]
    B, cell = spec.benchmark(), "k-exaone-236b.serve_reason"
    entry = next(c for c in B["configs"] if c["name"] == "k-exaone-236b")
    assert entry["reduced"] == ["num_hidden_layers", "layer_types", "mlp_layer_types", "sliding_windows", "num_experts",
                                "vocab_size", "num_nextn_predict_layers"]
    w_entry = next(e for e in B["workloads"] if e["name"] == cell)
    assert w_entry["chips"] == 1 and w_entry["config"] == "k-exaone-236b"
    assert {m["name"] for m in spec.cell_metrics(B, cell, "end_to_end")} == {"serve_out_tok_s", "setup_s"}
    per_layer = spec.cell_metrics(B, cell, "per_layer")
    assert {m["name"] for m in per_layer} == {
        "moe_decode_roofline_pct.serve", "moe_prefill_roofline_pct.serve", "attn_decode_roofline_pct.serve",
        "expert_rows_max_over_mean.serve", "held_share_pct.serve", "launch_s", "slots_active_mean.serve", "host_share_pct.serve",
        "decode_batch_mean.serve", "visible_share_pct.serve", "decode_step_ms.serve_tput", "chunk_period_ms.serve_tput",
        # PR 41: the host side of a pass
        "host_gap_pct.serve_tput", "host_offcpu_ms.serve_tput", "stream_write_ms.serve_tput", "fanout_delay_ms.serve_tput",
        "write_gap_pct.serve_tput",
        # PR 55: the start-up account and the compiles by source
        "submit_to_am_s", "allocate_s", "register_s", "runtime_init_s", "weights_s", "replica_warmup_s.serve",
        "setup_compile_s.serve", "setup_cache_load_s.serve", "setup_trace_lower_s.serve", "compile_ms_per_pass.serve",
        }
    assert {m["moves"] for m in per_layer} == {"serve_out_tok_s", "setup_s"}
    assert all(spec.metric(m["name"])["moves"] == m["moves"] for m in B["per_layer"])
    # the five this cell brought start at the cell's name (a later cell of a family with a routed FFN joins four of them)
    new = [m for m in B["per_layer"] if m["workloads"][0] == cell]
    at = B["per_layer"].index(new[0])
    assert len(new) == 5 and B["per_layer"][at:at + 5] == new                    # put at the end of their list, together,
    assert [m["name"] for m in B["per_layer"][at + 5:at + 10]] == [              # and PR 41's five after them
        "host_gap_pct.serve_tput", "host_offcpu_ms.serve_tput", "stream_write_ms.serve_tput", "fanout_delay_ms.serve_tput",
        "write_gap_pct.serve_tput"]
    # PR 43's after those (a later latent family's cell joins the last of them), then later PRs' own
    assert all(m["workloads"][0] == "dots3-note-prev.serve_notes" for m in B["per_layer"][at + 10:at + 15])
    w = spec.workload(cell)
    assert (w["engine"]["slots"], w["engine"]["max_len"], w["engine"]["prefill_chunk"], w["engine"]["decode_chunk"]) == (
        256, 6144, 2048, 8)
    assert w["engine"]["max_len"] % w["engine"]["page_len"] == 0 and w["traffic"]["arrivals"] == {
        "process": "closed", "clients": 256, "ramp_s": w["traffic"]["arrivals"]["ramp_s"]}
    p, a = w["traffic"]["prompt_len"], w["traffic"]["answer_len"]
    assert (p["median"], p["sigma"], p["min"], p["max"]) == (512, 0.6, 128, 2048) and p["max"] <= w["engine"]["prefill_chunk"]
    assert (a["median"], a["sigma"], a["min"], a["max"]) == (1536, 0.4, 512, 4096) and p["max"] + a["max"] == 6144
    assert w["traffic"]["draw_seed"] == 33
    # the limit stands between what sound runs read and what the float8 control reads, over the positions the reference states
    assert 0.03 < w["check"]["worst_gap_limit"] < 0.29 and "TIE_MARGIN" in w["check"]["why"] and w["check"]["samples"] == 4
    published = {"hidden_size": 6144, "intermediate_size": 18432, "moe_intermediate_size": 2048, "num_attention_heads": 64,
                 "num_key_value_heads": 8, "head_dim": 128, "num_experts_per_tok": 8, "num_shared_experts": 1,
                 "routed_scaling_factor": 2.5, "sliding_window": 128, "rms_norm_eps": 1e-05, "first_k_dense_replace": 1,
                 "n_group": 1, "topk_group": 1, "scoring_func": "sigmoid", "norm_topk_prob": True,
                 "max_position_embeddings": 262144, "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}
    cfg = spec.config("k-exaone-236b")
    assert {k: cfg[k] for k in published} == published
    cut = {"num_hidden_layers": (48, 5), "num_experts": (128, 16), "vocab_size": (153600, 19200), "num_nextn_predict_layers": (1, 0)}
    assert {k: (cfg[k]["source"], cfg[k]["serve-1chip"]) for k in cut} == cut and sorted(cfg["reduced"]) == sorted(entry["reduced"])
    assert all(cfg[k]["serve-1chip"] == cfg[k]["source"][:5] and len(cfg[k]["source"]) == 48
               for k in ("layer_types", "mlp_layer_types", "sliding_windows"))
    assert "8 chips share each layer" in cfg["deployments"]["serve-1chip"].replace("one of the 8 chips that share each layer", "8 chips share each layer")
    assert all(set(v) == {"value", "why"} and v["why"] for k, v in cfg["assumed"].items() if isinstance(v, dict))


def test_the_rehearsal_cell_runs_end_to_end_on_the_cpu(tmp_path, bench, startup_account):
    """`tiny-exaone-moe.serve` through run.py: the `tony serve` path, the
    router, the replica registered through the family's hook, bucketed prefill
    and decode through pages and rings under the interpreter, and the harness's
    own comparison with the reference: `correct`."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT, "HOME": str(tmp_path), "TMPDIR": str(tmp_path)}
    proc = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), "--workload", TINY + ".serve",
                           "--seed", str(2 ** 31 + 33), "--seconds", "3", "--trace", "0"],
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
