"""The granite_hybrid family on the CPU at a tiny size (`tiny-granite-hybrid`: hidden
64, two periods of [mamba x 2, attention, mamba], 4 state-space heads of 32 over a
state of 16, 4 / 2 attention heads of 16, 8 experts of 32 top-3 of which 4 are
held, a shared expert of 48, a convolution of 4 taps with a bias, float32; pages
of 16 positions): the program against the family's plain reference (forward;
prefill chunks then decode through the engine's cache), the two shares of a
layer against the uncut reference, and the engine. The scan's and the
convolution's forms are tests/test_granite_hybrid_scan.py; the engines that were
there, the family's files through the benchmark's harness and its rehearsal are
tests/test_granite_hybrid_family.py.

Tolerances. On logits: the seeded model's are SMALL (0.02 at most at this size: a random tied
head divided by `logits_scaling`, under an embedding drawn so that no position
predicts its own token; the configuration's `assumed.embed_init`); program and
reference, both float32, agree to 3e-8 on them and LOGIT_TOL is 1e-6; the float8
control moves them by 8e-3.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

TINY, MAX_LEN, PAGE = "tiny-granite-hybrid", 128, 16
LOGIT_TOL = 1e-6


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
    throughout; at the served widths in bfloat16 the decode batch is in the kernel and a 1024- or 2048-row chunk fetched
    (its rows by DMA from the tokens, its choices summed from the rows that exist)."""
    import dataclasses

    from tony_tpu.models import granite_hybrid as GH

    name = "tony_serve_routed_ffn_programs_total"
    if case == "the-served-widths":
        cfg = dataclasses.replace(tiny["cfg"], d_model=4096, d_expert=768, dtype="bfloat16")
        form = GH.serving_programs(cfg, "paged").routed_ffn_form
        assert [form(rows) for rows in (64, 512, 1024, 2048)] == ["in_kernel", "in_kernel", "fetched", "fetched"]
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
