"""The solar_open2 family on the CPU at a tiny size (`tiny-solar-open2`: hidden 64, two
periods of [attention, kda, kda, kda], 4 recurrent heads of 16 keys and 16 values, 4 /
2 attention heads of 16, 8 experts of 32 top-3 of which 4 are held, a shared expert of
32, a convolution of 4 taps, gates of rank 8, float32; pages of 16 positions): the
program against the family's plain reference (forward; prefill chunks then decode
through the engine's cache), the eight shares of a layer against the uncut
reference, each assumed form flipped once and seen to fail, and the engine. The
channel-gated delta rule's forms are tests/test_solar_open2_rule.py and
tests/test_solar_open2_rule_programs.py; the engines that were there, the family's
files through the benchmark's harness and its rehearsal are
tests/test_solar_open2_family.py.

Tolerances. On logits: the seeded model's are
of size 4; program and reference, both float32, agree to 6e-6 on them and LOGIT_TOL
is 5e-5; the float8 control moves them by 2, a flipped gate by 0.1 or more.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

TINY, MAX_LEN, PAGE = "tiny-solar-open2", 128, 16
LOGIT_TOL = 5e-5


@pytest.fixture(scope="module")
def tiny(bench, interpreted):
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config(TINY), "serve-1chip")
    module, cfg = families.load("solar_open2").program(sizes, MAX_LEN, PAGE)
    reference = families.reference(sizes)
    params = bench["chipside"].seed_weights(sizes, 7)

    ref = jax.jit(lambda p, t: reference.forward(p, t, sizes, "f32", 32))

    def ref_logits(seq, prec="f32", traced_anew=False):
        """The reference's logits for every position of `seq`, padded at the end to
        one length (one compile; a causal model's positions do not see it).
        `traced_anew`: not the compiled program (a test that replaces one of the
        reference's functions, or asks for the control)."""
        padded = jnp.asarray(list(seq) + [0] * (MAX_LEN - len(seq)), jnp.int32)
        if traced_anew or prec != "f32":
            return np.asarray(jax.jit(lambda p, t: reference.forward(p, t, sizes, prec, 32))(params, padded))[:len(seq)]
        return np.asarray(ref(params, padded))[:len(seq)]

    return {"sizes": sizes, "module": module, "cfg": cfg, "reference": reference, "params": params, "ref_logits": ref_logits}


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
    return seq, _a_rows_logits(tiny, seq + [0] * 32)[:96], tiny["ref_logits"](seq)


def test_forward_is_every_rows_chunk_and_finish(tiny):
    """`forward` itself, its `lax.map` over a batch of two rows of one short block: the rows' logits as above
    (one compiled program against the same operations one by one: within the tolerance the reference is held to)."""
    rows = [_tokens(66, 16), _tokens(67, 16)]
    got = np.asarray(tiny["module"].forward(tiny["params"], jnp.asarray(rows, jnp.int32), tiny["cfg"]))
    for row, logits in zip(rows, got):
        assert np.abs(logits - _a_rows_logits(tiny, row)).max() < LOGIT_TOL and np.abs(logits).max() > 0.5


@pytest.mark.parametrize("rows", [(0, 32), (32, 64), (64, 96)], ids=["first-block", "second-block", "third-block"])
def test_forward_agrees_with_the_reference(one_forward, rows):
    _, got, want = one_forward
    assert np.abs(got[rows[0]:rows[1]] - want[rows[0]:rows[1]]).max() < LOGIT_TOL
    assert np.abs(want[rows[0]:rows[1]]).max() > 1.0         # logits of size 4, not a row of zeros


def test_the_float8_control_lies_far_from_the_reference(tiny, one_forward):
    seq, _, want = one_forward
    assert np.abs(tiny["ref_logits"](seq, "fp8") - want).max() > 1000 * LOGIT_TOL


@pytest.mark.parametrize("kind", ["kda", "attention"])
def test_one_layer_of_each_kind_agrees_with_the_reference(tiny, kind):
    """A trunk of ONE layer of the kind (mixer and routed FFN)."""
    R, m, sizes, cfg = tiny["reference"], tiny["module"], tiny["sizes"], tiny["cfg"]
    at = sizes["layer_types"].index(kind)
    params = dict(tiny["params"], layers=[tiny["params"]["layers"][at]], **{k: tiny["params"][k][at:at + 1] for k in R.BANKS})
    seq = jnp.asarray(_tokens(65, 128), jnp.int32)              # `one_forward`'s length: the layer's kernels are the programs compiled there
    want = np.asarray(R.trunk(params, seq, dict(sizes, layer_types=(kind,), layers=1), "f32", 32)[0])
    one = dataclasses.replace(cfg, layer_types=(kind,))
    got = np.asarray(m._chunk(params, seq, m._init_staging(one, 128), jnp.int32(128), one)[0])
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max() and np.abs(want).max() > 0.5


def _silu_gated_head_norm(R):
    return lambda o, u, lp, s, prec: (R._rms_norm(o, lp["o_norm"], s["norm_eps"]).reshape(o.shape[0], -1)
                                      * jax.nn.silu(R._low_rank(u, lp["w_ga"], lp["w_gb"], prec)))


def _decay_a_head(R):
    real = R._decay

    def decay(u, lp, s, prec):
        g = real(u, lp, s, prec)
        return jnp.broadcast_to(g.mean(axis=-1, keepdims=True), g.shape)

    return decay


def _bias_in_the_gates(R):
    real = R._gates
    return lambda scores, bias, chosen, s: real(scores + 10.0 * bias, bias, chosen, s)


#: an assumed form of the reference -> (the function that states it, what builds the other form it could have had)
FLIPS = {
    "the-kda-output-gate-a-silu": ("_gated_head_norm", _silu_gated_head_norm),
    "the-attention-gate-left-out": ("_output_gate", lambda R: lambda o, u, lp, prec: o),
    "the-decay-a-head-not-a-channel": ("_decay", _decay_a_head),
    "the-bias-weighs-as-well-as-chooses": ("_gates", _bias_in_the_gates),
    "beta-in-0-1": ("_beta", lambda R: lambda u, lp, prec: jax.nn.sigmoid(R._mm("td,dh->th", u, lp["w_b"], prec))),
}


@pytest.mark.parametrize("flip", list(FLIPS))
def test_an_assumed_form_flipped_is_seen_to_fail(tiny, one_forward, monkeypatch, flip):
    """The comparison can tell each assumed form from its neighbour: with ONE
    function of the reference replaced, the program's logits lie far outside
    the tolerance they meet against the reference as it is."""
    R = tiny["reference"]
    seq, got, _ = one_forward
    name, other = FLIPS[flip]
    monkeypatch.setattr(R, name, other(R))
    assert np.abs(got - tiny["ref_logits"](seq, traced_anew=True)).max() > 100 * LOGIT_TOL


def test_the_bias_chooses_and_does_not_weigh(tiny):
    """The reference's gates are the program's: the top-k of score + bias chosen,
    the chosen SCORES over their sum; and with the bias in the weights too
    (the flipped form) they are not."""
    from tony_tpu.parallel.expert import _gating

    R, sizes, cfg = tiny["reference"], tiny["sizes"], tiny["cfg"]
    lp = dict(tiny["params"]["layers"][1])
    lp["router_bias"] = 10.0 * lp["router_bias"]                        # a bias large enough to change who is chosen
    h = jax.random.normal(jax.random.PRNGKey(1), (24, sizes["d_model"]))
    scores = jax.nn.sigmoid(jnp.einsum("td,de->te", h, lp["router"], precision="highest"))
    chosen = R._choose(scores, lp["router_bias"], sizes)
    vals, idx, _ = _gating(h[None], lp["router"], cfg.moe, bias=lp["router_bias"])[:3]
    dense = np.zeros((24, 8), np.float32)
    np.put_along_axis(dense, np.asarray(idx)[0], np.asarray(vals)[0], axis=1)
    assert np.abs(dense - np.asarray(R._gates(scores, lp["router_bias"], chosen, sizes))).max() < 1e-6 and np.asarray(chosen).sum(axis=1).tolist() == [3] * 24
    assert (np.asarray(chosen) != np.asarray(R._choose(scores, 0 * lp["router_bias"], sizes))).any()    # the bias did choose
    weighed = R._gates(scores + lp["router_bias"], lp["router_bias"], chosen, sizes)
    assert np.abs(dense - np.asarray(weighed)).max() > 1e-3


def test_the_eight_shares_add_up_to_the_uncut_layer(tiny, bench):
    """The routed parts that the eight shares (one expert each of the tiny
    layer's eight, as 40 each of 320) compute, with the shared expert counted
    once, are what the uncut reference gives for the whole FFN: program and
    reference alike."""
    from tony_tpu.parallel.expert import MoEConfig, held_expert_ffn

    R, sizes = tiny["reference"], tiny["sizes"]
    whole = bench["chipside"].seed_weights(dict(sizes, held=(0, 8)), 3)
    lp, banks = whole["layers"][1], tuple(whole[k] for k in R.BANKS)
    h = jax.random.normal(jax.random.PRNGKey(0), (40, sizes["d_model"]))
    uncut, _ = R.routed_ffn_and_slack(h, lp, banks, sizes, held=(0, 8), layer=1)
    routed_only, _ = R.routed_ffn_and_slack(h, lp, banks, sizes, held=(0, 8), shared=False, layer=1)
    parts, got = [], []
    for first in range(8):
        own = tuple(b[:, first:first + 1] for b in banks)
        parts.append(R.routed_ffn_and_slack(h, lp, own, sizes, held=(first, 1), shared=False, layer=1)[0])
        cfg = MoEConfig(num_experts=8, top_k=3, scoring="sigmoid", held=(first, 1))
        got.append(held_expert_ffn(h, lp["router"], lp["router_bias"], *own, jnp.int32(1), cfg)[0])
    shared = uncut - routed_only
    assert np.abs(np.asarray(sum(parts) + shared - uncut)).max() < 1e-5 and np.abs(np.asarray(uncut)).max() > 0.5
    assert np.abs(np.asarray(sum(got) + shared - uncut)).max() < 1e-5
    assert all(np.abs(np.asarray(p)).max() > 0.01 for p in parts)       # no share is empty


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
    whose state and tail stop at its last real row; a second chunk whose
    attention reads the first's staged keys and whose rule starts from the
    first's state), then 6 decode steps a position at a time through the paged
    pool, the carried state and the carried convolution tail, crossing a page's
    edge: every step's logits against the reference's full forward of
    everything so far."""
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


def test_the_routed_ffns_form_at_the_served_widths(tiny):
    """`ServingPrograms.routed_ffn_form`: float32 rows take `ragged_dot`, so the tiny engine is staged; at the
    served widths in bfloat16 the decode batch of 128 and a 512-row bucket are in the kernel, longer chunks fetched."""
    from tony_tpu.models import solar_open2 as SO

    cfg = dataclasses.replace(tiny["cfg"], d_model=4096, d_expert=1280, num_experts=320, held=(0, 40), top_k=8, dtype="bfloat16")
    form = SO.serving_programs(cfg, "paged").routed_ffn_form
    assert [form(rows) for rows in (128, 512, 1024, 2048)] == ["in_kernel", "in_kernel", "fetched", "fetched"]
    assert SO.serving_programs(tiny["cfg"], "paged").routed_ffn_form(2) == "staged"


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


def test_the_programs_own_init_draws_the_trees_the_reference_draws(tiny):
    """`tony serve --preset solar-open2-tiny` draws its weights through the module's
    `init`; the benchmark through the reference's `init_weights`: one tree, leaf for leaf."""
    own = jax.eval_shape(lambda: tiny["module"].init(jax.random.PRNGKey(0), tiny["cfg"]))
    ref = jax.eval_shape(lambda: tiny["params"])
    assert jax.tree.structure(own) == jax.tree.structure(ref)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(jax.tree.leaves(own), jax.tree.leaves(ref)))
    assert tiny["module"].ROUTER_BIAS_SCALE == tiny["sizes"]["router_bias_scale"]
