"""The solar_open2 family on the CPU at a tiny size (`tiny-solar-open2`: hidden 64, two
periods of [attention, kda, kda, kda], 4 recurrent heads of 16 keys and 16 values, 4 /
2 attention heads of 16, 8 experts of 32 top-3 of which 4 are held, a shared expert of
32, a convolution of 4 taps, gates of rank 8, float32; pages of 16 positions): the
channel-gated delta rule's chunk and step forms against the position-at-a-time
recurrence, the program against the family's plain reference (forward; prefill
chunks then decode through the engine's cache), the eight shares of a layer against
the uncut reference, each assumed form flipped once and seen to fail, the engines
that were there as they were, and the family's files through the benchmark's harness.

Tolerances. The chunk form differs from the recurrence in the order of its sums and
in splitting a pair's decay in two factors: 2e-5 of the largest output is ten times
what was seen (1.5e-6 at most, whatever the decays and with beta at 2); the step form
is the recurrence's own arithmetic (1e-6 of a state of size 4). A state kept in
bfloat16 moves the same outputs by 1e-3 and fails. On logits: the seeded model's are
of size 4; program and reference, both float32, agree to 6e-6 on them and LOGIT_TOL
is 5e-5; the float8 control moves them by 2, a flipped gate by 0.1 or more.
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
TINY, MAX_LEN, PAGE = "tiny-solar-open2", 128, 16
LOGIT_TOL = 5e-5
CONFIG, CELL = "solar-open2-250b", "solar-open2-250b.serve_extract"


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


# -- the rule: chunk and step against the recurrence a position at a time ---------------------------
def _rule_inputs(seed, H, T, dk, dv, case, dtype=jnp.float32):
    """q, k as the program makes them (SiLU outputs, L2-normed, q scaled), v SiLU outputs, a state of unit scale."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    l2 = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = (l2(jax.nn.silu(jax.random.normal(ks[0], (H, T, dk)))) * dk ** -0.5).astype(dtype)
    k = l2(jax.nn.silu(jax.random.normal(ks[1], (H, T, dk)))).astype(dtype)
    v = jax.nn.silu(jax.random.normal(ks[2], (H, T, dv))).astype(dtype)
    if case == "strong-beside-weak":
        # -20 a position on the even channels, -0.01 on their neighbours: exp(-G_j) would overflow after four positions
        g = jnp.where((jnp.arange(dk) % 2 == 0)[None, None, :], -20.0, -0.01) * jax.random.uniform(ks[3], (H, T, dk), minval=0.9, maxval=1.1)
    else:
        # "every-rate-at-once": a channel's own rate changes from position to position, -25 here, -0.0009 there
        low, high = (-7.0, 3.2) if case == "every-rate-at-once" else (-7.0, -3.0)
        g = -jnp.exp(jax.random.uniform(ks[3], (H, T, dk), minval=low, maxval=high))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (H, T)) + (5.0 if case == "beta-near-2" else 0.0))
    return q, k, v, g, beta, jax.random.normal(ks[5], (H, dk, dv))


RULE_CASES = ["weak-forgetting", "strong-beside-weak", "every-rate-at-once", "beta-near-2"]
#: heads, positions, d_k, d_v. A program of the blocked rule holds the most heads up to `delta_rule.CHUNK_HEADS` (4) that divide
#: H: 1, 2, 3 (six heads: two programs), ONE of thirteen (a prime over the bound: the one-head program), 3 of thirty, 4 of 64
SHAPES = {"two-blocks": (2, 128, 16, 32), "the-served-head": (1, 64, 128, 128), "a-short-block": (3, 16, 8, 8),
          "six-heads-in-two-programs": (6, 128, 8, 16), "thirteen-heads-a-program-each": (13, 64, 8, 8),
          "thirty-heads-by-three": (30, 32, 8, 8), "sixty-four-heads-by-four": (64, 32, 8, 8)}


def _close(got, want, tol=2e-5):
    return float(jnp.abs(got - want).max()) < tol * float(jnp.abs(want).max())


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("case", RULE_CASES)
def test_the_chunk_form_is_the_recurrence(interpreted, case, shape):
    from tony_tpu.ops import kda

    args = _rule_inputs(1, *SHAPES[shape], case)
    want, state = kda.kda_scan(*args)
    got, new = kda.kda_chunk(*args)
    assert _close(got, want) and _close(new, state) and bool(jnp.isfinite(got).all())


@pytest.mark.parametrize("heads,held", [(1, 1), (6, 3), (13, 1), (30, 3), (64, 4), (128, 4)])
def test_a_program_holds_the_most_heads_that_divide_and_fit(heads, held):
    """`hb` follows the input's shape: the divisors of H under the kernel's bound, and
    the bytes a head's blocks, states and live values take against the chip's VMEM
    (at 384 x 384 a head's states alone are 2.4 MB in the pipeline's buffers: two heads fit, not four)."""
    from tony_tpu.ops import delta_rule

    assert delta_rule._chunk_heads(heads, 64, 128, 128, 2) == held
    assert delta_rule._chunk_heads(heads, 64, 384, 384, 2) == min(held, 2 if heads % 2 == 0 else 1)


@pytest.mark.parametrize("shape", [(4, 128, 16, 32), (3, 64, 8, 8)], ids=["four-heads-of-two-blocks", "three-heads-of-one-block"])
@pytest.mark.parametrize("case", ["strong-beside-weak", "beta-near-2"])
def test_a_program_of_several_heads_is_its_heads_one_at_a_time(interpreted, case, shape):
    """ONE program of all the heads against the same inputs a head at a time (the
    one-head program, the parent's grid): the same operations a head in the same
    order, so the outputs and the states are equal BIT FOR BIT, not within a tolerance."""
    from tony_tpu.ops import kda

    args = _rule_inputs(7, *shape, case)
    got, new = kda.kda_chunk(*args, jnp.int32(shape[1] - 5))
    alone = [kda.kda_chunk(*(a[n:n + 1] for a in args), jnp.int32(shape[1] - 5)) for n in range(shape[0])]
    assert bool((got == jnp.concatenate([o for o, _ in alone])).all()) and bool((new == jnp.concatenate([s for _, s in alone])).all())


@pytest.mark.parametrize("heads", [2, 6], ids=["two-heads-a-program", "two-programs-of-three-heads"])
@pytest.mark.parametrize("case", ["strong-beside-weak", "beta-near-2"])
@pytest.mark.parametrize("valid", [1, 11, 64, 75, 128])
def test_a_padded_chunks_state_stops_at_valid(interpreted, valid, case, heads):
    """Rows past `valid` neither decay nor write: the state is the recurrence's
    after `valid` positions, and the rows before it read what they read unpadded."""
    from tony_tpu.ops import kda

    args = _rule_inputs(2, heads, 128, 16, 32, case)
    want, state = kda.kda_scan(*(a[:, :valid] for a in args[:5]), args[5])
    got, new = kda.kda_chunk(*args, jnp.int32(valid))
    assert _close(got[:, :valid], want) and _close(new, state)


@pytest.mark.parametrize("heads", [2, 6], ids=["two-heads-a-program", "two-programs-of-three-heads"])
@pytest.mark.parametrize("case", ["strong-beside-weak", "every-rate-at-once"])
@pytest.mark.parametrize("cut", [64, 128])
def test_a_chunk_boundary_inside_a_prompt_carries_the_state(interpreted, cut, case, heads):
    """Two chunks, the second from the first's state: the one recurrence."""
    from tony_tpu.ops import kda

    args = _rule_inputs(3, heads, 192, 16, 32, case)
    want, state = kda.kda_scan(*args)
    first, mid = kda.kda_chunk(*(a[:, :cut] for a in args[:5]), args[5])
    second, new = kda.kda_chunk(*(a[:, cut:] for a in args[:5]), mid)
    assert _close(jnp.concatenate([first, second], axis=1), want) and _close(new, state)


@pytest.mark.parametrize("heads", [4, 64], ids=["four-heads-a-program", "two-programs-of-32-heads"])
@pytest.mark.parametrize("case", RULE_CASES)
def test_the_step_form_is_the_recurrence(interpreted, case, heads):
    """One position a slot, each slot its own state: `kda_scan` over one position."""
    from tony_tpu.ops import kda

    S, dk, dv = 3, 16, 32
    q, k, v, g, beta, _ = _rule_inputs(4, S, heads, dk, dv, case)       # [S, H, ...]: a slot where a head's positions were
    state = jax.random.normal(jax.random.PRNGKey(9), (S, heads, dk, dv))
    got, new = kda.kda_step(q, k, v, g, beta, state)
    for s in range(S):
        want, after = kda.kda_scan(*(a[s][:, None] for a in (q, k, v, g, beta)), state[s])
        assert float(jnp.abs(got[s] - want[:, 0]).max()) < 1e-6 * float(jnp.abs(state).max()) and float(jnp.abs(new[s] - after).max()) < 2e-6


def test_a_bfloat16_state_fails_the_tolerance(interpreted):
    """What the float32 state is for: the same chunks from a state rounded to
    bfloat16 at every chunk's edge lie fifty tolerances away."""
    from tony_tpu.ops import kda

    args = _rule_inputs(5, 2, 256, 16, 32, "weak-forgetting")
    want, _ = kda.kda_scan(*args)
    state, rows = args[5], []
    for at in range(0, 256, 64):
        o, state = kda.kda_chunk(*(a[:, at:at + 64] for a in args[:5]), state.astype(jnp.bfloat16).astype(jnp.float32))
        rows.append(o)
    assert float(jnp.abs(jnp.concatenate(rows, axis=1) - want).max()) > 1e-3 * float(jnp.abs(want).max())


def test_a_chunk_that_is_no_power_of_two_is_refused(interpreted):
    from tony_tpu.ops import kda

    with pytest.raises(ValueError, match="power of two"):
        kda.kda_chunk(*_rule_inputs(6, 1, 48, 8, 8, "weak-forgetting"))


# -- the program against the reference ----------------------------------------------------------
@pytest.fixture(scope="module")
def one_forward(tiny):
    seq = _tokens(64, 96)
    got = np.asarray(tiny["module"].forward(tiny["params"], jnp.asarray([seq + [0] * 32], jnp.int32), tiny["cfg"]))[0]
    return seq, got[:96], tiny["ref_logits"](seq)


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
    seq = jnp.asarray(_tokens(65, 64), jnp.int32)
    want = np.asarray(R.trunk(params, seq, dict(sizes, layer_types=(kind,), layers=1), "f32", 32)[0])
    one = dataclasses.replace(cfg, layer_types=(kind,))
    got = np.asarray(m._chunk(params, seq, m._init_staging(one, 64), jnp.int32(64), one)[0])
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
    served widths in bfloat16 the decode batch of 128 and a 512-row bucket are in the kernel, longer chunks staged."""
    from tony_tpu.models import solar_open2 as SO

    cfg = dataclasses.replace(tiny["cfg"], d_model=4096, d_expert=1280, num_experts=320, held=(0, 40), top_k=8, dtype="bfloat16")
    form = SO.serving_programs(cfg, "paged").routed_ffn_form
    assert [form(rows) for rows in (128, 512, 1024, 2048)] == ["in_kernel", "in_kernel", "staged", "staged"]
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


# -- the engines that were there, as they were ------------------------------------------------------
#: sha256 (16 hex) of the lowered text of the three nearest older families' jitted serving programs, taken on the
#: parent commit (0e6c370) by the code of `_lowered` below: the two hybrids this family shares `short_conv_chunk` /
#: `short_conv_step`, the state store's layout and `held_expert_ffn` with, and the routed family whose router it
#: follows. This PR edits NO file those programs import but models/registry.py (one line appended); ops/kda.py is new
#: and imports `_dot` from ops/delta_rule.py, which it does not touch; models/serving.py is not touched.
#: PR 58 gives a program of `delta_chunk` several heads (ops/delta_rule.py): `tiny-olmo-hybrid`'s `prefill_chunk`, the one program of
#: the ten that calls it, a8070b28081f1aad until then; the other nine stand as they stood (no other family imports the chunk kernels)
OLDER = {"tiny-olmo-hybrid": (16, 8), "tiny-granite-hybrid": (16,), "tiny-exaone-moe": ()}
PARENT_LOWERED = {
    "tiny-olmo-hybrid": {"prefill_chunk": "d5b15725174aa157", "insert": "467f8fef5bdd71ae", "gather_prefix": "39af1ed27e7717dc", "decode_chunk": "8ef8c3cb33512f03"},
    "tiny-granite-hybrid": {"prefill_chunk": "0fce7728d40655fb", "insert": "69c05903c8d36adc", "decode_chunk": "a2c311b795fa3b8e"},
    "tiny-exaone-moe": {"prefill_chunk": "f320ace621150f5f", "insert": "275db05cc5c489ca", "decode_chunk": "04de1dc498703da3"},
}


def _lowered(bench, config, max_len=128, page=16, chunk=32):
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config(config), "serve-1chip")
    m, cfg = families.load(sizes["module"]).program(sizes, max_len, *OLDER[config])
    params = jax.eval_shape(lambda: bench["chipside"].seed_weights(sizes, 7))
    slots, n_pages = 2, max_len // page
    cache = jax.eval_shape(lambda: m._init_cache(cfg, slots, max_len, page, slots * n_pages + 1))
    staging = jax.eval_shape(lambda: m._init_staging(cfg, max_len))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    extra = (i32(),) if hasattr(m, "gather_prefix") else ()              # olmo_hybrid's insert also takes where its snapshot goes
    # the modules' own jitted programs, which `serving_programs` hands the engine
    text = {"prefill_chunk": m.prefill_chunk.lower(params, i32(1, chunk), staging, i32(), cfg).as_text(),
            "insert": m.insert_prefill.lower(cache, staging, i32(n_pages), i32(n_pages), i32(), i32(), i32(), i32(), *extra).as_text(),
            "decode_chunk": m.decode_steps.lower(params, cache, i32(slots), jax.ShapeDtypeStruct((2,), jnp.uint32), cfg, 4).as_text()}
    if extra:
        text["gather_prefix"] = m.gather_prefix.lower(staging, cache, i32(n_pages), i32(), i32()).as_text()
    return {k: hashlib.sha256(v.encode()).hexdigest()[:16] for k, v in text.items()}


@pytest.fixture(scope="module")
def lowered(bench, interpreted):
    return {config: _lowered(bench, config) for config in OLDER}


@pytest.mark.parametrize("config,program", [(c, p) for c, programs in PARENT_LOWERED.items() for p in programs])
def test_the_older_families_serving_programs_lower_to_the_parents_text(lowered, config, program):
    assert lowered[config][program] == PARENT_LOWERED[config][program]


def test_a_replica_finds_the_familys_presets():
    from tony_tpu.models import registry, solar_open2

    presets = registry.presets()
    assert presets["solar-open2-tiny"] is solar_open2.PRESETS["solar-open2-tiny"]
    assert registry.module_of(presets["solar-open2-tiny"]) is solar_open2 and {"tiny", "olmo-hybrid-tiny", "granite-hybrid-tiny"} <= set(presets)
    params = jax.eval_shape(lambda: solar_open2.init(jax.random.PRNGKey(0), solar_open2.SOLAR_OPEN2_TINY))
    assert len(params["layers"]) == 8 and params["lm_head"].shape == (64, 256) and params["we_gate"].shape == (8, 4, 64, 32)
    assert params["layers"][1]["w_qkv"].shape == (64, 192) and params["layers"][0]["w_gate"].shape == (64, 64)
    assert params["layers"][1]["w_fb"].shape == (8, 64) and params["layers"][1]["dt_bias"].shape == (64,)


def test_the_programs_own_init_draws_the_trees_the_reference_draws(tiny):
    """`tony serve --preset solar-open2-tiny` draws its weights through the module's
    `init`; the benchmark through the reference's `init_weights`: one tree, leaf for leaf."""
    own = jax.eval_shape(lambda: tiny["module"].init(jax.random.PRNGKey(0), tiny["cfg"]))
    ref = jax.eval_shape(lambda: tiny["params"])
    assert jax.tree.structure(own) == jax.tree.structure(ref)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(jax.tree.leaves(own), jax.tree.leaves(ref)))
    assert tiny["module"].ROUTER_BIAS_SCALE == tiny["sizes"]["router_bias_scale"]


# -- the family's files through the harness ---------------------------------------------------------
def test_the_family_resolves_sizes_program_reference_and_counts(bench):
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config(CONFIG), "serve-1chip")
    assert sizes["layers"] == 4 and sizes["layer_types"] == ("attention", "kda", "kda", "kda")
    assert (sizes["num_experts"], sizes["held"], sizes["top_k"], sizes["vocab"]) == (320, (0, 40), 8, 24_576)
    assert (sizes["kda_heads"], sizes["kda_head_dim"], sizes["conv_taps"], sizes["gate_rank"]) == (64, 128, 4, 128)
    assert (sizes["heads"], sizes["kv_heads"], sizes["head_dim"], sizes["d_expert"], sizes["d_shared"]) == (64, 8, 128, 1280, 1280)
    hash(tuple(sorted(sizes.items())))                               # check.py keys its traced programs by the sizes
    module, cfg = families.load("solar_open2").program(sizes, 6144)
    assert module.__name__ == "tony_tpu.models.solar_open2" and cfg.kda_width == 8192 and cfg.conv_channels == 24_576
    assert cfg.moe.held == (0, 40) and cfg.moe.scoring == "sigmoid" and cfg.moe.routed_scale == 1.0 and cfg.count("kda") == 3
    assert families.reference(sizes).__name__ == "families.solar_open2_reference" and families.reference(sizes).CONTROL == "fp8"
    assert families.counts(sizes).__name__ == "families.solar_open2_counts"


def test_the_configuration_holds_every_published_key_at_its_published_value(bench):
    """Against the catalog's row where the catalog is installed; the four cut keys
    carry the source's value beside the deployment's, and no width is among them."""
    cfg = bench["spec"].config(CONFIG)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("the catalog of architectures is not installed here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Solar-Open2-250B")
    cut = ["gqa_layers", "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["source"] == row["source_url"] and sorted(cfg["reduced"]) == cut
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key]["source"] == value, key
        else:
            assert cfg[key] == value, key
    assert [cfg[k]["serve-1chip"] for k in cut] == [[0], 40, 4, 24_576]
    assert "EIGHT" in cfg["deployments"]["serve-1chip"] and "pipeline stages" in cfg["deployments"]["serve-1chip"]
    assert cfg["assumed"]["gate_rank"]["value"] == 128 and cfg["assumed"]["state_dtype"]["value"] == "float32"
    assert all(set(entry) == {"value", "why"} and len(entry["why"]) > 20 for entry in cfg["assumed"].values())


@pytest.mark.parametrize("change,error,match", [
    ({"use_rope": True}, ValueError, "use_rope"),
    ({"use_gqa_gate": False}, ValueError, "use_gqa_gate"),
    ({"kda_use_full_proj": True}, ValueError, "kda_use_full_proj"),
    ({"tie_word_embeddings": True}, ValueError, "tie_word_embeddings"),
    ({"first_k_dense_replace": 1}, ValueError, "first_k_dense_replace"),
    ({"sliding_window": 4096}, KeyError, "does not know"),
    ({"gqa_interval": 1}, ValueError, "gqa_layers"),
    ({"linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64, "num_kv_heads": 8}}, ValueError, "num_kv_heads"),
    ({"assumed": {}}, KeyError, "assumed"),
], ids=["a-rope", "no-output-gate", "full-gate-projections", "a-tied-head", "a-dense-layer", "an-unknown-key", "another-period",
        "fewer-key-heads", "nothing-assumed"])
def test_the_family_refuses_what_it_does_not_know(bench, change, error, match):
    cfg = {**bench["spec"].config(CONFIG), **change}
    with pytest.raises(error, match=match):
        bench["spec"].model_sizes(cfg, "serve-1chip")


def test_an_assumed_choice_is_one_value(bench):
    cfg = bench["spec"].config(CONFIG)
    cfg = {**cfg, "assumed": {**cfg["assumed"], "kda_output": {"value": "gate_then_rmsnorm", "why": "a guess"}}}
    with pytest.raises(ValueError, match="kda_output"):
        bench["spec"].model_sizes(cfg, "serve-1chip")


def test_a_checkout_without_the_model_module_has_no_such_family(bench, monkeypatch):
    """The benchmark's files laid over a parent commit: run.py's own process says
    so and exits 2 before any launch."""
    fam = bench["families"].load("solar_open2")
    monkeypatch.setattr(fam, "PROGRAM", os.path.join(ROOT, "tony_tpu", "models", "no_such_model.py"))
    with pytest.raises(bench["families"].NoFamily, match="from the commit"):
        bench["spec"].model_sizes(bench["spec"].config(CONFIG), "serve-1chip")


def test_the_counts_on_hand_worked_sizes(bench):
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config(CONFIG), "serve-1chip")
    C, engine = families.counts(sizes), spec.workload(CELL)["engine"]
    assert C.state_bytes(sizes) == 4 * 64 * 128 * 128 == 4_194_304 and C.step_ops(sizes) == 7 * 64 * 128 * 128        # 4.19 MB a layer and slot
    assert C.expert_params(sizes) == 3 * 4096 * 1280 == 15_728_640
    assert C.mixer_params(sizes, "kda") == 4096 * 24_576 + 8192 * 4096 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64 + 4 * 24_576 + 8192 + 64 + 128 == 137_732_288
    assert C.mixer_params(sizes, "attention") == 4096 * 10_240 + 2 * 4096 * 8192 == 109_051_904
    assert C.layer_params(sizes, "kda") == 137_732_288 + 15_728_640 + 1_310_720 + 40 * 15_728_640 == 783_917_248
    assert C.total_params(sizes) == 3 * 783_917_248 + 755_236_864 + 2 * 24_576 * 4096 == 3_308_315_200                  # 3.308 B held
    whole = dict(sizes, held=(0, 320), vocab=196_608, layer_types=("attention", "kda", "kda", "kda") * 12)
    assert round(C.total_params(whole) / 1e9, 1) == 250.3                                                               # the name's 250B
    means = {"live_slots": 120.0, "held_rows_per_step": 480.0, "touched_per_step": 150.0, "prefill_rows_per_chunk": 1024.0}
    assert C.kda_decode_call(sizes, engine, means) == (7 * 64 * 128 * 128 * 120 * 3, 2 * 4_194_304 * 120 * 3)           # 3.0 GB a step
    ops, nbytes = C.kda_prefill_call(sizes, engine, means)
    assert ops == 3 * 1024 * 64 * (6 * 128 * 128 + 2 * 128 * 64 + 3 * 64 * 128) and nbytes == 3 * (1024 * 8192 * 12 + 2 * 4_194_304)
    ops, nbytes = C.moe_decode_call(sizes, engine, means)
    assert (ops, nbytes) == (2 * 15_728_640 * 480, 2 * (150 * 15_728_640 + 2 * 480 * 4096))                              # the slabs COUNTED, not expected
    ops, nbytes = C.moe_prefill_call(sizes, engine, means)
    assert ops == 2 * 15_728_640 * 1024 * 4 and nbytes == 2 * 4 * (40 * 15_728_640 + 2 * 1024 * 4096)
    slots = engine["slots"]
    assert re.search(C.kda_decode_operands(sizes, engine), f"(f32[{slots},2,128,128]{{3,2,1,0}}, f32[{slots},64,128,128]{{3,2,1,0:T(8,128)}}) custom-call")
    assert re.search(C.kda_prefill_operands(sizes, engine), "(bf16[64,2048,128]{2,1,0}, f32[64,128,128]{2,1,0:T(8,128)}) custom-call")
    assert not re.search(C.kda_prefill_operands(sizes, engine), f"f32[{slots},64,128,128]")                             # not the slots' state
    assert re.search(C.moe_decode_operands(sizes, engine), "bf16[4,40,4096,1280]{3,2,1,0}") and re.search(C.moe_prefill_operands(sizes, engine), "bf16[4,40,1280,4096]")
    assert C.kda_decode_calls(sizes, engine) == ("decode_steps", 8) == C.moe_decode_calls(sizes, engine)
    assert C.kda_prefill_calls(sizes, engine) == ("prefill_chunk", 1) == C.moe_prefill_calls(sizes, engine)


def test_the_cell_is_the_issues(bench):
    spec = bench["spec"]
    w, b = spec.workload(CELL), spec.benchmark()
    t, e = w["traffic"], w["engine"]
    assert w["kind"] == "serve" and w["deployment"] == "serve-1chip" and w["chips"] == 1 and w["config"] == CONFIG
    assert t["arrivals"]["process"] == "closed" and t["arrivals"]["clients"] == e["slots"] and e["slots"] in (128, 96, 64)   # 96, 64: the issue's named fallbacks
    assert "sessions" not in t and "prefix" not in t and t["draw_seed"] == 57
    assert t["prompt_len"] == {"dist": "lognormal", "median": 1536, "sigma": 0.7, "min": 256, "max": 4096}
    assert t["answer_len"] == {"dist": "lognormal", "median": 640, "sigma": 0.6, "min": 128, "max": 2048}
    assert {k: e[k] for k in ("max_len", "page_len", "prefill_chunk", "decode_chunk")} == {"max_len": 6144, "page_len": 256, "prefill_chunk": 2048, "decode_chunk": 8}
    assert e["num_pages"] == e["slots"] * 16 + 1 and 4096 + 2048 <= e["max_len"]                      # 16 pages a slot in the mean; the longest request fits
    listed = {m["name"] for m in spec.cell_metrics(b, CELL, "per_layer")}
    assert {"kda_decode_roofline_pct.serve", "kda_prefill_roofline_pct.serve", "moe_decode_roofline_pct.serve", "moe_prefill_roofline_pct.serve",
            "held_share_pct.serve", "launch_s", "decode_step_ms.serve_tput", "slots_active_mean.serve"} <= listed
    assert not ({"prefix_hit_pct.serve", "delta_decode_roofline_pct.serve", "ssd_decode_roofline_pct.serve", "expert_rows_max_over_mean.serve"} & listed)
    assert {m["name"] for m in spec.cell_metrics(b, CELL, "end_to_end")} == {"serve_out_tok_s", "setup_s"}
    for name, kernel, match in (("kda_decode_roofline_pct.serve", "kda_decode", "kda_step"), ("kda_prefill_roofline_pct.serve", "kda_prefill", "kda_chunk")):
        m = next(m for m in b["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and spec.metric(name)["reader"] == "family_roofline" and spec.metric(name)["args"] == {"kernel": kernel, "match": match}
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size"]
    assert entry["file"] == "benchmark/configs/solar-open2-250b.json" and b["workloads"][10]["name"] == CELL and sum(c["chips"] == 4 for c in b["workloads"]) == 1   # the eleventh cell; later ones follow it


# -- the family's rehearsal (benchmark/tests/test_solar_open2_rehearsal.py), run with the suite
def _rehearsal():
    import importlib.util

    spec_ = importlib.util.spec_from_file_location("solar_open2_rehearsal", os.path.join(BENCH, "tests", "test_solar_open2_rehearsal.py"))
    module = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(module)
    return module


test_the_rehearsal_cell_runs_end_to_end_on_the_cpu = _rehearsal().test_the_rehearsal_cell_runs_end_to_end_on_the_cpu
test_the_control_lies_far_from_the_reference_and_the_program_on_it = _rehearsal().test_the_control_lies_far_from_the_reference_and_the_program_on_it
