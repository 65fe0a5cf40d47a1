"""The phi4_flash family on the CPU at a tiny size (`tiny-phi4-flash`: hidden 64, eight
layers: mamba, window, mamba whose scan is the memory, full, gmu, cross, gmu, cross; 8 / 4
heads of 8 in two stripes, a window of 8, an inner width of 128 over a state of 16, an FFN
of 96, LayerNorm with biases, float32; pages of 16 positions): the program against the
family's plain reference (forward; prefill chunks then decode through the ONE layer's pool,
the rings, the states and the tails), a chunk's one cross-decoder row against a forward that
ran all rows, the shared page, `lam0`, the sub-norm and the stripes each shown to matter,
and the engine. The kernels' forms are tests/test_selective_scan.py and
tests/test_differential_attention.py; the family's files through the benchmark's harness
and its rehearsal are tests/test_phi4_flash_family.py.

Tolerances. The seeded logits are of size ONE (the embedding is drawn over a fan-in of the
width; 4.3 at most here); program and reference, both float32, agree to 6e-6 on them and
LOGIT_TOL is 3e-5; the float8 control moves them by 1.5.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

TINY, MAX_LEN, PAGE = "tiny-phi4-flash", 128, 16
LOGIT_TOL = 3e-5


@pytest.fixture(scope="module")
def tiny(bench, interpreted):
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config(TINY), "serve-1chip")
    module, cfg = families.load("phi4_flash").program(sizes, MAX_LEN, PAGE)
    reference = families.reference(sizes)
    params = bench["chipside"].seed_weights(sizes, 7)
    ref = jax.jit(lambda p, t: reference.forward(p, t, sizes, "f32", 32))

    def ref_logits(seq, params=params):
        """The reference's logits for every position of `seq`, padded at the end
        to one length (one compile; a causal model's positions do not see it)."""
        return np.asarray(ref(params, jnp.asarray(list(seq) + [0] * (MAX_LEN - len(seq)), jnp.int32)))[:len(seq)]

    return {"sizes": sizes, "module": module, "cfg": cfg, "reference": reference, "params": params, "ref_logits": ref_logits}


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


def test_the_layer_kinds_are_the_configurations(tiny):
    cfg, R, sizes = tiny["cfg"], tiny["reference"], tiny["sizes"]
    kinds = ["mamba", "window", "mamba", "full", "gmu", "cross", "gmu", "cross"]
    assert [cfg.kind(i) for i in range(cfg.n_layers)] == kinds == [R.kind(i, sizes) for i in range(sizes["layers"])]
    published = dataclasses.replace(cfg, n_layers=32, memory_layer=16)
    assert [published.kind(i) for i in (0, 1, 15, 16, 17, 18, 19, 30, 31)] == ["mamba", "window", "window", "mamba", "full", "gmu", "cross", "gmu", "cross"]
    assert published.periods == (8, 7) and cfg.periods == (1, 2)


def test_the_lower_precision_control_fails_the_tolerance(tiny):
    """The reference with every matrix product's operands rounded to float8: what a program computed
    in a precision below the configuration's would read."""
    seq = _tokens(68, 96)
    ctl = np.asarray(tiny["reference"].forward(tiny["params"], jnp.asarray(seq + [0] * 32, jnp.int32), tiny["sizes"], "fp8", 32))[:96]
    assert np.abs(ctl - tiny["ref_logits"](seq)).max() > 1000 * LOGIT_TOL


# -- each piece of the differential form matters, and is computed where the reference computes it -------
def _forward_with(tiny, monkeypatch, module, name, changed):
    """The program's whole forward with one function of `module` replaced: (the sequence, its logits)."""
    monkeypatch.setattr(module, name, changed)
    seq = _tokens(67, 64)
    return seq, np.asarray(tiny["module"].forward(tiny["params"], jnp.asarray([seq], jnp.int32), tiny["cfg"]))[0]


def test_lam0_follows_the_layers_index(tiny, monkeypatch):
    """lam0 = 0.8 - 0.6 exp(-0.3 i) by the layer's own index: the program with one constant for every layer fails."""
    assert np.allclose(tiny["module"].lam0(np.array([1, 17, 31])), [0.8 - 0.6 * np.exp(-0.3), 0.8 - 0.6 * np.exp(-5.1), 0.8 - 0.6 * np.exp(-9.3)])
    seq, got = _forward_with(tiny, monkeypatch, tiny["module"], "lam0", lambda i: 0.5 + 0.0 * np.asarray(i, np.float32))
    assert np.abs(got - tiny["ref_logits"](seq)).max() > 100 * LOGIT_TOL


def test_the_sub_norm_matters(tiny, monkeypatch):
    """A1 - lam A2 without its RMSNorm over the pair's width (the weight and (1 - lam0) kept) fails."""
    from tony_tpu.ops import attention as A

    def unnormed(o, lam, lam0, weight, eps):
        *lead, H, wide = o.shape
        maps = o.astype(jnp.float32).reshape(*lead, H // 2, 2, wide)
        return (maps[..., 0, :] - lam * maps[..., 1, :]) * weight.astype(jnp.float32) * (1.0 - lam0)

    seq, got = _forward_with(tiny, monkeypatch, A, "differential_combine", unnormed)
    assert np.abs(got - tiny["ref_logits"](seq)).max() > 100 * LOGIT_TOL


def test_the_stripes_matter(tiny, monkeypatch):
    """The stripes the other way round (the odd query heads against the even kv heads) fail."""
    from tony_tpu.ops import attention as A

    widened = A.differential_queries
    seq, got = _forward_with(tiny, monkeypatch, A, "differential_queries", lambda q, dtype=None: jnp.roll(widened(q, dtype), q.shape[-1], axis=-1))
    assert np.abs(got - tiny["ref_logits"](seq)).max() > 100 * LOGIT_TOL


@pytest.mark.parametrize("leaf", ["norm_b", "ffn_norm_b", "b_qkv", "bo", "conv_bias"])
def test_every_bias_is_read(tiny, leaf):
    """A bias set to zero in the weights moves the program as it moves the reference: far, and to the same place."""
    zeroed = jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a) if path[-1].key == leaf else a, tiny["params"])
    seq = _tokens(69, 64)
    got = np.asarray(tiny["module"].forward(zeroed, jnp.asarray([seq], jnp.int32), tiny["cfg"]))[0]
    assert np.abs(got - tiny["ref_logits"](seq)).max() > 30 * LOGIT_TOL
    assert np.abs(got - tiny["ref_logits"](seq, zeroed)).max() < LOGIT_TOL


# -- prefill in chunks, the one cross-decoder row, then decode ---------------------------------------
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


@pytest.mark.parametrize("take", [1, 19, 32], ids=["first-row", "a-middle-row", "last-row"])
def test_a_chunks_one_cross_decoder_row_is_the_row_of_a_forward_that_ran_all_rows(tiny, take):
    """The prefill's claim: layers above the full one write nothing, so the row `take - 1` sent through them
    alone (its memory, the staged keys up to it) is the same row of the program that sent every row through."""
    m, cfg = tiny["module"], tiny["cfg"]
    seq = jnp.asarray(_tokens(70, 32), jnp.int32)
    whole, _ = m._chunk(tiny["params"], seq, m._init_staging(cfg, MAX_LEN), jnp.int32(take), cfg)
    one, _ = m._chunk(tiny["params"], seq, m._init_staging(cfg, MAX_LEN), jnp.int32(take), cfg, row=jnp.int32(take - 1))
    assert one.shape == (1, cfg.d_model) and np.abs(np.asarray(one)[0] - np.asarray(whole)[take - 1]).max() < 1e-5


@pytest.mark.parametrize("prompt_len,chunk", [(5, 32), (29, 32), (32, 32), (49, 32), (77, 32)],
                         ids=["one-short-chunk", "under-a-chunk", "one-whole-chunk", "two-chunks", "three-chunks-the-last-padded"])
def test_chunked_prefill_then_decode_agree_with_the_reference(tiny, prompt_len, chunk):
    """LOGITS: the last prompt row from the chunked prefill (one chunk, two, three; a padded last chunk whose
    states, tails and rings stop at its last real row), then 12 decode steps a position at a time through the
    ONE layer's pages, the rings (a window of 8: every prompt but the first is past its edge, and the steps
    walk on), the carried states and tails, crossing a page's edge: every step's logits against the
    reference's full forward of everything so far, which ran EVERY layer on EVERY row."""
    m, cfg = tiny["module"], tiny["cfg"]
    prompt = _tokens(prompt_len + chunk, prompt_len)
    staging, last = _prefill(tiny, prompt, chunk)
    assert np.abs(last - tiny["ref_logits"](prompt)[-1]).max() < LOGIT_TOL
    slots, slot = 2, 1
    cache = _admit(tiny, staging, slots, slot, prompt_len)
    assert cache.k.shape[0] == 1 and cache.wk.shape[0] == 1 and cache.state.shape[0] == cache.tail.shape[0] == 2   # ONE layer of pages
    seq, toks = list(prompt), np.zeros(slots, np.int32)
    toks[slot] = int(np.argmax(last))
    for _ in range(12):
        seq.append(int(toks[slot]))
        logits, cache = m.decode_logits(tiny["params"], cache, jnp.asarray(toks), cfg)
        assert np.abs(np.asarray(logits)[slot] - tiny["ref_logits"](seq)[-1]).max() < LOGIT_TOL
        toks[slot] = int(np.argmax(np.asarray(logits)[slot]))
    assert np.asarray(cache.lengths).tolist() == [0, prompt_len + 12]


def test_every_cross_layer_reads_the_full_layers_page_written_once(tiny, monkeypatch):
    """A decode step makes ONE read of the shared pool a reading layer (the full layer and both cross layers here),
    every one with the same pool, the same table, the same staged rows and the same current pair: the full
    layer's. A cross layer that read keys of its own would hand the kernel another current pair."""
    from tony_tpu.ops import decode_attention as DA

    m, cfg = tiny["module"], tiny["cfg"]
    staging, last = _prefill(tiny, _tokens(71, 40), 32)
    cache = _admit(tiny, staging, 2, 0, 40)
    seen = []
    paged = DA.paged_decode_attention

    def spy(q, kp, vp, lengths, page_table, layer, **rest):
        seen.append((kp, page_table, layer, rest["cur_k"], rest["cur_v"], rest["staged_k"]))
        return paged(q, kp, vp, lengths, page_table, layer, **rest)

    monkeypatch.setattr(DA, "paged_decode_attention", spy)
    with jax.disable_jit():
        m._decode_one(tiny["params"], cache, jnp.asarray([int(np.argmax(last)), 0]), cfg, (m._stages(cfg, cache, 2, 1), jnp.int32(0)))
    assert len(seen) == 1 + cfg.periods[1] == 3
    for kp, table, layer, cur_k, cur_v, staged_k in seen:
        assert kp is cache.k and kp.shape[0] == 1 and table is cache.page_table and int(layer) == 0
        assert cur_k is seen[0][3] and cur_v is seen[0][4] and np.array_equal(staged_k, seen[0][5])
    # and a poisoned page shows in every reader: the logits move when the one pool's live page does
    clean, _ = m.decode_logits(tiny["params"], cache, jnp.asarray([1, 0]), cfg)               # donates the cache
    poisoned = _admit(tiny, staging, 2, 0, 40)
    dirty, _ = m.decode_logits(tiny["params"], poisoned._replace(k=poisoned.k.at[0, 1].add(1.0)), jnp.asarray([1, 0]), cfg)
    assert np.abs(np.asarray(clean)[0] - np.asarray(dirty)[0]).max() > 100 * LOGIT_TOL


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


def test_the_engine_decodes_the_references_greedy_tokens_and_counts_the_cross_rows(tiny):
    """Three requests over two slots through the normal engine (chunked prefill, admission, decode chunks
    of 4 through the staged rows every reader shares, a slot used again): each answer is the reference's
    greedy one, no prefix is shared, and a prefill chunk sends ONE row through the cross-decoder."""
    before = _counters()
    eng = _engine(tiny)
    prompts = [_tokens(90 + i, n) for i, n in enumerate((40, 21, 67))]
    rids = [eng.submit(p, 9) for p in prompts]
    done = eng.run()
    for rid, p in zip(rids, prompts):
        assert done[rid] == _greedy(tiny, p, 9)
    moved = {k: v - before.get(k, 0) for k, v in _counters().items()}
    assert eng.prefix_hit_tokens == 0 and eng.programs.gather_prefix is None and eng.programs.routed_ffn_form is None
    # the rows as dispatched, a last chunk padded to its bucket: 40 = 32 + 8 (16), 21 (32), 67 = 32 + 32 + 3 (16): six chunks
    assert moved["tony_serve_prefill_tokens_total"] == 48 + 32 + 80 and moved["tony_serve_cross_rows_total"] == 6
    assert moved["tony_serve_context_tokens_total"] > 0 and moved["tony_serve_decode_slots_total"] > 0


def test_a_slot_used_again_reads_nothing_of_its_last_tenant(tiny):
    """Pages, rings, states and tails stay in a released slot; the next admission overwrites all a step
    may read: the same prompt twice, with every cache poisoned in between."""
    eng = _engine(tiny, num_slots=1)
    prompt = _tokens(30, 37)
    first = eng.submit(prompt, 9)
    done = eng.run()
    c = eng.cache
    eng.cache = c._replace(k=jnp.full_like(c.k, 1e4), v=jnp.full_like(c.v, 1e4), wk=jnp.full_like(c.wk, 1e4), wv=jnp.full_like(c.wv, 1e4),
                           state=jnp.full_like(c.state, 1e3), tail=jnp.full_like(c.tail, 1e3))
    again = eng.submit(prompt, 9)
    assert eng.run()[again] == done[first] == _greedy(tiny, prompt, 9)


def test_the_kinds_are_named_in_the_programs(tiny):
    """A trace splits a step between the kinds of layer by these scopes (docs/observability.md), and finds the
    scans by their kernels' names."""
    m, cfg = tiny["module"], tiny["cfg"]
    params = jax.eval_shape(lambda: tiny["params"])
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    staging = jax.eval_shape(lambda: m._init_staging(cfg, MAX_LEN))
    cache = jax.eval_shape(lambda: m._init_cache(cfg, 2, MAX_LEN, PAGE, 17))
    prefill = m.prefill_chunk.lower(params, i32(1, 32), staging, i32(), cfg).as_text(debug_info=True)
    decode = m.decode_steps.lower(params, cache, i32(2), jax.ShapeDtypeStruct((2,), jnp.uint32), cfg, 4).as_text(debug_info=True)
    for scope in ("phi4_flash.mamba", "phi4_flash.window", "phi4_flash.full", "phi4_flash.gmu", "phi4_flash.cross", "phi4_flash.ffn"):
        assert scope in prefill and scope in decode, scope


def test_the_modules_own_draw_is_the_references(tiny):
    """`tony serve --preset` draws through the module's `init`, the benchmark through the reference's
    `init_weights`: one draw (to a float32's last place: one is jitted), so that a served preset has the reference's scales."""
    R, m = tiny["reference"], tiny["module"]
    mine = m.init(R.seed_key(7), tiny["cfg"])
    assert jax.tree.structure(mine) == jax.tree.structure(tiny["params"])
    assert all(jax.tree.leaves(jax.tree.map(lambda a, b: bool(jnp.allclose(a, b, rtol=1e-6, atol=1e-7)), mine, tiny["params"])))
