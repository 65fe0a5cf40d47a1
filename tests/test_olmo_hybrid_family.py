"""The olmo_hybrid family's files through the benchmark's harness (sizes, the published configuration, what it
refuses, the counts on hand-worked sizes, the window's means, the cell), the engines that were there as
they were, and the family's rehearsal and the generator's sessions. The program against its reference and
the state's snapshots through the engine are tests/test_olmo_hybrid.py; the rule's forms
tests/test_olmo_hybrid_rule.py.
"""
import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CONFIG, CELL = "olmo-hybrid-7b", "olmo-hybrid-7b.serve_sessions"


def _tokens(seed, n, vocab=256):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


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
