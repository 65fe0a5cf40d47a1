"""The granite_hybrid family's files through the benchmark's harness (sizes, the published configuration, what
it refuses, the counts on hand-worked sizes, the window's means, the cell), the engines that were there as
they were (`tiny-olmo-hybrid`'s serving programs lower to the parent's text), and the family's rehearsal
end to end on the CPU. The program against its reference is tests/test_granite_hybrid.py; the scan's forms
tests/test_granite_hybrid_scan.py.
"""
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CONFIG, CELL = "granite-4.0-h-small", "granite-4.0-h-small.serve_assist"


# -- the engines that were there, as they were ------------------------------------------------------
#: sha256 (16 hex) of the lowered text of `tiny-olmo-hybrid`'s jitted serving programs, taken on the parent commit
#: (920d5c3) by the code of `_lowered_olmo_hybrid` below. The five families older than it are held by
#: tests/test_dots3_note.py's, tests/test_mistral4.py's and tests/test_olmo_hybrid_family.py's tables, whose hashes this PR
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
