"""The solar_open2 family's files through the benchmark's harness (sizes, the published configuration, what
it refuses, the counts on hand-worked sizes, the cell), the engines that were there as they were (their
serving programs lower to the parent's text), and the family's rehearsal end to end on the CPU. The
program against its reference is tests/test_solar_open2.py; the rule's forms tests/test_solar_open2_rule.py.
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
CONFIG, CELL = "solar-open2-250b", "solar-open2-250b.serve_extract"


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
