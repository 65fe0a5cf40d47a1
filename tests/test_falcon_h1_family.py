"""The falcon_h1 family's files through the benchmark's harness (sizes, the published configuration, what it
refuses, the counts on hand-worked sizes, the window's means, the cell), the engines that were there as
they were (the one-group family's serving programs lower to the parent's text), and the family's rehearsal
end to end on the CPU. The program against its reference is tests/test_falcon_h1.py; the grouped scan's
forms tests/test_falcon_h1_scan.py.
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
TINY, MAX_LEN, PAGE = "tiny-falcon-h1", 128, 16
CONFIG, CELL = "falcon-h1-34b", "falcon-h1-34b.serve_rewrite"


# -- the engines that were there, as they were ------------------------------------------------------
#: sha256 (16 hex) of the lowered text of `tiny-granite-hybrid`'s jitted serving programs, taken on the parent commit
#: (4068d63) by the code of `_lowered_granite_hybrid` below: the ONE-group path of ops/ssd.py is the program it was.
#: This PR edits one file those programs import, ops/ssd.py (B and C may come in groups; with [T, N] the call, its
#: operands, its index maps and its kernels' bodies are what they were), and appends one name to models/registry.py;
#: models/serving.py and models/paged_cache.py are not touched. The families older than granite_hybrid are held by
#: the tables of tests/test_dots3_note.py, tests/test_mistral4.py, tests/test_olmo_hybrid_family.py and tests/test_granite_hybrid_family.py.
PARENT_LOWERED_GRANITE_HYBRID = {"prefill_chunk": "0fce7728d40655fb", "insert": "69c05903c8d36adc", "decode_chunk": "a2c311b795fa3b8e"}


def _lowered_granite_hybrid(bench, max_len=128, page=16, chunk=32):
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config("tiny-granite-hybrid"), "serve-1chip")
    m, cfg = families.load("granite_hybrid").program(sizes, max_len, page)
    params = jax.eval_shape(lambda: bench["chipside"].seed_weights(sizes, 7))
    slots, n_pages = 2, max_len // page
    cache = jax.eval_shape(lambda: m._init_cache(cfg, slots, max_len, page, slots * n_pages + 1))
    staging = jax.eval_shape(lambda: m._init_staging(cfg, max_len))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    text = {
        "prefill_chunk": m.prefill_chunk.lower(params, i32(1, chunk), staging, i32(), cfg).as_text(),
        "insert": m.insert_prefill.lower(cache, staging, i32(n_pages), i32(n_pages), i32(), i32(), i32(), i32()).as_text(),
        "decode_chunk": m.decode_steps.lower(params, cache, i32(slots), jax.ShapeDtypeStruct((2,), jnp.uint32), cfg, 4).as_text(),
    }
    return {k: hashlib.sha256(v.encode()).hexdigest()[:16] for k, v in text.items()}


@pytest.mark.parametrize("program", list(PARENT_LOWERED_GRANITE_HYBRID))
def test_the_one_group_family_lowers_to_the_parents_text(bench, interpreted, program):
    assert _lowered_granite_hybrid(bench)[program] == PARENT_LOWERED_GRANITE_HYBRID[program]


def test_a_replica_finds_the_familys_presets():
    from tony_tpu.models import falcon_h1, registry

    presets = registry.presets()
    assert presets["falcon-h1-tiny"] is falcon_h1.PRESETS["falcon-h1-tiny"]
    assert registry.module_of(presets["falcon-h1-tiny"]) is falcon_h1 and {"tiny", "granite-hybrid-tiny", "solar-open2-tiny"} <= set(presets)
    params = jax.eval_shape(lambda: falcon_h1.init(jax.random.PRNGKey(0), falcon_h1.FALCON_H1_TINY))
    assert len(params["layers"]) == 2 and params["lm_head"].shape == params["embed"].shape == (256, 64)
    lp = params["layers"][0]
    assert lp["w_qkv"].shape == (64, (10 + 2 * 2) * 16) and lp["w_in"].shape == (64, 2 * 128 + 2 * 2 * 16) and lp["w_dt"].shape == (64, 4)
    assert lp["conv"].shape == (4, 128 + 64) and lp["y_norm"].shape == (128,) and lp["w_gate"].shape == (64, 160)


# -- the family's files through the harness ---------------------------------------------------------
def test_the_family_resolves_sizes_program_reference_and_counts(bench):
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config(CONFIG), "serve-1chip")
    assert (sizes["layers"], sizes["vocab"], sizes["d_model"], sizes["d_ff"]) == (9, 130_560, 5120, 21_504)
    assert (sizes["ssm_heads"], sizes["ssm_head_dim"], sizes["ssm_state"], sizes["ssm_groups"], sizes["conv_taps"]) == (32, 128, 256, 2, 4)
    assert (sizes["heads"], sizes["kv_heads"], sizes["head_dim"], sizes["rope_theta"]) == (20, 4, 128, 1e11)
    assert len(sizes["ssm_multipliers"]) == 5 and len(sizes["mlp_multipliers"]) == 2 and sizes["attention_in_multiplier"] == 1.0
    hash(tuple(sorted(sizes.items())))                               # check.py keys its traced programs by the sizes
    module, cfg = families.load("falcon_h1").program(sizes, 3072)
    assert module.__name__ == "tony_tpu.models.falcon_h1" and cfg.d_inner == 4096 and cfg.conv_channels == 5120 and cfg.n_layers == 9
    assert cfg.ssm_multipliers == sizes["ssm_multipliers"] and cfg.key_multiplier == sizes["key_multiplier"] and cfg.max_seq == 3072
    assert families.reference(sizes).__name__ == "families.falcon_h1_reference" and families.reference(sizes).CONTROL == "fp8"
    assert families.counts(sizes).__name__ == "families.falcon_h1_counts"


def test_the_configuration_holds_every_published_key_at_its_published_value(bench):
    """Against the catalog's row where the catalog is installed; the two cut keys
    carry the source's value beside the deployment's."""
    cfg = bench["spec"].config(CONFIG)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("the catalog of architectures is not installed here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Falcon-H1-34B-Instruct")
    cut = ["num_hidden_layers", "vocab_size"]
    assert cfg["source"] == row["source_url"] and sorted(cfg["reduced"]) == cut
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key]["source"] == value, key
        else:
            assert cfg[key] == value, key
    assert [cfg[k]["serve-1chip"] for k in cut] == [9, 130_560]
    assert "eight pipeline stages of nine" in cfg["deployments"]["serve-1chip"] and "ONE of the two vocabulary matrices" in cfg["reduced"]["vocab_size"]
    assert all(set(entry) == {"value", "why"} and len(entry["why"]) > 40 for entry in cfg["assumed"].values())
    tiny = bench["spec"].config(TINY)
    scalars = [k for k in cfg if k.endswith("_multiplier") or k.endswith("_multipliers")]
    assert len(scalars) == 9 and all(tiny[k] == cfg[k] for k in scalars) and tiny["mamba_n_groups"] == 2                  # the same scalars at the tiny size


@pytest.mark.parametrize("change,error,match", [
    ({"attn_layer_indices": [0, 8]}, ValueError, "attn_layer_indices"),
    ({"mamba_norm_before_gate": True}, ValueError, "mamba_norm_before_gate"),
    ({"tie_word_embeddings": True}, ValueError, "tie_word_embeddings"),
    ({"sliding_window": 4096}, KeyError, "does not know"),
    ({"mamba_d_ssm": 10240}, ValueError, "inner width"),
    ({"mamba_n_groups": 3}, ValueError, "divides"),
    ({"ssm_multipliers": [0.5, 0.5]}, ValueError, "ssm_multipliers"),
    ({"assumed": {}}, KeyError, "assumed"),
], ids=["attention-in-some-layers", "norm-before-gate", "a-tied-head", "an-unknown-key", "expand-x-hidden", "groups-that-do-not-divide",
        "a-short-vector", "nothing-assumed"])
def test_the_family_refuses_what_it_does_not_know(bench, change, error, match):
    cfg = {**bench["spec"].config(CONFIG), **change}
    with pytest.raises(error, match=match):
        bench["spec"].model_sizes(cfg, "serve-1chip")


@pytest.mark.parametrize("choice", ["block", "rope", "in_proj_order", "ssm_output", "dt_limits", "mlp_multipliers_order", "state_dtype", "ssm_init",
                                    "matrix_init"])
def test_an_assumed_choice_is_one_value(bench, choice):
    cfg = bench["spec"].config(CONFIG)
    cfg = {**cfg, "assumed": {**cfg["assumed"], choice: {"value": "another", "why": "a guess"}}}
    with pytest.raises(ValueError, match=choice):
        bench["spec"].model_sizes(cfg, "serve-1chip")


def test_the_one_group_family_still_refuses_what_its_configuration_does_not_publish(bench):
    """Groups are in ops/ssd.py now; granite_hybrid's family computes ITS configuration's one group only (its
    gated norm spans the whole inner width). Its message still calls one group a law ("here"): the file is the
    benchmark's, and a PR that is not a `benchmark` one edits none of those (CHANGES.md, PR 59)."""
    cfg = {**bench["spec"].config("granite-4.0-h-small"), "mamba_n_groups": 2}
    with pytest.raises(ValueError, match="one group"):
        bench["spec"].model_sizes(cfg, "serve-1chip")


def test_a_checkout_without_the_model_module_has_no_such_family(bench, monkeypatch):
    """The benchmark's files laid over a parent commit: run.py's own process says
    so and exits 2 before any launch."""
    fam = bench["families"].load("falcon_h1")
    monkeypatch.setattr(fam, "PROGRAM", os.path.join(ROOT, "tony_tpu", "models", "no_such_model.py"))
    with pytest.raises(bench["families"].NoFamily, match="from the commit"):
        bench["spec"].model_sizes(bench["spec"].config(CONFIG), "serve-1chip")


def test_the_counts_on_hand_worked_sizes(bench):
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config(CONFIG), "serve-1chip")
    C, engine = families.counts(sizes), spec.workload(CELL)["engine"]
    assert C.state_bytes(sizes) == 4 * 32 * 128 * 256 == 4_194_304 and C.step_ops(sizes) == 5 * 32 * 128 * 256      # 4.19 MB a layer and slot
    assert C.layer_params(sizes) == 31_457_280 + (5120 * 9248 + 4096 * 5120 + 5 * 5120 + 3 * 32 + 4096) + 3 * 5120 * 21_504 == 430_109_792
    assert C.total_params(sizes) == 9 * 430_109_792 + 2 * 130_560 * 5120 == 5_207_922_528                            # 5.208 B held, 10.42 GB
    assert 72 * C.layer_params(sizes) + 2 * 261_120 * 5120 == 33_641_773_824                                          # the name's 34 B
    means = {"live_slots": 40.0, "context_per_slot": 1100.0, "prefill_rows_per_chunk": 512.0, "prefill_pairs_per_chunk": 512 * 256 + 512 * 513 // 2}
    assert C.ssd_decode_call(sizes, engine, means) == (5 * 32 * 128 * 256 * 40 * 9, 2 * 4_194_304 * 40 * 9)          # 3.0 GB a step at 40 live
    ops, nbytes = C.ssd_prefill_call(sizes, engine, means)
    assert ops == 9 * 512 * (2 * 2 * 128 * 256 + 32 * (2 * 128 * 128 + 4 * 256 * 128)) and nbytes == 9 * (2 * 512 * (2 * 4096 + 2 * 512) + 2 * 4_194_304)
    assert C.attn_decode_call(sizes, engine, means) == (4 * 20 * 128 * 40 * 1100 * 9, 18_432 * 40 * 1100)            # 18,432 B a position over nine layers
    ops, nbytes = C.attn_prefill_call(sizes, engine, means)
    assert ops == 4 * 20 * 128 * means["prefill_pairs_per_chunk"] * 9 and nbytes == 9 * 2 * 128 * (2 * 4 * (means["prefill_pairs_per_chunk"] / 512 + 256) + 2 * 20 * 512)
    assert re.search(C.ssd_decode_operands(sizes, engine), "(f32[48,1,4096]{2,1,0}, f32[48,256,4096]{2,1,0:T(8,128)}) custom-call")
    assert re.search(C.ssd_prefill_operands(sizes, engine), "(bf16[1024,4096]{1,0}, f32[256,4096]{1,0:T(8,128)}) custom-call")
    assert not re.search(C.ssd_prefill_operands(sizes, engine), "f32[48,256,4096]")                                   # not the slots' state
    assert re.search(C.attn_decode_operands(sizes, engine), "bf16[9,577,4,256,128]{4,3,2,1,0}") and not re.search(C.attn_decode_operands(sizes, engine), "bf16[9,1,4,3072,128]")
    assert re.search(C.attn_prefill_operands(sizes, engine), "bf16[9,1,4,3072,128]") and not re.search(C.attn_prefill_operands(sizes, engine), "bf16[9,577,4,256,128]")
    assert C.ssd_decode_calls(sizes, engine) == ("decode_steps", 8) == C.attn_decode_calls(sizes, engine)
    assert C.ssd_prefill_calls(sizes, engine) == ("prefill_chunk", 1) == C.attn_prefill_calls(sizes, engine)


def test_window_means_from_the_replicas_counters(bench):
    sizes = bench["spec"].model_sizes(bench["spec"].config(CONFIG), "serve-1chip")
    C = bench["families"].counts(sizes)
    deltas = {"tony_serve_engine_chunks_total": 10, "tony_serve_decode_slots_total": 400, "tony_serve_context_tokens_total": 400 * 8 * 1100,
              "tony_serve_prefill_tokens_total": 7 * 1024, "tony_serve_prefill_chunks_total": 7, "tony_serve_prefill_pairs_total": 7 * 600_000}
    means = C.window_means(lambda name, where=None: deltas.get(name), {"decode_chunk": 8})
    assert means == {"live_slots": 40.0, "context_per_slot": 1100.0, "prefill_rows_per_chunk": 1024.0, "prefill_pairs_per_chunk": 600_000.0}
    assert C.window_means(lambda name, where=None: None, {}) is None                   # a program without the counters


def test_the_cell_is_the_issues(bench):
    spec = bench["spec"]
    w, b = spec.workload(CELL), spec.benchmark()
    t, e = w["traffic"], w["engine"]
    assert w["kind"] == "serve" and w["deployment"] == "serve-1chip" and w["chips"] == 1 and w["config"] == CONFIG
    assert t["arrivals"] == {"process": "closed", "clients": e["slots"], "ramp_s": 8.0} and 32 <= e["slots"] <= 48     # callers = slots, the most that fit
    assert "sessions" not in t and "prefix" not in t and t["draw_seed"] == 59
    assert t["prompt_len"] == {"dist": "lognormal", "median": 768, "sigma": 0.7, "min": 128, "max": 2048}
    assert t["answer_len"] == {"dist": "lognormal", "median": 512, "sigma": 0.5, "min": 64, "max": 1024}
    assert {k: e[k] for k in ("max_len", "page_len", "prefill_chunk", "decode_chunk")} == {"max_len": 3072, "page_len": 256, "prefill_chunk": 1024, "decode_chunk": 8}
    assert e["num_pages"] == e["slots"] * (e["max_len"] // e["page_len"]) + 1 and 2048 + 1024 <= e["max_len"]        # every slot's pages; the longest request fits
    assert w["check"]["samples"] == 2 and 0 < w["check"]["worst_gap_limit"] and "control" in w["check"]["why"]
    listed = {m["name"] for m in spec.cell_metrics(b, CELL, "per_layer")}
    rooflines = {"ssd_decode_roofline_pct.serve": ("ssd_decode", "ssd_step"), "ssd_prefill_roofline_pct.serve": ("ssd_prefill", "ssd_chunk"),
                 "attn_decode_roofline_pct.serve": ("attn_decode", "tpu_custom_call"), "attn_prefill_roofline_pct.serve": ("attn_prefill", "chunk_prefill_attention")}
    assert set(rooflines) | {"launch_s", "decode_step_ms.serve_tput", "slots_active_mean.serve", "host_gap_pct.serve_tput", "weights_s"} <= listed
    assert not {m for m in listed if m.startswith(("moe_", "held_share", "expert_rows", "prefix_hit", "delta_", "kda_"))}
    assert {m["name"] for m in spec.cell_metrics(b, CELL, "end_to_end")} == {"serve_out_tok_s", "setup_s"}
    for name, (kernel, match) in rooflines.items():
        m = next(m for m in b["per_layer"] if m["name"] == name)
        assert CELL in m["workloads"] and spec.metric(name)["reader"] == "family_roofline" and spec.metric(name)["args"] == {"kernel": kernel, "match": match}
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"] and entry["source"] == spec.config(CONFIG)["source"]
    assert entry["file"] == "benchmark/configs/falcon-h1-34b.json" and len(b["workloads"]) >= 12 and sum(c["chips"] == 4 for c in b["workloads"]) == 1
    assert b["workloads"][11]["name"] == CELL and b["configs"][8]["name"] == CONFIG                                    # appended behind the eleven cells of eight configurations


# -- the family's rehearsal (benchmark/tests/test_falcon_h1_rehearsal.py), run with the suite
def _rehearsal():
    import importlib.util

    spec_ = importlib.util.spec_from_file_location("falcon_h1_rehearsal", os.path.join(BENCH, "tests", "test_falcon_h1_rehearsal.py"))
    module = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(module)
    return module


test_the_rehearsal_cell_runs_end_to_end_on_the_cpu = _rehearsal().test_the_rehearsal_cell_runs_end_to_end_on_the_cpu
test_the_control_lies_far_from_the_reference_and_the_program_on_it = _rehearsal().test_the_control_lies_far_from_the_reference_and_the_program_on_it
