"""The phi4_flash family's files through the benchmark's harness (sizes, the published configuration, what it
refuses, the counts on hand-worked sizes, the window's means, the cell), the preset a replica finds, and the
family's rehearsal end to end on the CPU. The program against its reference is tests/test_phi4_flash.py; the
kernels' forms tests/test_selective_scan.py and tests/test_differential_attention.py.
"""
import json
import os
import re

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
TINY = "tiny-phi4-flash"
CONFIG, CELL = "phi-4-mini-flash", "phi-4-mini-flash.serve_manyshot"


def test_a_replica_finds_the_familys_presets():
    from tony_tpu.models import phi4_flash, registry

    presets = registry.presets()
    assert presets["phi4-flash-tiny"] is phi4_flash.PRESETS["phi4-flash-tiny"]
    assert registry.module_of(presets["phi4-flash-tiny"]) is phi4_flash and {"tiny", "falcon-h1-tiny", "exaone-moe-tiny"} <= set(presets)
    params = jax.eval_shape(lambda: phi4_flash.init(jax.random.PRNGKey(0), phi4_flash.PHI4_FLASH_TINY))
    assert set(params) == {"embed", "trunk", "memory", "full", "cross", "final_norm", "final_norm_b"} and "lm_head" not in params      # tied
    assert params["trunk"]["mamba"]["w_in"].shape == (1, 64, 256) and params["cross"]["cross"]["w_q"].shape == (2, 64, 64)
    assert "w_qkv" not in params["cross"]["cross"] and params["cross"]["gmu"]["w_g"].shape == (2, 64, 128)                            # a query and no key
    assert params["full"]["w_qkv"].shape == (64, (8 + 2 * 4) * 8) and params["memory"]["A_log"].shape == (128, 16)


# -- the family's files through the harness ---------------------------------------------------------
def test_the_family_resolves_sizes_program_reference_and_counts(bench):
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config(CONFIG), "serve-1chip")
    assert (sizes["layers"], sizes["memory_layer"], sizes["vocab"], sizes["d_model"], sizes["d_ff"]) == (32, 16, 200_064, 2560, 10_240)
    assert (sizes["d_inner"], sizes["ssm_state"], sizes["conv_taps"], sizes["dt_rank"]) == (5120, 16, 4, 160)
    assert (sizes["heads"], sizes["kv_heads"], sizes["head_dim"], sizes["window"]) == (40, 20, 64, 512)
    hash(tuple(sorted(sizes.items())))                               # check.py keys its traced programs by the sizes
    module, cfg = families.load("phi4_flash").program(sizes, 36_864)
    assert module.__name__ == "tony_tpu.models.phi4_flash" and cfg.periods == (8, 7) and cfg.pairs == 10 and cfg.pair_dim == 128
    assert cfg.page_len == 256 and cfg.dtype == "bfloat16"
    assert families.reference(sizes).__name__ == "families.phi4_flash_reference" and families.reference(sizes).CONTROL == "fp8"
    assert families.counts(sizes).__name__ == "families.phi4_flash_counts"


def test_the_configuration_holds_every_published_key_at_its_published_value(bench):
    """Against the catalog's row where the catalog is installed: nothing is cut."""
    cfg = bench["spec"].config(CONFIG)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("the catalog of architectures is not installed here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Phi-4-mini-flash-reasoning")
    assert cfg["source"] == row["source_url"] and not cfg["reduced"]
    for key, value in row["config"].items():
        assert cfg[key] == value, key
    assert "all 32 layers" in cfg["deployments"]["serve-1chip"] and "200,064" in cfg["deployments"]["serve-1chip"]
    assert all(set(entry) == {"value", "why"} and len(entry["why"]) > 40 for entry in cfg["assumed"].values())
    tiny = bench["spec"].config(TINY)
    assert {k: v["value"] for k, v in tiny["assumed"].items() if isinstance(v["value"], str)} == {
        k: v["value"] for k, v in cfg["assumed"].items() if isinstance(v["value"], str)}                     # the same choices at the tiny size


@pytest.mark.parametrize("change,error,match", [
    ({"mb_per_layer": 4}, ValueError, "mb_per_layer"),
    ({"tie_word_embeddings": False}, ValueError, "tie_word_embeddings"),
    ({"mlp_bias": True}, ValueError, "mlp_bias"),
    ({"rope_theta": 10000.0}, KeyError, "does not know"),
    ({"num_attention_heads": 32}, ValueError, "head_dim"),
    ({"num_hidden_layers": 19}, ValueError, "memory_layer"),
    ({"assumed": {}}, KeyError, "assumed"),
], ids=["a-mamba-every-fourth-layer", "an-untied-head", "a-bias-in-the-ffn", "a-position-term", "heads-that-do-not-give-the-width",
        "an-odd-depth", "nothing-assumed"])
def test_the_family_refuses_what_it_does_not_know(bench, change, error, match):
    cfg = {**bench["spec"].config(CONFIG), **change}
    with pytest.raises(error, match=match):
        bench["spec"].model_sizes(cfg, "serve-1chip")


@pytest.mark.parametrize("choice", ["mamba_biases", "layer_kinds", "position_term", "differential_form", "norm", "attention_biases", "window_edge",
                                    "state_dtype", "seeded_draws"])
def test_an_assumed_choice_is_one_value(bench, choice):
    cfg = bench["spec"].config(CONFIG)
    cfg = {**cfg, "assumed": {**cfg["assumed"], choice: {"value": "another", "why": "a guess"}}}
    with pytest.raises(ValueError, match=choice):
        bench["spec"].model_sizes(cfg, "serve-1chip")


@pytest.mark.parametrize("size", ["head_dim", "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank", "memory_layer"])
def test_an_assumed_size_has_its_why(bench, size):
    cfg = bench["spec"].config(CONFIG)
    cfg = {**cfg, "assumed": {**cfg["assumed"], size: {"value": cfg["assumed"][size]["value"]}}}
    with pytest.raises(KeyError, match=size):
        bench["spec"].model_sizes(cfg, "serve-1chip")


def test_a_checkout_without_the_model_module_has_no_such_family(bench, monkeypatch):
    """The benchmark's files laid over a parent commit: run.py's own process says
    so and exits 2 before any launch."""
    fam = bench["families"].load("phi4_flash")
    monkeypatch.setattr(fam, "PROGRAM", os.path.join(ROOT, "tony_tpu", "models", "no_such_model.py"))
    with pytest.raises(bench["families"].NoFamily, match="from the commit"):
        bench["spec"].model_sizes(bench["spec"].config(CONFIG), "serve-1chip")


def test_the_counts_on_hand_worked_sizes(bench):
    spec, families = bench["spec"], bench["families"]
    sizes = spec.model_sizes(spec.config(CONFIG), "serve-1chip")
    C, engine = families.counts(sizes), spec.workload(CELL)["engine"]
    assert C.layers_of(sizes) == {"mamba": 9, "window": 8, "full": 1, "gmu": 7, "cross": 7} and C.readers(sizes) == 8
    ffn = 3 * 2560 * 10_240
    assert C.layer_params(sizes) == {"mamba": 2560 * 10_240 + 5120 * 192 + 160 * 5120 + 5120 * 2560 + 4 * 5120 + 5120 * 16 + ffn,
                                     "window": 2560 * 5120 + 2560 * 2560 + ffn, "full": 2560 * 5120 + 2560 * 2560 + ffn,
                                     "gmu": 2 * 2560 * 5120 + ffn, "cross": 2 * 2560 * 2560 + ffn}
    assert C.total_params(sizes) == 3_851_980_800                                                                     # the name's 3.8 B, 7.70 GB
    assert C.position_bytes(sizes) == 5120 and C.state_bytes(sizes) == 327_680 and C.step_ops(sizes) == 6 * 5120 * 16 and C.pair_ops(sizes) == 6 * 40 * 64
    means = {"live_slots": 15.0, "context_per_slot": 15_000.0, "prefill_rows_per_chunk": 2048.0, "prefill_pairs_per_chunk": 2048 * 4096 + 2048 * 2049 // 2}
    assert C.diff_decode_call(sizes, engine, means) == (15_360 * 15 * 15_000 * 8, 5120 * 15 * 15_000 * 8)            # 9.2 GB a step: the ONE pool, eight times
    assert C.diff_ring_call(sizes, engine, means) == (15_360 * 15 * 512 * 8, 5120 * 15 * 512 * 8)                    # the window, not the context
    assert C.scan_decode_call(sizes, engine, means) == (6 * 5120 * 16 * 15 * 9, 2 * 327_680 * 15 * 9)
    ops, nbytes = C.scan_prefill_call(sizes, engine, means)
    assert ops == 6 * 5120 * 16 * 2048 * 9 and nbytes == 9 * (2 * 2048 * (3 * 5120 + 32) + 2 * 327_680)
    ops, nbytes = C.diff_prefill_call(sizes, engine, means)
    assert ops == 15_360 * means["prefill_pairs_per_chunk"] and nbytes == 5120 * (means["prefill_pairs_per_chunk"] / 2048 + 1024) + 2 * 2560 * 3 * 2048
    assert re.search(C.diff_decode_operands(sizes, engine), "bf16[1,2305,10,256,128]{4,3,2,1,0}") and not re.search(C.diff_decode_operands(sizes, engine), "bf16[1,1,10,36864,128]")
    assert re.search(C.diff_ring_operands(sizes, engine), "bf16[8,16,10,528,128]{4,3,2,1,0}") and not re.search(C.diff_ring_operands(sizes, engine), "bf16[1,2305,10,256,128]")
    assert re.search(C.diff_prefill_operands(sizes, engine), "bf16[1,1,10,36864,128]") and not re.search(C.diff_prefill_operands(sizes, engine), "bf16[1,2305,10,256,128]")
    assert re.search(C.scan_decode_operands(sizes, engine), "(f32[16,1,5120]{2,1,0}, f32[16,16,5120]{2,1,0:T(8,128)}) custom-call")
    assert re.search(C.scan_prefill_operands(sizes, engine), "(f32[2048,5120]{1,0}, f32[16,5120]{1,0:T(8,128)}) custom-call")
    assert not re.search(C.scan_prefill_operands(sizes, engine), "f32[16,16,5120]")                                   # not the slots' state
    assert C.diff_decode_calls(sizes, engine) == C.diff_ring_calls(sizes, engine) == C.scan_decode_calls(sizes, engine) == ("decode_steps", 8)
    assert C.diff_prefill_calls(sizes, engine) == C.scan_prefill_calls(sizes, engine) == ("prefill_chunk", 1)


def test_window_means_from_the_replicas_counters(bench):
    sizes = bench["spec"].model_sizes(bench["spec"].config(CONFIG), "serve-1chip")
    C = bench["families"].counts(sizes)
    deltas = {"tony_serve_engine_chunks_total": 10, "tony_serve_decode_slots_total": 150, "tony_serve_context_tokens_total": 150 * 8 * 15_000,
              "tony_serve_prefill_tokens_total": 7 * 2048, "tony_serve_prefill_chunks_total": 7, "tony_serve_prefill_pairs_total": 7 * 9_000_000}
    means = C.window_means(lambda name, where=None: deltas.get(name), {"decode_chunk": 8})
    assert means == {"live_slots": 15.0, "context_per_slot": 15_000.0, "prefill_rows_per_chunk": 2048.0, "prefill_pairs_per_chunk": 9_000_000.0}
    assert C.window_means(lambda name, where=None: None, {}) is None                   # a program without the counters


def test_the_cell_is_the_issues(bench):
    spec = bench["spec"]
    w, b = spec.workload(CELL), spec.benchmark()
    t, e = w["traffic"], w["engine"]
    assert w["kind"] == "serve" and w["deployment"] == "serve-1chip" and w["chips"] == 1 and w["config"] == CONFIG
    assert t["arrivals"]["process"] == "closed" and t["arrivals"]["clients"] == e["slots"] and 12 <= e["slots"] <= 16  # callers = slots, the most that fit
    assert "sessions" not in t and "prefix" not in t and t["draw_seed"] == 63
    assert t["prompt_len"] == {"dist": "lognormal", "median": 12_288, "sigma": 0.6, "min": 2048, "max": 32_768}
    assert t["answer_len"] == {"dist": "lognormal", "median": 1024, "sigma": 0.4, "min": 384, "max": 2048}
    assert {k: e[k] for k in ("max_len", "page_len", "prefill_chunk", "decode_chunk")} == {"max_len": 36_864, "page_len": 256, "prefill_chunk": 2048, "decode_chunk": 8}
    assert e["num_pages"] == e["slots"] * (e["max_len"] // e["page_len"]) + 1 and 32_768 + 2048 <= e["max_len"]      # every slot's pages; the longest request fits
    assert w["check"]["samples"] in (1, 2, 4) and 0 < w["check"]["worst_gap_limit"] and "control" in w["check"]["why"]
    listed = {m["name"] for m in spec.cell_metrics(b, CELL, "per_layer")}
    rooflines = {"diff_decode_roofline_pct.serve": ("diff_decode", "tpu_custom_call"), "diff_ring_roofline_pct.serve": ("diff_ring", "ring_decode_attention"),
                 "diff_prefill_roofline_pct.serve": ("diff_prefill", "chunk_prefill_attention"), "scan_decode_roofline_pct.serve": ("scan_decode", "selective_step"),
                 "scan_prefill_roofline_pct.serve": ("scan_prefill", "selective_chunk")}
    assert set(rooflines) | {"cross_rows_pct.serve", "launch_s", "decode_step_ms.serve_tput", "slots_active_mean.serve", "host_gap_pct.serve_tput", "weights_s"} <= listed
    assert not {m for m in listed if m.startswith(("moe_", "held_share", "expert_rows", "prefix_hit", "delta_", "kda_", "ssd_", "attn_"))}
    assert {m["name"] for m in spec.cell_metrics(b, CELL, "end_to_end")} == {"serve_out_tok_s", "setup_s"}
    for name, (kernel, match) in rooflines.items():
        m = next(m for m in b["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and spec.metric(name)["reader"] == "family_roofline" and spec.metric(name)["args"] == {"kernel": kernel, "match": match}
    cross = spec.metric("cross_rows_pct.serve")
    assert cross["reader"] == "registry_delta" and cross["args"]["num"] == {"name": "tony_serve_cross_rows_total"} and cross["args"]["den"] == {"name": "tony_serve_prefill_tokens_total"}
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [] and entry["source"] == spec.config(CONFIG)["source"] and entry["file"] == "benchmark/configs/phi-4-mini-flash.json"
    assert len(b["workloads"]) >= 13 and sum(c["chips"] == 4 for c in b["workloads"]) == 1
    assert b["workloads"][12]["name"] == CELL and b["configs"][9]["name"] == CONFIG                                    # appended behind the twelve cells of nine configurations


# -- the family's rehearsal (benchmark/tests/test_phi4_flash_rehearsal.py), run with the suite
def _rehearsal():
    import importlib.util

    spec_ = importlib.util.spec_from_file_location("phi4_flash_rehearsal", os.path.join(BENCH, "tests", "test_phi4_flash_rehearsal.py"))
    module = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(module)
    return module


test_the_rehearsal_cell_runs_end_to_end_on_the_cpu = _rehearsal().test_the_rehearsal_cell_runs_end_to_end_on_the_cpu
test_the_control_lies_far_from_the_reference_and_the_program_on_it = _rehearsal().test_the_control_lies_far_from_the_reference_and_the_program_on_it
