"""Tests of the benchmark's own arithmetic and data files.

Pure functions and hand-built fixtures: no job or server is started (the only
subprocesses are the harness's own files run from a temporary copy of
benchmark/), nothing compiles at real width, and no TPU library is touched at
import. Run with `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`.
"""

import importlib
import json
import math
import os
import re
import shutil
import subprocess
import sys
import time
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.dirname(BENCH))

import counts  # noqa: E402
import families  # noqa: E402
import reduce as R  # noqa: E402
import serve_cell  # noqa: E402
import spec  # noqa: E402
import traffic as T  # noqa: E402
from families import llama_counts  # noqa: E402

B = spec.benchmark()
CELLS = [w["name"] for w in B["workloads"]]
WORKLOAD_FILES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "workloads")) if f.endswith(".json"))
METRIC_FILES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "metrics")) if f.endswith(".json"))
CONFIG_FILES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "configs")) if f.endswith(".json"))
WIDTH = ("hidden_size", "intermediate_size", "head_dim", "num_experts_per_tok", "num_attention_heads",
         "num_key_value_heads", "sliding_window")


# -- the contract's shape -----------------------------------------------------
def test_benchmark_json_has_exactly_the_contracts_keys():
    assert set(B) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= B["run_seconds"] <= 51 and isinstance(B["run_seconds"], int)
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) <= 64 * 1024
    runs = 2 + 14 * 24
    assert runs * (B["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200, "run_seconds must fit with 24 cells"


def test_names_and_units_hold_to_the_allowed_characters():
    names = [e["name"] for s in ("configs", "workloads", "end_to_end", "per_layer") for e in B[s]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in B["workloads"]] + [k for c in B["configs"] for k in c["reduced"]]:
        assert spec.NAME_RE.match(n), n
    for m in B["end_to_end"] + B["per_layer"]:
        assert spec.UNIT_RE.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in B["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in B["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    for e in B["workloads"]:
        assert set(e) == {"name", "config", "traffic", "chips", "why"} and len(e["why"]) <= 200
        assert e["chips"] in (1, 4)
    assert sum(e["chips"] == 4 for e in B["workloads"]) <= max(1, len(B["workloads"]) // 4)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_cross_references(cell):
    entry = next(e for e in B["workloads"] if e["name"] == cell)
    assert cell == f"{entry['config']}.{entry['traffic']}"
    w = spec.workload(cell)
    assert w["kind"] in ("train", "serve") and w["chips"] == entry["chips"]
    cfg = spec.config(entry["config"])
    sizes = spec.model_sizes(cfg, w["deployment"])
    assert sizes["layers"] >= 1 and w["deployment"] in cfg["deployments"]
    importlib.import_module(w["kind"] + "_cell")
    e2e = spec.cell_metrics(B, cell, "end_to_end")
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert spec.cell_metrics(B, cell, "per_layer")
    for m in spec.cell_metrics(B, cell, "per_layer"):
        assert m["moves"] in {x["name"] for x in e2e}, (cell, m["name"], "moves a metric the cell does not report")


@pytest.mark.parametrize("name", [c["name"] for c in B["configs"]])
def test_configuration_names_source_and_cuts_no_width(name):
    entry = next(c for c in B["configs"] if c["name"] == name)
    assert any(w["config"] == name for w in B["workloads"])
    assert entry["file"] == f"benchmark/configs/{name}.json"
    cfg = spec.config(name)
    assert cfg["source"] == entry["source"] and cfg["source"].startswith("https://")
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    assert not set(entry["reduced"]) & set(WIDTH) and not any(k.endswith(("_dim", "_rank")) for k in entry["reduced"])
    assert callable(families.load(cfg["module"]).sizes) and "assumed" in cfg
    for family in ("llama", "gemma", "gpt-oss", "qwen3.5"):
        assert family not in cfg["source"].lower()


def test_published_sizes_of_the_source():
    m = spec.config("mistral-7b")
    assert (m["hidden_size"], m["intermediate_size"], m["num_attention_heads"], m["num_key_value_heads"],
            m["vocab_size"], m["sliding_window"], m["rope_theta"], m["num_hidden_layers"]["source"]) == (
        4096, 14336, 32, 8, 32000, 4096, 10000.0, 32)


@pytest.mark.parametrize("name", WORKLOAD_FILES)
def test_every_workload_file_loads(name):
    w = spec.workload(name)
    spec.model_sizes(spec.config(w["config"]), w["deployment"])
    if w["kind"] == "serve":
        plan = T.plan(w["traffic"], 1, 5.0, 1000)
        assert plan and all(1 <= len(p.prompt) <= w["engine"]["max_len"] for p in plan)
        assert all(len(p.prompt) + p.max_tokens <= w["engine"]["max_len"] for p in plan)
    else:
        assert w["data"]["tokens"] > w["batch_size"] * (w["seq_len"] + 1)


@pytest.mark.parametrize("name", METRIC_FILES)
def test_every_metric_file_names_a_reader(name):
    m = spec.metric(name)
    assert spec.NAME_RE.match(name) and spec.UNIT_RE.match(m["unit"]) and m["what"]
    assert callable(importlib.import_module("readers." + m["reader"]).read)
    listed = [x for x in B["per_layer"] if x["name"] == name]
    for x in listed:
        assert (x["unit"], x["better"], x["layer"], x["moves"]) == (m["unit"], m["better"], m["layer"], m["moves"])


def test_a_reader_with_nothing_to_read_returns_nothing():
    ctx = {"run": None, "device": {"platform": "cpu"}, "trace": None, "tok_s_chip": None,
           "drive": {"snap0": None, "snap1": None, "records": [], "stats": [], "t_open": 0, "t_close": 1}}
    assert importlib.import_module("readers.mfu").read(ctx) is None
    assert importlib.import_module("readers.kernel_roofline").read(ctx, kernel="flash", match="flash") is None
    assert importlib.import_module("readers.module_time").read(ctx, match="x") is None
    assert importlib.import_module("readers.stats_mean").read(ctx, field="slots_active") is None
    assert importlib.import_module("readers.router_added").read(ctx, histogram="h") is None


# -- counts -------------------------------------------------------------------
MISTRAL = spec.model_sizes(spec.config("mistral-7b"), "train-1chip")
# Mixtral-8x7B's published widths (no cell yet: its fused MoE kernel does not fit the chip's VMEM)
MIXTRAL = {**MISTRAL, "module": "mixtral", "layers": 2, "experts": 8, "top_k": 2, "window": 0, "rope_theta": 1e6}


def test_mistral_layer_is_218_112_000_parameters():
    assert llama_counts.layer_params(MISTRAL) == 218_112_000
    assert llama_counts.layer_matmul_params(MISTRAL) == 218_112_000 - 2 * 4096
    assert llama_counts.total_params(MISTRAL) == 4 * 218_112_000 + 2 * 32000 * 4096 + 4096


def test_mixtral_counts_active_experts_only():
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024
    assert llama_counts.layer_params(MIXTRAL) == attn + 8 * 3 * 4096 * 14336 + 4096 * 8 + 2 * 4096
    assert llama_counts.layer_matmul_params(MIXTRAL) == attn + 2 * 3 * 4096 * 14336 + 4096 * 8


@pytest.mark.parametrize("seq,window,pairs", [
    (4, 0, 10), (4, 2, 7), (4, 4, 10), (4, 9, 10), (8192, 4096, 4096 * 4097 // 2 + 4096 * 4096)])
def test_attention_pairs_inside_the_band(seq, window, pairs):
    assert counts.causal_pairs(seq, window) == pairs
    brute = sum(1 for i in range(min(seq, 64)) for j in range(i + 1) if window <= 0 or j > i - window)
    if seq <= 64:
        assert brute == pairs


def test_train_flops_per_token_by_hand():
    matmul = 2 * (4 * (218_112_000 - 8192) + 4096 * 32000)
    attn = 4 * 4 * 32 * 128 * counts.causal_pairs(8192, 4096) / 8192
    assert llama_counts.train_flops_per_token(MISTRAL, 8192) == pytest.approx(3 * (matmul + attn))
    ops, nbytes = llama_counts.flash_call(MISTRAL, 2, 8192, backward=False)
    assert ops == 4 * 32 * 128 * 2 * counts.causal_pairs(8192, 4096)
    assert nbytes == 2 * (2 * 2 * 8192 * 32 * 128) + 2 * (2 * 2 * 8192 * 8 * 128)
    peak = counts.peak_for("TPU v5 lite", spec.load_json("peaks.json"))
    assert counts.roofline_seconds(197e12, 0, peak) == pytest.approx(1.0)
    assert counts.roofline_seconds(0, 819e9, peak) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        counts.peak_for("TPU v9", spec.load_json("peaks.json"))


# -- traffic ------------------------------------------------------------------
CHAT = spec.workload("mistral-7b.serve_chat")["traffic"]


def test_traffic_repeats_for_a_seed_and_differs_across_seeds():
    a, b, c = (T.plan(CHAT, s, 30.0, 32000) for s in (7, 7, 2 ** 31 + 5))
    assert [(p.due_s, p.prompt, p.max_tokens) for p in a] == [(p.due_s, p.prompt, p.max_tokens) for p in b]
    assert [p.prompt[:4] for p in a] != [p.prompt[:4] for p in c]
    # the schedule is the file's: every seed sends the same sizes at the same times
    assert [(p.due_s, len(p.prompt), p.max_tokens) for p in a] == [(p.due_s, len(p.prompt), p.max_tokens) for p in c]


def test_arrivals_fill_the_window_at_the_files_rate():
    base = np.random.default_rng(CHAT["draw_seed"])
    gaps = T.draw_gaps(base, CHAT["arrivals"], 30.0)
    for seed in (1, 2, 3):
        due = [p.due_s for p in T.plan(CHAT, seed, 30.0, 32000)]
        assert all(0 <= x < 30.0 for x in due) and due == sorted(due)
        assert abs(len(due) - CHAT["arrivals"]["rate"] * 30.0) < 5 * math.sqrt(CHAT["arrivals"]["rate"] * 30.0)
    assert abs(gaps.mean() - 1 / CHAT["arrivals"]["rate"]) < 0.2 / CHAT["arrivals"]["rate"]


def test_lengths_hold_to_their_clips_and_bursty_keeps_the_mean_rate():
    rng = np.random.default_rng(0)
    x = T.draw_lengths(rng, CHAT["prompt_len"], 5000)
    assert x.min() >= 64 and x.max() <= 3072 and 400 < np.median(x) < 640
    assert set(T.draw_lengths(rng, {"dist": "fixed", "value": 128}, 9)) == {128}
    g = T.draw_gaps(rng, {"process": "bursty", "rate": 5.0, "burst_factor": 4, "burst_s": 1, "period_s": 10}, 400)
    assert 4.3 < len(g) / g.sum() < 5.7
    closed = T.plan({"arrivals": {"process": "closed", "clients": 2}, "prompt_len": {"dist": "fixed", "value": 8},
                     "answer_len": {"dist": "fixed", "value": 4}, "prefix": {"groups": 1, "tokens": 5}}, 3, 1.0, 100)
    assert len(closed) == 128 and all(p.due_s is None for p in closed)
    assert len({tuple(p.prompt[:5]) for p in closed}) == 1 and len({tuple(p.prompt) for p in closed}) > 1


def test_bursts_hold_their_share_of_the_arrivals():
    a = {"process": "bursty", "rate": 7.5, "burst_factor": 8, "burst_s": 1, "period_s": 10}
    t = np.cumsum(T.draw_gaps(np.random.default_rng(5), a, 2000))
    in_burst = (t % 10) < 1
    assert 0.76 < in_burst.mean() < 0.84            # 8 x rate for a tenth of the time
    assert 7.0 < len(t) / t[-1] < 8.0               # and the mean rate stays the file's
    plan = T.plan({"draw_seed": 2, "arrivals": a, "prompt_len": {"dist": "fixed", "value": 8},
                   "answer_len": {"dist": "fixed", "value": 4}}, 1, 40.0, 100)
    st = T.schedule_stats(plan)
    assert st["requests"] == len(plan) and st["prompt_tokens"] == 8 * len(plan) and st["most_arrivals_in_1s"] >= 30


def test_schedule_stats_by_hand():
    plan = [T.Planned(i, due, [1] * n, 5, "s") for i, (due, n) in enumerate([(0.0, 100), (0.5, 3000), (1.2, 50), (9.0, 2048)])]
    assert T.schedule_stats(plan) == {"requests": 4, "prompt_tokens": 5198, "answer_tokens": 20, "prompts_2048_up": 2,
                                      "most_arrivals_in_1s": 2, "most_prompt_tokens_in_2s": 3150}
    closed = [T.Planned(0, None, [1, 2], 5, "s")]
    assert "most_arrivals_in_1s" not in T.schedule_stats(closed)


def test_shared_prefixes_come_from_the_seed_and_sizes_from_the_file():
    mix = {"draw_seed": 4, "arrivals": {"process": "poisson", "rate": 20.0}, "prefix": {"groups": 3, "tokens": 16},
           "prompt_len": {"dist": "lognormal", "median": 64, "sigma": 0.5, "min": 24, "max": 256},
           "answer_len": {"dist": "fixed", "value": 4}}
    a, b = T.plan(mix, 1, 10.0, 1000), T.plan(mix, 2, 10.0, 1000)
    heads = {tuple(p.prompt[:16]) for p in a}
    assert len(heads) == 3 and heads.isdisjoint({tuple(p.prompt[:16]) for p in b})
    assert [len(p.prompt) for p in a] == [len(p.prompt) for p in b]  # the prefix is part of the length, not added
    assert len({tuple(p.prompt[16:]) for p in a}) == len(a)


def test_a_sessions_later_turns_resend_the_conversation(monkeypatch):
    mix = {"sessions": {"turns": 3, "turn_tokens": 4, "think_s": 0.01}}
    c = T.Client("http://127.0.0.1:1", mix, vocab=100, seed=9)
    sent = []

    def post(prompt, max_tokens, session, rec=None):
        sent.append((list(prompt), session))
        rec.arrivals.append((T.time.time(), max_tokens))
        return [7] * max_tokens

    monkeypatch.setattr(c, "post", post)
    c._conversation(T.Planned(0, 0.0, [1, 2, 3], 5, "s9-0"), due_t=T.time.time())
    assert [r.planned.turn for r in c.records] == [0, 1, 2] and {s for _, s in sent} == {"s9-0"}
    assert [len(p) for p, _ in sent] == [3, 3 + 5 + 4, 3 + 5 + 4 + 5 + 4]
    assert sent[1][0][:8] == [1, 2, 3] + [7] * 5 and sent[2][0][:12] == sent[1][0]
    # a later turn is due `think_s` after the reply, and timed from then
    assert c.records[1].due_t == pytest.approx(c.records[0].done_t + 0.01)
    assert all(r.ttft_s is not None and r.ttft_s >= 0 for r in c.records)
    # one turn where the mix has no sessions; a failed turn ends the conversation
    one = T.Client("http://127.0.0.1:1", {}, 100, 9)
    monkeypatch.setattr(one, "post", post)
    one._conversation(T.Planned(0, 0.0, [1], 2, "x"), T.time.time())
    assert len(one.records) == 1
    bad = T.Client("http://127.0.0.1:1", mix, 100, 9, timeout_s=0.2)
    bad._conversation(T.Planned(0, 0.0, [1], 2, "x"), T.time.time())
    assert len(bad.records) == 1 and bad.records[0].error


def test_due_time_lateness_and_gap_arithmetic():
    p = T.Planned(0, 1.5, [1, 2, 3], 8, "s")
    r = T.Sent(p, due_t=100.0, sent_t=100.25, arrivals=[(100.75, 1), (101.0, 8), (101.5, 8)])
    assert r.ttft_s == pytest.approx(0.75)  # from when it was due, not sent
    g = r.gaps_s()
    assert len(g) == 16 and g[0] == pytest.approx(0.25) and g[8] == pytest.approx(0.5) and g.count(0.0) == 14
    assert T.lateness([r]) == {"mean_ms": pytest.approx(250.0), "max_ms": pytest.approx(250.0)}
    assert T.Sent(p, due_t=1.0).ttft_s is None


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert T.percentile(xs, 95) == 95 and T.percentile(xs, 50) == 50 and T.percentile(xs, 100) == 100
    assert T.percentile([5.0], 95) == 5.0 and T.percentile([1, 2], 50) == 1
    gaps = [0.0] * 7 + [240.0]  # a chunk of 8: the p95 is the stall between chunks
    assert T.percentile(gaps * 10, 95) == 240.0


def test_prompt_buckets_to_warm():
    assert serve_cell.buckets(64, 3072) == [64, 128, 256, 512, 1024, 2048, 3072]
    assert serve_cell.buckets(128, 128) == [128] and serve_cell.buckets(16, 120) == [16, 32, 64, 120]


# -- trace reduction ----------------------------------------------------------
def test_interval_arithmetic():
    assert R.union([(0, 1), (0.5, 2), (3, 4), (4, 4)]) == [(0, 2), (3, 4)]
    assert R.total(R.union([(0, 1), (0.5, 2), (3, 4)])) == 3
    assert R.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [(0, 1), (2, 4), (6, 9)]
    assert R.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert R.covering([("outer", 0, 10), ("inner", 2, 3)], 2.5) == "inner"


def test_summarise_busy_idle_and_exposed_collectives():
    ops = {
        "/device:TPU:0": [("fusion.1", 0.0, 1.0), ("all-gather-start.3", 1.0, 1.5), ("fusion.2", 1.2, 2.0),
                          ("flash_fwd", 3.0, 4.0)],
        "/device:TPU:1": [("fusion.1", 0.0, 2.0), ("all-reduce.9", 2.0, 3.0), ("flash_fwd", 3.0, 4.0)],
        "/device:TPU:2": [],
    }
    host = [("train_loop", 0.0, 4.0), ("np.asarray", 2.1, 2.9)]
    s = R.summarise(ops, host, {"/device:TPU:0": [("jit_step(1)", 0.0, 2.0), ("jit_step(1)", 3.0, 4.0)]})
    assert s["planes"] == 2 and s["window_s"] == 4.0
    assert s["busy_s"] == pytest.approx((3.0 + 4.0) / 2)
    assert s["collective_s"] == pytest.approx((0.5 + 1.0) / 2)
    assert s["collective_exposed_s"] == pytest.approx((0.2 + 1.0) / 2)
    assert s["op_time_s"]["flash_fwd"] == pytest.approx(1.0) and s["op_count"]["flash_fwd"] == 1  # calls a chip
    assert s["breakdown"]["idle_gaps"][0] == ["np.asarray", pytest.approx(0.5)]
    assert len(s["breakdown"]["device_ops"]) <= 10 and s["modules"]["jit_step(1)"] == [2.0, 1.0]
    assert R.summarise({}, [])["busy_s"] == 0.0
    # chip 0's programs ran 0-2 and 3-4 and some operation ran in all of it; chips 1 and 2 have no programs' line
    assert s["module_cover"] == pytest.approx(1.0)
    # a thread asleep names no gap, however short its sleep (the replica's control threads poll by sleeping)
    s = R.summarise(ops, host + [("$time sleep", 2.45, 2.47)], None)
    assert s["breakdown"]["idle_gaps"][0] == ["np.asarray", pytest.approx(0.5)]


@pytest.mark.parametrize("kept,whole", [(1.0, True), (0.9, True), (0.6, False), (0.3, False)])
def test_a_capture_that_lost_its_operations_events_fails_the_run(monkeypatch, kept, whole):
    """Ten programs of 0.1 s, back to back operations in each; a capture that kept only `kept` of the
    operations' events (the programs' line whole) has shares that read 1 / kept too high: the run fails."""
    import jobs

    programs = [("jit_decode_steps(3)", 0.2 * i, 0.2 * i + 0.1) for i in range(10)]
    ops = [("fusion.1", s, s + 0.1 * kept) for _, s, _ in programs]
    summary = R.summarise({"/device:TPU:0": ops}, [], {"/device:TPU:0": programs})
    assert summary["module_cover"] == pytest.approx(kept) and summary["busy_s"] == pytest.approx(kept)
    monkeypatch.setattr(R, "reduce_in_child", lambda search_dir, work: summary)
    if whole:
        assert R.reduced_or_fail("x", "y") is summary
    else:
        with pytest.raises(jobs.JobFailed, match="the capture is not whole"):
            R.reduced_or_fail("x", "y")
    monkeypatch.setattr(R, "reduce_in_child", lambda search_dir, work: None)
    with pytest.raises(jobs.JobFailed, match="left no device trace"):
        R.reduced_or_fail("x", "y")


HLO_FUSION = ("%fusion.603 = (bf16[2,8192,14336]{2,1,0:T(8,128)(2,1)}, f32[2]{0:T(128)S(1)}) fusion("
              "bf16[4096,14336]{1,0:T(8,128)(2,1)} %all-gather.37, bf16[2,8192,4096]{2,1,0} %copy-done.3), kind=kOutput")
HLO_GATHER = "%all-gather.37 = bf16[4096,14336]{1,0:T(8,128)(2,1)} all-gather(bf16[1024,14336]{1,0} %param.4), dimensions={0}"
HLO_WHILE = "%while.12 = (s32[]{:T(128)}, bf16[2,8192,4096]{2,1,0:T(8,128)(2,1)}) while((s32[], bf16[2,8192,4096]) %tuple.9), body=%scan_body"
HLO_PALLAS = ("%shard_map.335 = (f32[16,8192,128]{2,1,0:T(8,128)}, f32[16,8192,128]{2,1,0:T(8,128)}) custom-call("
              "s32[864]{0:T(1024)S(1)} %copy-done.68), custom_call_target=\"tpu_custom_call\"")


@pytest.mark.parametrize("name,op,collective,container", [
    (HLO_FUSION, "fusion", False, False),      # reads %all-gather.37: still compute
    (HLO_GATHER, "all-gather", True, False),
    (HLO_WHILE, "while", False, True),
    (HLO_PALLAS, "custom-call", False, False),
    ("%all-gather-done.3 = bf16[8]{0} all-gather-done((bf16[2]{0}, bf16[8]{0}) %all-gather-start.3)", "all-gather-done", True, False),
    ("%collective-permute-done.1 = f32[4]{0:T(128)} collective-permute-done(f32[4]{0} %x)", "collective-permute-done", True, False),
    ("%reduce-scatter.2 = f32[4]{0} reduce-scatter(f32[16]{0} %y), dimensions={0}", "reduce-scatter", True, False),
    ("all-reduce.9", "all-reduce", True, False), ("%fusion.2", "fusion", False, False), ("while.1", "while", False, True),
    ("%conditional.4 = f32[] conditional(pred[] %p, f32[] %a, f32[] %b)", "conditional", False, True),
])
def test_an_event_is_classed_by_its_own_opcode(name, op, collective, container):
    assert R.opcode(name) == op
    assert R.is_collective(name) is collective and bool(R.CONTAINER.match(op)) is container


def test_a_fusion_around_nothing_but_a_collective_is_one():
    scatter = ("%fusion.545 = f32[1024,32000]{1,0:T(8,128)} fusion(f32[4096,32000]{1,0:T(8,128)} "
               "%convolution_bitcast_fusion.14), kind=kCustom, calls=%all-reduce-scatter.clone.clone")
    under = ("%fusion.611 = bf16[14336,2,1024]{2,0,1:T(8,128)(2,1)} fusion(bf16[2,8192,14336]{2,1,0} %fusion.603, "
             "bf16[4096]{0} %collective-permute-done.4), kind=kOutput, calls=%async_collective_fusion.7")
    assert R.opcode(scatter) == "fusion" and R.is_collective(scatter)
    assert not R.is_collective(under)  # compute that a collective runs under
    s = R.summarise({"/device:TPU:0": [(under, 0.0, 2.0), (scatter, 2.0, 3.0)]}, [])
    assert s["collective_exposed_s"] == pytest.approx(1.0) and s["busy_s"] == pytest.approx(3.0)


def test_a_scan_container_hides_no_collective_and_a_consumer_fusion_is_compute():
    # a layer scan: the `while` lies over its body on the same line. The
    # all-gather at 2-5 has nothing beside it, the fusion that reads it is compute
    ops = {"/device:TPU:0": [(HLO_WHILE, 0.0, 10.0), (HLO_FUSION, 0.0, 2.0), (HLO_GATHER, 2.0, 5.0),
                             (HLO_FUSION, 5.0, 6.0), (HLO_PALLAS, 8.0, 10.0)]}
    s = R.summarise(ops, [("host.wait", 6.0, 8.0)])
    assert s["window_s"] == 10.0 and s["busy_s"] == pytest.approx(8.0)  # 6-8 is idle though the while covers it
    assert s["collective_s"] == pytest.approx(3.0) and s["collective_exposed_s"] == pytest.approx(3.0)
    assert HLO_WHILE not in s["op_time_s"] and s["op_time_s"][HLO_FUSION] == pytest.approx(3.0)
    assert s["breakdown"]["idle_gaps"] == [["host.wait", pytest.approx(2.0)]]
    # an asynchronous collective that compute overlaps is exposed only where it stands alone
    ops = {"/device:TPU:0": [("while.1", 0.0, 4.0), ("all-gather-start.1", 0.0, 0.1), ("fusion.1", 0.1, 3.0),
                             ("all-gather-done.1", 3.0, 4.0)]}
    s = R.summarise(ops, [])
    assert s["collective_exposed_s"] == pytest.approx(1.1) and s["busy_s"] == pytest.approx(4.0)


# -- the reference and its control, at a size a test run can hold -------------
@pytest.mark.parametrize("name,deployment", [("tiny-dense", "train-1chip"), ("tiny-moe", "train-4chip")])
def test_reference_agrees_with_the_program_in_float32_and_the_control_does_not(name, deployment):
    import dataclasses

    import jax
    import jax.numpy as jnp

    import compare

    sizes = spec.model_sizes(spec.config(name), deployment)
    module, cfg = families.load(sizes["module"]).program(sizes, 128)
    reference = families.reference(sizes)
    for seed in (3, 2 ** 31 + 11, 77):
        params = reference.init_weights(reference.seed_key(seed), sizes)
        toks = jnp.asarray(compare.zipf_tokens(seed, 128, sizes["vocab"]))
        ref = reference.forward(params, toks, sizes, "f32", 64)
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        out = module.forward(p32, toks[None], dataclasses.replace(cfg, dtype="float32"))
        exact = compare.rel_rms((out[0] if isinstance(out, tuple) else out)[0], ref)
        out = module.forward(params, toks[None], cfg)
        sound = compare.rel_rms((out[0] if isinstance(out, tuple) else out)[0], ref)
        control = compare.rel_rms(reference.forward(params, toks, sizes, reference.CONTROL, 64), ref)
        assert exact < 1e-5, "the reference is the program's mathematics in float32"
        assert control > 2.5 * sound and control > 0.03, (sound, control)
        rows = ref[-8:]
        assert float(compare.chosen_gap(rows, rows.argmax(-1)).max()) == 0.0


def test_the_gradient_comparison_separates_the_program_from_its_control():
    import dataclasses

    import jax
    import jax.numpy as jnp

    import check

    sizes = spec.model_sizes(spec.config("tiny-dense"), "train-1chip")
    module, cfg = families.load(sizes["module"]).program(sizes, 128)
    reference = families.reference(sizes)
    sound_cmp = check.TrainComparison(module, cfg, None, sizes, 128)
    exact_cmp = check.TrainComparison(module, dataclasses.replace(cfg, dtype="float32"), None, sizes, 128)
    for seed in (5, 2 ** 31 + 12, 78):
        params = reference.init_weights(reference.seed_key(seed), sizes)
        r = sound_cmp.run(params, seed, rows=2, control=True, grad=True)
        assert r["control_grad_rel_rms"] > 3 * r["grad_rel_rms"] and r["control_grad_rel_rms"] > 0.03, r
        assert set(r["grad_rel_rms_by_leaf"]) == {"/".join(path) for path in reference.GRAD_LEAVES}
        assert r["grad_rel_rms"] == max(r["grad_rel_rms_by_leaf"].values())
        # the reference's gradient is the program's in float32, over both rows of the batch
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        assert exact_cmp.run(p32, seed, rows=2, grad=True)["grad_rel_rms"] < 1e-4


@pytest.mark.parametrize("exits, tries, result", [
    ((0,), 1, {"logit_rel_rms": 0.01}),
    ((1, 0), 2, {"logit_rel_rms": 0.01}),  # the chip refused the first child: the second reads the same seed
    ((1, 1, 1, 1), 4, {}),  # a comparison that never ran leaves the run not correct
])
def test_the_comparisons_child_is_started_again_after_a_failure(monkeypatch, tmp_path, exits, tries, result):
    import subprocess
    import types

    import jobs

    calls = []

    def fake_run(cmd, **kw):
        calls.append(cmd)
        rc = exits[len(calls) - 1]
        return subprocess.CompletedProcess(cmd, rc, stdout='noise\n{"logit_rel_rms": 0.01}\n' if rc == 0 else "",
                                           stderr="the TPU is in use")

    monkeypatch.setattr(jobs.subprocess, "run", fake_run)
    monkeypatch.setattr(jobs.time, "sleep", lambda s: None)
    run = types.SimpleNamespace(work=str(tmp_path), staging=str(tmp_path), seed=7, control=False,
                                w={"config": "tiny-dense", "deployment": "train-1chip"})
    got = jobs.compare_in_child(run, {"kind": "train"}, "train")
    assert len(calls) == tries == 1 + len(jobs.CHILD_RETRY_WAITS[:tries - 1])
    assert {k: v for k, v in got.items() if k not in ("seconds", "tries")} == result
    assert got.get("tries") == (tries if result else None)


# -- the family seam ------------------------------------------------------------
FAMILY_SIZES = {
    "llama": ("mistral-7b", "serve-1chip", {
        "module": "llama", "vocab": 32000, "d_model": 4096, "layers": 8, "heads": 32, "kv_heads": 8, "head_dim": 128,
        "d_ff": 14336, "rope_theta": 10000.0, "norm_eps": 1e-05, "window": 4096, "experts": 0, "top_k": 0,
        "dtype": "bfloat16"}),
    "mixtral": ("tiny-moe", "train-4chip", {
        "module": "mixtral", "vocab": 256, "d_model": 64, "layers": 2, "heads": 4, "kv_heads": 2, "head_dim": 16,
        "d_ff": 128, "rope_theta": 1000000.0, "norm_eps": 1e-05, "window": 0, "experts": 4, "top_k": 2,
        "dtype": "bfloat16"}),
}


@pytest.mark.parametrize("family", sorted(FAMILY_SIZES))
def test_a_family_answers_for_sizes_program_reference_and_counts(family):
    config, deployment, expected = FAMILY_SIZES[family]
    cfg = spec.config(config)
    assert cfg["module"] == family
    sizes = spec.model_sizes(cfg, deployment)
    assert sizes == expected and list(sizes) == list(expected)
    fam = families.load(family)
    # a key the family does not know is an error, and so is a value it does not compute
    with pytest.raises(KeyError, match="lightning_nh"):
        fam.sizes({**cfg, "lightning_nh": 32}, deployment)
    with pytest.raises(ValueError, match="hidden_act"):
        fam.sizes({**cfg, "hidden_act": "gelu"}, deployment)
    with pytest.raises(KeyError, match="no depth for deployment"):
        fam.sizes(cfg, "serve-9chip")
    module, pcfg = fam.program(sizes, 512)
    assert module.__name__ == "tony_tpu.models." + family and callable(module.forward) and callable(module.loss_fn)
    assert (pcfg.n_layers, pcfg.d_model, pcfg.max_seq, pcfg.sliding_window) == (
        sizes["layers"], sizes["d_model"], 512, sizes["window"])
    assert getattr(pcfg, "num_experts", 0) == sizes["experts"]
    ref = families.reference(sizes)
    assert all(callable(getattr(ref, f)) for f in ("seed_key", "init_weights", "forward", "nll"))
    assert ref.CONTROL != "f32" and all(isinstance(path, tuple) for path in ref.GRAD_LEAVES)
    own = families.counts(sizes)
    assert own.train_flops_per_token(sizes, 8192) > 6 * sizes["layers"] * own.layer_matmul_params(sizes)
    assert callable(fam.serve_install)


def test_a_family_that_cannot_be_served_says_so_itself():
    sizes = spec.model_sizes(spec.config("tiny-moe"), "train-4chip")
    with pytest.raises(SystemExit, match="a Mixtral replica waits for the program"):
        families.load("mixtral").serve_install(sizes, {"config": "tiny-moe", "seed": 1, "engine": {"max_len": 64}})


FLASH = ("%{name} = (bf16[64,8192,128]{{2,1,0}}, f32[64,8192,128]{{2,1,0}}) custom-call(bf16[64,8192,128]{{2,1,0}} %q), "
         "custom_call_target=\"tpu_custom_call\"")
OTHER_CALL = "%moe.2 = bf16[16384,14336]{1,0} custom-call(bf16[16384,4096]{1,0} %x), custom_call_target=\"tpu_custom_call\""


def _train_ctx(op_time_s: dict, op_count: dict) -> dict:
    run = types.SimpleNamespace(sizes=MISTRAL, w={"seq_len": 8192, "batch_size": 2}, chips=1, peaks=spec.load_json("peaks.json"))
    return {"run": run, "device": {"platform": "tpu", "kind": "TPU v5 lite"}, "trace": {"op_time_s": op_time_s, "op_count": op_count}}


def test_kernel_roofline_finds_its_count_by_the_kernel_argument():
    read = importlib.import_module("readers.kernel_roofline").read
    fwd_call, bwd_call = FLASH.format(name="flash_fwd.1"), FLASH.format(name="flash_bwd.11")
    ctx = _train_ctx({fwd_call: 1.0, bwd_call: 2.0, OTHER_CALL: 5.0}, {fwd_call: 20, bwd_call: 20, OTHER_CALL: 20})
    peak = counts.peak_for("TPU v5 lite", ctx["run"].peaks)
    fwd, bwd = (counts.roofline_seconds(*llama_counts.flash_call(MISTRAL, 2, 8192, b), peak) for b in (False, True))
    # what the step runs since PR 52: the forward once and one backward, 20 layer-steps of them in 3 s
    assert read(ctx, kernel="flash", match="tpu_custom_call") == pytest.approx(100.0 * (fwd + bwd) * 20 / 3.0)
    assert llama_counts.flash_layer_step(MISTRAL, 2, 8192) == [llama_counts.flash_call(MISTRAL, 2, 8192, b) for b in (False, True)]
    ctx["trace"] = {"op_time_s": {OTHER_CALL: 5.0}, "op_count": {OTHER_CALL: 20}}
    assert read(ctx, kernel="flash", match="tpu_custom_call") is None  # no call with flash's shape: nothing, never 0
    with pytest.raises(ValueError, match="llama_counts.py has no moe_gemm_operands"):
        read(ctx, kernel="moe_gemm", match="tpu_custom_call")


@pytest.mark.parametrize("layers,steps", [(4, 5), (16, 3), (4, 1), (1, 7)])
def test_a_rolled_and_an_unrolled_step_read_the_same_flash_share(layers, steps):
    """One instruction a kernel run layers x steps times (the layers in a loop), and an instruction a layer run
    steps times each (the layers unrolled): the same calls and the same device time, so the same share."""
    read = importlib.import_module("readers.kernel_roofline").read
    t_fwd, t_bwd = 0.0069, 0.0130  # device seconds a call
    rolled_names = {"flash_fwd.1": t_fwd, "flash_bwd.11": t_bwd}
    rolled = _train_ctx({FLASH.format(name=n): t * layers * steps for n, t in rolled_names.items()},
                        {FLASH.format(name=n): layers * steps for n in rolled_names})
    unrolled_names = {f"flash_{kind}.{i}": t for i in range(layers) for kind, t in (("fwd", t_fwd), ("bwd", t_bwd))}
    unrolled = _train_ctx({FLASH.format(name=n): t * steps for n, t in unrolled_names.items()},
                          {FLASH.format(name=n): steps for n in unrolled_names})
    a, b = (read(ctx, kernel="flash", match="tpu_custom_call") for ctx in (rolled, unrolled))
    peak = counts.peak_for("TPU v5 lite", rolled["run"].peaks)
    least = sum(counts.roofline_seconds(*c, peak) for c in llama_counts.flash_layer_step(MISTRAL, 2, 8192))
    assert a == pytest.approx(b, rel=1e-12) and a == pytest.approx(100.0 * least / (t_fwd + t_bwd))
    assert 0 < a < 100


# -- the routed FFN's count, once for the five routed families ---------------------------------------
ROUTED_CELLS = ["k-exaone-236b.serve_reason", "dots3-note-prev.serve_notes", "mistral-small-4-119b.serve_docqa",
                "granite-4.0-h-small.serve_assist", "solar-open2-250b.serve_extract"]


def _cell(cell: str):
    w = spec.workload(cell)
    sizes = spec.model_sizes(spec.config(w["config"]), w["deployment"])
    return w, sizes, families.counts(sizes)


@pytest.mark.parametrize("cell", ROUTED_CELLS)
def test_the_routed_decode_count_is_one_and_follows_the_slabs_the_program_read(cell):
    from families import exaone_moe_counts as E

    w, s, C = _cell(cell)
    assert C.moe_decode_call is E.moe_decode_call and C.moe_prefill_call is E.moe_prefill_call   # one count, five families
    layers, held, slab = E.n_routed(s), s["held"][1], 3 * s["d_model"] * s["d_expert"]
    assert layers == s["layers"] - s.get("dense_layers", 0) and slab == E.expert_params(s)
    means = {"live_slots": 20.0, "held_rows_per_step": 100.0, "prefill_rows_per_chunk": 512.0}
    # with the counter: the slabs the program counted, 60% of the held ones here, whatever even routing would say
    ops, nbytes = C.moe_decode_call(s, w["engine"], {**means, "touched_per_step": 0.6 * layers * held})
    assert ops == 2 * slab * 100 and nbytes == pytest.approx(2 * (0.6 * layers * held * slab + 2 * 100 * s["d_model"]))
    assert C.moe_decode_call(s, w["engine"], {**means, "touched_per_step": 0.3 * layers * held})[1] < 0.51 * nbytes
    # without it: the expectation under even routing
    expected = layers * held * (1 - (1 - s["top_k"] / s["num_experts"]) ** 20)
    assert C.moe_decode_call(s, w["engine"], means)[1] == pytest.approx(2 * (expected * slab + 2 * 100 * s["d_model"]))


@pytest.mark.parametrize("cell", ROUTED_CELLS)
@pytest.mark.parametrize("counter", [True, False])
def test_every_routed_familys_window_means_carry_the_touched_slabs_when_the_program_counts_them(cell, counter):
    w, s, C = _cell(cell)
    moved = {"tony_serve_engine_chunks_total": 10, "tony_serve_decode_slots_total": 200, "tony_serve_expert_rows_total": 10 * 8 * 100,
             "tony_serve_experts_touched_total": 10 * 8 * 55, "tony_serve_prefill_tokens_total": 5120, "tony_serve_prefill_chunks_total": 10,
             "tony_serve_visible_tokens_total": 200 * 8 * 300, "tony_serve_context_tokens_total": 200 * 8 * 900,
             "tony_serve_prefill_pairs_total": 10 * 1000, "tony_serve_index_positions_total": 80}
    if not counter:
        del moved["tony_serve_experts_touched_total"]
    means = C.window_means(lambda name, where=None: moved.get(name), w["engine"])
    assert means["live_slots"] == 20.0 and means["held_rows_per_step"] == 100.0 and means["prefill_rows_per_chunk"] == 512.0
    assert means.get("touched_per_step") == (55.0 if counter else None)
    assert C.window_means(lambda name, where=None: None, w["engine"]) is None


@pytest.mark.parametrize("cell", ROUTED_CELLS)
def test_the_fullest_experts_rows_over_the_mean_read_1_under_even_routing_whatever_is_held(cell):
    """16, 32, 32, 36 and 40 held experts: every held expert gets r rows a layer and step, so the fullest
    gets r and the ratio is 1. (A fixed scale of 16 read 36 held experts' 1.0 as 0.44.)"""
    read = importlib.import_module("readers.registry_delta").read
    w, s, _ = _cell(cell)
    held, layer_steps, r = s["held"][1], 4 * 80, 7

    def snap(n):
        totals = {"tony_serve_expert_rows_max_total": n * layer_steps * r, "tony_serve_expert_rows_total": n * layer_steps * r * held}
        return {"metrics": [{"name": k, "samples": [{"labels": {}, "value": v}]} for k, v in totals.items()]}

    ctx = {"run": types.SimpleNamespace(sizes=s), "drive": {"snap0": snap(1), "snap1": snap(3)}}
    args = spec.metric("expert_rows_max_over_mean.serve")["args"]
    assert held in (16, 32, 36, 40) and read(ctx, **args) == pytest.approx(1.0)
    # a straggler with three times the mean reads 3
    ctx["drive"]["snap1"]["metrics"][0]["samples"][0]["value"] = layer_steps * r * (1 + 2 * 3)
    assert read(ctx, **args) == pytest.approx(3.0)
    # a family that holds no experts has nothing to read (a rehearsal scans every metric of its kind)
    assert read({**ctx, "run": types.SimpleNamespace(sizes=MISTRAL)}, **args) is None


# -- the seed orders a routed cell's work and does not size it ------------------------------------------
@pytest.mark.parametrize("experts,held", [(128, 16), (8, 4), (4, 4)])
@pytest.mark.parametrize("seed", [7, 6400000211, 2 ** 31 + 5])
def test_every_seed_gives_every_chip_the_same_choosing_biases_in_another_order(experts, held, seed):
    """The slabs a decode step of `serve_reason` reads follow the held experts' biases (12.2 to 14.9 a layer
    by the seed while the 16 were drawn freely, and `serve_out_tok_s` with them: PERF.md section 6, PR 64)."""
    from families import exaone_moe_reference as X

    b = np.asarray(X.choosing_bias(X.seed_key(seed), 3, experts, held))
    assert b.shape == (3, experts) and b.dtype == np.float32 and (b != 0).all() and np.abs(b).max() < 0.2
    chips = np.sort(b.reshape(3, experts // held, held), axis=-1)
    assert (chips == chips[0, 0]).all() and len(set(chips[0, 0].tolist())) == held      # one set of values, all distinct
    assert chips[0, 0] == pytest.approx(-chips[0, 0][::-1], abs=1e-6)                    # and symmetric about nought
    other = np.asarray(X.choosing_bias(X.seed_key(seed + 1), 3, experts, held))
    assert (np.sort(other.reshape(chips.shape), axis=-1) == chips).all()
    if held == 16:  # four values fall into one order once in 24 draws
        assert (other != b).any() and (b[0] != b[1]).any() and (b[0, :held] != b[0, held:2 * held]).any()  # a seed's, a layer's, a chip's own order
    with pytest.raises(ValueError, match="do not divide"):
        X.choosing_bias(X.seed_key(seed), 3, experts + 1, held)


def test_the_exaone_weights_take_their_bias_from_the_fixed_set_and_every_other_leaf_from_the_seed():
    from families import exaone_moe_reference as X

    s = spec.model_sizes(spec.config("tiny-exaone-moe"), "serve-1chip")
    a, b = (X.init_weights(X.seed_key(seed), s) for seed in (11, 12))
    E, held = s["num_experts"], s["held"][1]
    for tree in (a["layers"], a["mtp"]["layers"]) if "mtp" in a else (a["layers"],):
        rb = np.asarray(tree["router_bias"])
        assert rb.shape[-1] == E and rb.dtype == np.float32
        assert (np.sort(rb.reshape(-1, held), axis=-1) == np.sort(rb.reshape(-1, held), axis=-1)[0]).all()
    assert (np.sort(np.asarray(a["layers"]["router_bias"]).reshape(-1, held)) == np.sort(np.asarray(b["layers"]["router_bias"]).reshape(-1, held))).all()
    assert (np.asarray(a["layers"]["router"]) != np.asarray(b["layers"]["router"])).any()


# -- the latent row: found at any laid-out width, counted by its numbers ------------------------------
@pytest.mark.parametrize("cell,kernel,numbers,shape", [
    ("mistral-small-4-119b.serve_docqa", "latent_rows_decode", 320, "bf16[5,577,1024,{w}]"),
    ("mistral-small-4-119b.serve_docqa", "latent_prefill", 320, "bf16[35840,{w}]"),
    ("dots3-note-prev.serve_notes", "latent_decode", 576, "bf16[1,24,2048,{w}]"),
    ("dots3-note-prev.serve_notes", "latent_ring_decode", 1088, "bf16[3,24,640,{w}]"),
    ("dots3-note-prev.serve_notes", "latent_prefill", 576, "bf16[67584,{w}]"),
    ("dots3-note-prev.serve_notes", "latent_prefill", 1088, "bf16[2560,{w}]"),
])
def test_a_latent_call_is_found_with_its_filling_and_without_and_counted_by_its_numbers(cell, kernel, numbers, shape):
    w, s, C = _cell(cell)
    pat = re.compile(getattr(C, kernel + "_operands")(s, w["engine"]))
    lanes = -(-numbers // 128) * 128
    for width in (numbers, (numbers + lanes) // 2, lanes):      # no filling, some, whole lanes (today's pools)
        assert pat.search(shape.format(w=width)), width
    for width in (numbers - 1, lanes + 1, lanes + 128):
        assert not pat.search(shape.format(w=width)), width
    assert spec.metric(f"{'latent_paged_decode' if kernel == 'latent_rows_decode' else kernel}_roofline_pct.serve")["args"]["kernel"] == kernel


def test_the_latent_decode_counts_bytes_are_the_numbers_at_either_width():
    """The least a step could read: 2 bytes a number of a row, 320 (mistral4), 576 and 1088 (dots3_note), once
    for all heads; the filling up to 384, 640 and 1152 lanes is the layout's cost and is in the device time only."""
    w, s, C = _cell("mistral-small-4-119b.serve_docqa")
    means = {"live_slots": 48.0, "context_per_slot": 33_000.0}
    rows = 48 * 33_000 * 5
    assert C.row(s) == 320 and C.latent_rows_decode_call(s, w["engine"], means) == (2.0 * 32 * rows * (320 + 256), 2.0 * 320 * rows)
    assert C.latent_rows_decode_call(s, w["engine"], means)[1] * 384 == C.latent_paged_decode_call(s, w["engine"], means)[1] * 320
    w, s, C = _cell("dots3-note-prev.serve_notes")
    assert (C.row(s, C.FULL), C.row(s, C.SLIDING)) == (576, 1088)
    means = {"live_slots": 24.0, "visible_per_slot": (2 * 2048 + 3 * 513) / 5, "context_per_slot": 40_000.0}
    assert C.latent_decode_call(s, w["engine"], means)[1] == pytest.approx(2 * 576 * 24 * 2048 * 2)
    assert C.latent_ring_decode_call(s, w["engine"], means)[1] == pytest.approx(2 * 1088 * 24 * 513 * 3)


# -- a traced run keeps its registry ---------------------------------------------------------------
def _fleet(tmp_path):
    run = types.SimpleNamespace(out_dir=str(tmp_path), staging=str(tmp_path / "staging"), w={"engine": {}})
    os.makedirs(tmp_path / "ctl")
    fleet = serve_cell.Fleet(run)
    fleet.out_path = str(tmp_path / "serve.out")
    open(fleet.out_path, "w").close()
    return fleet


@pytest.mark.parametrize("late_s", [0.0, 0.3])
def test_a_snapshot_that_comes_late_is_returned(tmp_path, late_s):
    """A fake control directory and no replica: the answer is written `late_s` after it was asked for, as a
    capture's export delays it, and is returned with the registry's own stamp."""
    import threading

    fleet = _fleet(tmp_path)
    answer = {"t": 123.5, "metrics": [{"name": "tony_serve_engine_chunks_total", "samples": [{"labels": {}, "value": 9.0}]}]}

    def replica():
        req = tmp_path / "ctl" / "snap.close.req"
        while not req.exists():
            time.sleep(0.005)
        time.sleep(late_s)
        os.remove(req)
        serve_cell.write_json(str(tmp_path / "ctl" / "snap.close.json"), answer)

    threading.Thread(target=replica, daemon=True).start()
    t0 = time.time()
    assert fleet.snapshot("close", wait_s=5.0) == answer and time.time() - t0 >= late_s


@pytest.mark.parametrize("asked_ago_s", [0.0, 10.0])
def test_a_snapshot_that_never_comes_fails_the_run(tmp_path, asked_ago_s):
    """No replica answers: the run fails (JobFailed: run.py exits 1 and prints no result line), where it returned
    None until PR 64 and thirteen metrics read nothing. The wait counts from the asking, not from the collecting."""
    import jobs

    fleet = _fleet(tmp_path)
    t0 = time.time()
    with pytest.raises(jobs.JobFailed, match="no registry snapshot 'close' within"):
        if asked_ago_s:
            fleet.ask_snapshot("close")
            fleet.snapshot("close", asked=time.time() - asked_ago_s, wait_s=asked_ago_s + 0.2)
        else:
            fleet.snapshot("close", wait_s=0.2)
    assert time.time() - t0 < 2.0 and os.path.exists(tmp_path / "ctl" / "snap.close.req")
    assert serve_cell.SNAPSHOT_WAIT_S >= 60  # as long as a capture's export is given to end (serve_cell.run)


def test_the_replica_answers_snapshots_on_a_thread_that_waits_for_no_capture(tmp_path):
    """entry/serve_replica.py: `snapshots` is a loop of its own, apart from the one that traces, and stamps the
    registry with the time it was read."""
    import threading

    sys.path.insert(0, os.path.join(BENCH, "entry"))
    try:
        replica = importlib.import_module("serve_replica")
    finally:
        sys.path.remove(os.path.join(BENCH, "entry"))
    ctl = tmp_path / "ctl"
    os.makedirs(ctl)
    threading.Thread(target=replica.snapshots, args=(str(ctl),), daemon=True).start()  # ends when the directory goes
    fleet = serve_cell.Fleet(types.SimpleNamespace(out_dir=str(tmp_path), w={"engine": {}}))
    t0 = time.time()
    for tag in ("open", "close"):
        got = fleet.snapshot(tag, wait_s=10.0)
        assert t0 <= got["t"] <= time.time() and isinstance(got["metrics"], list)
        assert not os.path.exists(ctl / f"snap.{tag}.req")
    assert "trace.req" not in replica.snapshots.__code__.co_consts  # the capture is the other loop's


def test_the_routers_added_time_ends_where_the_closing_snapshot_was_read():
    read = importlib.import_module("readers.router_added").read
    hist = lambda s, n: {"metrics": [{"name": "h", "samples": [{"labels": {}, "sum": s, "count": n}]}]}
    rec = lambda sent, first: types.SimpleNamespace(sent_t=sent, arrivals=[(first, 1)])
    records = [rec(1.0, 1.5), rec(2.0, 2.5), rec(39.8, 40.6)]            # the third's first token comes after the close
    d = {"snap0": hist(0.0, 0), "snap1": {**hist(0.8, 2), "t": 40.01}, "records": records, "t_close": 40.0}
    assert read({"drive": d}, histogram="h") == pytest.approx(1000 * (0.5 - 0.4))
    # a closing snapshot read late counted the third request's first token too, and so do the clients
    d["snap1"] = {**hist(1.6, 3), "t": 41.0}
    assert read({"drive": d}, histogram="h") == pytest.approx(1000 * ((0.5 + 0.5 + 0.8) / 3 - 1.6 / 3))


def _copy_of_the_benchmark(tmp_path) -> str:
    """BENCHMARK.json and benchmark/ as git would commit them, in a directory
    of their own beside a link to the program."""
    top = str(tmp_path / "checkout")
    shutil.copytree(BENCH, os.path.join(top, "benchmark"), ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), top)
    os.symlink(os.path.join(spec.ROOT, "tony_tpu"), os.path.join(top, "tony_tpu"))
    return top


def _python(top: str, *argv: str, timeout: float = 600.0) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": top, "PYTHONDONTWRITEBYTECODE": "1"}
    return subprocess.run([sys.executable, *argv], cwd=top, env=env, capture_output=True, text=True, timeout=timeout)


def _write_json(top: str, rel: str, obj: dict) -> None:
    with open(os.path.join(top, "benchmark", rel), "x") as f:
        json.dump(obj, f)


def _files(top: str) -> dict[str, bytes]:
    out = {}
    for r, dirs, fns in os.walk(top):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for fn in fns:
            with open(os.path.join(r, fn), "rb") as f:
                out[os.path.relpath(os.path.join(r, fn), top)] = f.read()
    return out


ECHO_FAMILY = '''"""A family added by files alone: the llama family's program, reference and
counts, and one key of its own that it reads from the configuration."""
from families import llama

REFERENCE, COUNTS = llama.REFERENCE, llama.COUNTS
program, serve_install = llama.program, llama.serve_install


def sizes(cfg, deployment):
    own = {k: v for k, v in cfg.items() if k != "echo_width"}
    return {**llama.sizes(own, deployment), "echo_width": int(cfg["echo_width"])}
'''

DRIVE_THE_ECHO_FAMILY = '''
import json, sys
sys.path.insert(0, "benchmark")
import jax, jax.numpy as jnp, numpy as np
import check, chipside, families, spec

out = {}
w = spec.workload("tiny-echo.train")
sizes = spec.model_sizes(spec.config(w["config"]), w["deployment"])
fam = families.load(sizes["module"])
module, cfg = fam.program(sizes, w["seq_len"])
out["sizes"], out["family"], out["program"] = sizes, fam.__file__, [module.__name__, cfg.n_layers, cfg.dtype]
out["reference"], out["counts"] = families.reference(sizes).__name__, families.counts(sizes).__name__
out["flops_per_token"] = families.counts(sizes).train_flops_per_token(sizes, w["seq_len"])
comparison, weights, rows = check.train_setup(
    w["config"], w["deployment"], ["--batch_size", str(w["batch_size"]), "--seq_len", str(w["seq_len"])])
out["train"] = comparison.run(weights(2 ** 31 + 5), 2 ** 31 + 5, rows, control=True, grad=True)

# serving: the reference's own greedy continuation is what a sound engine returns
w = spec.workload("tiny-echo.serve")
sizes = spec.model_sizes(spec.config(w["config"]), w["deployment"])
R, params = families.reference(sizes), chipside.seed_weights(sizes, 7)
step = jax.jit(lambda seq, n: R.forward(params, seq, sizes, "f32", 64)[n - 1].argmax())
prompt = np.random.default_rng(7).integers(1, sizes["vocab"], 20).tolist()
tokens = []
for _ in range(6):
    seq = np.zeros(64, np.int32)
    seq[:len(prompt) + len(tokens)] = prompt + tokens
    tokens.append(int(step(jnp.asarray(seq), len(prompt) + len(tokens))))
out["serve"] = check.check_serve(params, sizes, [{"prompt": prompt, "tokens": tokens}], control=True, pad_seq=512, pad_answer=8)
wrong = [(t + 1) % sizes["vocab"] for t in tokens]
out["serve_wrong"] = check.check_serve(params, sizes, [{"prompt": prompt, "tokens": wrong}], pad_seq=512, pad_answer=8)
print(json.dumps(out))
'''


def test_a_family_is_added_by_files_alone(tmp_path):
    """A family file, a configuration and two workloads written into a copy of
    benchmark/: sizes, program, reference and counts resolve through them, the
    training and the serving comparison run against them in float32, and no
    file that was there differs."""
    top = _copy_of_the_benchmark(tmp_path)
    before = _files(top)
    with open(os.path.join(top, "benchmark", "families", "echo.py"), "x") as f:
        f.write(ECHO_FAMILY)
    _write_json(top, "configs/tiny-echo.json",
                {**spec.config("tiny-dense"), "module": "echo", "echo_width": 7, "torch_dtype": "float32"})
    for traffic in ("train", "serve"):
        with open(os.path.join(BENCH, "workloads", f"tiny-dense.{traffic}.json")) as f:
            _write_json(top, f"workloads/tiny-echo.{traffic}.json", {**json.load(f), "config": "tiny-echo"})
    proc = _python(top, "-c", DRIVE_THE_ECHO_FAMILY)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sizes"]["echo_width"] == 7 and out["sizes"]["module"] == "echo" and out["sizes"]["dtype"] == "float32"
    assert out["family"] == os.path.join(top, "benchmark", "families", "echo.py")
    assert out["program"] == ["tony_tpu.models.llama", 2, "float32"]
    assert (out["reference"], out["counts"]) == ("families.llama_reference", "families.llama_counts")
    assert out["flops_per_token"] == llama_counts.train_flops_per_token(spec.model_sizes(spec.config("tiny-dense"), "train-1chip"), 128)
    train = out["train"]
    assert train["logit_rel_rms"] < 1e-5 and train["grad_rel_rms"] < 1e-4, train
    assert train["control_logit_rel_rms"] > 0.03 and train["control_grad_rel_rms"] > 0.03, train
    assert set(train["grad_rel_rms_by_leaf"]) == {"layers/wq", "layers/wk", "layers/wv"}
    assert out["serve"]["worst_gap"] < 1e-4 and out["serve"]["argmax_agree"] == out["serve"]["tokens"] == 6, out["serve"]
    assert out["serve_wrong"]["worst_gap"] > 0.01 and out["serve_wrong"]["argmax_agree"] < 6, out["serve_wrong"]
    after = _files(top)
    assert {k: after[k] for k in before} == before, "a file that was there was edited"
    assert sorted(set(after) - set(before)) == [
        "benchmark/configs/tiny-echo.json", "benchmark/families/echo.py",
        "benchmark/workloads/tiny-echo.serve.json", "benchmark/workloads/tiny-echo.train.json"]
    repo = {os.path.join("benchmark", k): v for k, v in _files(BENCH).items()}
    with open(os.path.join(spec.ROOT, "BENCHMARK.json"), "rb") as f:
        assert before == {**repo, "BENCHMARK.json": f.read()}, "the copy is the repo's"


def test_a_module_without_a_family_file_fails_before_any_job(tmp_path):
    top = _copy_of_the_benchmark(tmp_path)
    _write_json(top, "configs/tiny-sala.json", {**spec.config("tiny-dense"), "module": "sala"})
    with open(os.path.join(BENCH, "workloads", "tiny-dense.serve.json")) as f:
        _write_json(top, "workloads/tiny-sala.serve.json", {**json.load(f), "config": "tiny-sala"})
    proc = _python(top, "benchmark/run.py", "--workload", "tiny-sala.serve", "--seed", "1", "--seconds", "1", timeout=60)
    assert proc.returncode == 2 and not proc.stdout.strip()
    assert os.path.join(top, "benchmark", "families", "sala.py") in proc.stderr and "add it" in proc.stderr
    assert not os.path.exists(os.path.join(top, ".bench_work")), "nothing was launched"
    with pytest.raises(families.NoFamily):
        families.load("../spec")


HARNESS_OFF_JAX = '''
import argparse, sys
sys.path.insert(0, "benchmark")
import run
r = run.Run(argparse.Namespace(workload=sys.argv[1], seed=2 ** 31 + 3, seconds=1.0, trace=1, control=0))
assert r.sizes["layers"] >= 1
print(sorted(m for m in sys.modules if m == "jax" or m.startswith(("jax.", "jaxlib", "tony_tpu.models"))))
'''


@pytest.mark.parametrize("workload", ["mistral-7b.train_8k", "mistral-7b.serve_chat", "tiny-dense.serve", "tiny-moe.train"])
def test_the_harness_process_stays_off_jax(tmp_path, workload):
    """run.py's process builds its Run (sizes through the family's file) with
    neither JAX nor the program's models imported: a child holds the chip."""
    top = _copy_of_the_benchmark(tmp_path)
    proc = _python(top, "-c", HARNESS_OFF_JAX, workload, timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().splitlines()[-1] == "[]"


@pytest.mark.parametrize("correct", [True, False])
def test_the_compared_numbers_end_both_streams(capsys, correct):
    """Every run prints each number compared beside its limit, and they are the
    last lines of standard error too: of a run that is not correct the driver's
    record keeps the end of that, and of standard output the last line's keys."""
    run = importlib.import_module("run")
    r = types.SimpleNamespace(trace=False, listed=False, bench=B, cell="tiny-dense.serve", w={"kind": "serve"})
    compared = ["requests failed: 0 of 3 []", f"worst_gap = {0.01 if correct else 0.2} over 6 tokens (limit 0.12)"]
    result = {"ctx": {}, "end_to_end": {"setup_s": 1.5, "serve_out_tok_s": 2.5}, "compared": compared,
              "correct": correct, "attempted": 3, "failed": 0, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert run.finish(r, result) == 0
    out, err = capsys.readouterr()
    assert err.splitlines() == [f"[correct] {line}" for line in compared]
    assert out.splitlines()[:-1] == err.splitlines()
    last = json.loads(out.splitlines()[-1])
    assert last["correct"] is correct and last["metrics"]["serve_out_tok_s"] == {"value": 2.5, "unit": "tokens/s"}
