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
import shutil
import subprocess
import sys
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


def test_kernel_roofline_finds_its_count_by_the_kernel_argument():
    read = importlib.import_module("readers.kernel_roofline").read
    flash = ("%shard_map.1 = (bf16[64,8192,128]{2,1,0}, f32[64,8192,128]{2,1,0}) custom-call(bf16[64,8192,128]{2,1,0} %q), "
             "custom_call_target=\"tpu_custom_call\"")
    other = "%moe.2 = bf16[16384,14336]{1,0} custom-call(bf16[16384,4096]{1,0} %x), custom_call_target=\"tpu_custom_call\""
    run = types.SimpleNamespace(sizes=MISTRAL, w={"seq_len": 8192, "batch_size": 2}, chips=1,
                                peaks=spec.load_json("peaks.json"))
    ctx = {"run": run, "device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "trace": {"op_time_s": {flash: 3.0, other: 5.0}, "op_count": {flash: 20, other: 20}}}
    peak = counts.peak_for("TPU v5 lite", run.peaks)
    fwd, bwd = (counts.roofline_seconds(*llama_counts.flash_call(MISTRAL, 2, 8192, b), peak) for b in (False, True))
    assert read(ctx, kernel="flash", match="tpu_custom_call") == 100.0 * (2 * fwd + bwd) * 20 / 3.0
    assert llama_counts.flash_layer_step(MISTRAL, 2, 8192) == [
        llama_counts.flash_call(MISTRAL, 2, 8192, b) for b in (False, False, True)]
    ctx["trace"] = {"op_time_s": {other: 5.0}, "op_count": {other: 20}}
    assert read(ctx, kernel="flash", match="tpu_custom_call") is None  # no call with flash's shape: nothing, never 0
    with pytest.raises(ValueError, match="llama_counts.py has no moe_gemm_operands"):
        read(ctx, kernel="moe_gemm", match="tpu_custom_call")


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
