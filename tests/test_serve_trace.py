"""The serving engine's account of its own time (docs/observability.md "Where
a request's TTFT goes", "Where a pass's host time goes"): request stages
stamped in the engine, engine phases on two clocks and their time off the CPU,
the stream writers' own instruments, and the benchmark readers that turn them
into metrics.

Tiny config, ``ContinuousBatcher`` and ``EngineServer`` in-process: no fleet,
no subprocess, no sleep over 0.2 s.
"""

import json
import os
import sys
import threading
import time
import types
import urllib.request
from http.server import ThreadingHTTPServer

import jax
import pytest

from tony_tpu.models import serving, serving_http
from tony_tpu.models.llama import LLAMA_TINY, init
from tony_tpu.models.serving import ContinuousBatcher
from tony_tpu.models.serving_http import EngineServer, RequestStream
from tony_tpu.obs import trace as obs_trace

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
# at the END of the path: the benchmark's top-level module names (run, check,
# spec, jobs, reduce ...) must not shadow anything a later test file imports
if BENCH not in sys.path:
    sys.path.append(BENCH)

from readers import gap_by_span, gap_under_writes, registry_delta  # noqa: E402

STAGES = ("queue", "prefill", "emit")


@pytest.fixture(scope="module")
def params():
    return init(jax.random.PRNGKey(0), LLAMA_TINY)


def engine(params, **kw):
    return ContinuousBatcher(params, LLAMA_TINY, **{"num_slots": 2, "max_len": 64, "decode_chunk": 4, **kw})


def finish(out):
    while True:
        kind, payload = out.get(timeout=120)
        if kind != "tokens":
            assert kind == "done", payload
            return payload


def observed(monkeypatch, histogram):
    """Every value the histogram is handed from here on, with its labels."""
    seen, real = [], histogram.observe

    def observe(value, exemplar=None, **labels):
        seen.append((labels.get("stage"), value))
        real(value, exemplar=exemplar, **labels)

    monkeypatch.setattr(histogram, "observe", observe)
    return seen


def blocked(reason):
    return serving._ADMIT_BLOCKED.value(reason=reason)


def check_stages(outs, ttfts, stages):
    """queue + prefill + emit is the TTFT the replica observed, request by
    request (``outs`` in the order of their first fanouts)."""
    assert len(ttfts) == len(outs) and len(stages) == 3 * len(outs)
    for i, (_, ttft) in enumerate(ttfts):
        triple = stages[3 * i:3 * i + 3]
        assert tuple(label for label, _ in triple) == STAGES
        assert all(seconds >= 0 for _, seconds in triple)
        assert sum(seconds for _, seconds in triple) == pytest.approx(ttft, abs=1e-9)
    for out, (_, ttft) in zip(outs, ttfts):
        assert out.submitted_s <= out.req.staged_s <= out.req.slot_s <= out.submitted_s + ttft


def test_stages_add_up_to_the_ttft_of_every_request_one_of_which_waited_for_a_slot(params, monkeypatch):
    ttfts = observed(monkeypatch, serving_http._TTFT)
    stages = observed(monkeypatch, serving_http._STAGE)
    slots0 = blocked("slots")
    srv = EngineServer(engine(params, num_slots=1)).start()
    try:
        outs = [srv.submit([1 + i, 2, 3], max_tokens=9) for i in range(3)]
        assert all(len(finish(out)) == 9 for out in outs)
    finally:
        assert srv.stop()
    check_stages(outs, ttfts, stages)
    # one slot: the third request stayed in `pending` (its queue stage) while
    # the first two decoded, and the engine counted passes with nobody admitted
    assert blocked("slots") > slots0
    assert outs[2].req.staged_s > outs[0].submitted_s + ttfts[0][1]  # staged after the first one's fanout
    assert outs[2].req.staged_s - outs[2].submitted_s > outs[0].req.staged_s - outs[0].submitted_s


def test_a_request_that_waited_for_pages_is_counted_and_still_adds_up(params, monkeypatch):
    ttfts = observed(monkeypatch, serving_http._TTFT)
    stages = observed(monkeypatch, serving_http._STAGE)
    pages0 = blocked("pages")
    # 8 + 16 tokens = 3 pages of 8 a request; the pool holds 4: the second
    # request has a free slot and its prefill done, and waits for pages
    srv = EngineServer(engine(params, kv="paged", page_len=8, num_pages=5)).start()
    try:
        outs = [srv.submit(list(range(1 + i, 9 + i)), max_tokens=16) for i in range(2)]
        assert all(len(finish(out)) == 16 for out in outs)
    finally:
        assert srv.stop()
    check_stages(outs, ttfts, stages)
    assert blocked("pages") > pages0
    waited = outs[1].req
    assert waited.slot_s - waited.staged_s > outs[0].req.slot_s - outs[0].req.staged_s  # in its prefill stage


def test_phases_tile_the_engine_threads_time(params, monkeypatch):
    """Σ phase seconds is the loop's wall time within 1% (other engine threads
    of this process, which earlier test files may have left idling, are kept
    out: the shared counter is replaced by one that takes this thread only)."""
    by_phase: dict[str, float] = {}
    wall = []

    class OnThisThread:
        def inc(self, amount=1.0, **labels):
            if threading.current_thread() is srv._thread:
                by_phase[labels["phase"]] = by_phase.get(labels["phase"], 0.0) + amount

    monkeypatch.setattr(serving, "_ENGINE_SECONDS", OnThisThread())
    srv = EngineServer(engine(params))
    loop = srv._loop_inner

    def timed():
        t0 = time.perf_counter()
        try:
            loop()
        finally:
            wall.append(time.perf_counter() - t0)

    srv._loop_inner = timed
    srv.start()
    outs = [srv.submit([1 + i, 2, 3], max_tokens=9) for i in range(5)]
    assert all(len(finish(out)) == 9 for out in outs)
    time.sleep(0.2)  # one idle wait of the loop
    assert srv.stop()
    assert set(by_phase) == {"intake", "admit", "prefill_wait", "dispatch", "decode_wait", "emit", "idle"}
    assert all(v >= 0 for v in by_phase.values())
    assert sum(by_phase.values()) == pytest.approx(wall[0], rel=0.01)
    assert srv.engine.phase._name is None  # the loop closed its last phase


class Recorded:
    """A counter's place: what was added, by phase, in order."""

    def __init__(self):
        self.seen = []

    def inc(self, amount=1.0, **labels):
        self.seen.append((labels["phase"], amount))


def test_the_phase_clock_counts_wall_less_cpu_as_time_off_the_cpu_and_never_less_than_nothing(monkeypatch):
    wall, off = Recorded(), Recorded()
    monkeypatch.setattr(serving, "_ENGINE_SECONDS", wall)
    monkeypatch.setattr(serving, "_ENGINE_OFFCPU", off)
    # (wall, cpu) at each change of phase: dispatch runs 1 s of which 0.25 on the CPU; decode_wait 2 s with none;
    # emit is all CPU, and the CPU clock's coarser tick reads past the wall clock's: that is no negative wait
    stamps = iter([(10.0, 5.0), (11.0, 5.25), (13.0, 5.25), (13.5, 5.875), (14.0, 5.875)])
    now = {}

    def tick():
        now["wall"], now["cpu"] = next(stamps)
        return now["wall"]

    clock = serving._PhaseClock(wall=tick, cpu=lambda: now["cpu"])
    for name in ("dispatch", "decode_wait", "emit", "idle", None):
        clock.to(name)
    assert wall.seen == [("dispatch", 1.0), ("decode_wait", 2.0), ("emit", 0.5), ("idle", 0.5)]
    assert off.seen == [("dispatch", 0.75), ("decode_wait", 2.0), ("emit", 0.0), ("idle", 0.5)]
    assert sum(v for _, v in wall.seen) == 14.0 - 10.0  # the phases still tile the thread's time
    assert all(0.0 <= o <= w for (_, w), (_, o) in zip(wall.seen, off.seen)) and clock._name is None


def test_on_the_real_clocks_a_thread_asleep_is_off_the_cpu_and_one_spinning_is_on_it(monkeypatch):
    """Whatever else the box runs: the sleeper is off the CPU for its sleep, and
    of the spinner's phase, however long a loaded scheduler made it, all but
    what the thread's own CPU clock counted is off the CPU."""
    monkeypatch.setattr(serving, "_ENGINE_SECONDS", wall := Recorded())
    monkeypatch.setattr(serving, "_ENGINE_OFFCPU", off := Recorded())
    clock = serving._PhaseClock()
    clock.to("idle")
    time.sleep(0.05)
    clock.to("emit")
    cpu0 = time.thread_time()
    while time.thread_time() - cpu0 < 0.05:
        pass
    clock.to(None)
    (_, asleep), (_, spinning) = off.seen
    assert asleep >= 0.04
    assert 0.0 <= spinning <= wall.seen[1][1] - 0.04


def sum_count(histogram):
    _, children = histogram._snapshot_children()
    return next(((c["sum"], c["count"]) for key, c in children if key == ()), (0.0, 0))


def test_a_streamed_request_leaves_one_write_an_event_and_a_delay_no_shorter_than_the_write(params):
    """Over HTTP, as a client sees it: every SSE event is one observation of
    the writer's time and one of the delay since the engine handed it over."""
    srv = EngineServer(engine(params)).start()
    handler = type("Handler", (serving_http._Handler,), {"server_ref": srv, "tokenizer": None})
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    write0, delay0 = sum_count(serving_http._STREAM_WRITE), sum_count(serving_http._FANOUT_DELAY)
    try:
        req = urllib.request.Request(f"http://127.0.0.1:{httpd.server_address[1]}/v1/completions", json.dumps(
            {"prompt_tokens": [4, 5, 6], "max_tokens": 13, "stream": True}).encode(), {"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            events = [json.loads(line[6:]) for line in resp.read().decode().splitlines() if line.startswith("data: ")]
    finally:
        httpd.shutdown()
        httpd.server_close()
        assert srv.stop()
    assert events[-1]["finished"] and len(events[-1]["tokens"]) == 13
    assert len(events) >= 4 and sum(len(e["tokens"]) for e in events[:-1]) <= 13  # chunks of 4, then the whole answer
    write1, delay1 = sum_count(serving_http._STREAM_WRITE), sum_count(serving_http._FANOUT_DELAY)
    assert write1[1] - write0[1] == delay1[1] - delay0[1] == len(events)
    assert delay1[0] - delay0[0] >= write1[0] - write0[0] > 0.0


def test_an_events_stamp_rides_with_it_and_a_later_event_does_not_overwrite_it():
    stream = RequestStream(4)
    stream.put(("tokens", [1]))
    between = time.perf_counter()
    EngineServer._finish_stream(stream, ("done", [1, 2]))
    assert stream.get(timeout=1) == ("tokens", [1]) and 0.0 < stream.handed_s <= between
    assert stream.get(timeout=1) == ("done", [1, 2]) and stream.handed_s >= between
    # a full queue: the terminal event takes a buffered chunk's place, with its own stamp
    full = RequestStream(1)
    full.put(("tokens", [1]))
    EngineServer._finish_stream(full, ("error", "cancelled"))
    assert full.get(timeout=1) == ("error", "cancelled") and full.handed_s >= between


def admitted(under):
    return serving._ADMISSIONS.value(under=under)


def test_chunk_slot_prefill_and_admission_counters_match_a_hand_count(params):
    """Two slots, chunks of 4. Pass 1 finds nothing running: it admits A and B
    with the device idle, takes their first tokens at once and dispatches chunk
    1 over them; B's budget (5) ends inside it, so its slot counts as free.
    Pass 2 stages, prefills and inserts C behind chunk 1, dispatches chunk 2
    over A and C behind that, and reads chunk 1: A at 5, B done. Pass 3 reads
    chunk 2 (with C's first token): A reaches 9, C 5, both done. Two chunks,
    two slots each; three prompts of 3 tokens, each padded to the smallest
    bucket (16); two admissions under nothing, one under a chunk."""
    counters = (serving._CHUNKS, serving._DECODE_SLOTS, serving._PREFILL_TOKENS)
    before = [c.value() for c in counters] + [admitted("idle"), admitted("chunk")]
    eng = engine(params)
    rids = [eng.submit([1, 2, 3], n) for n in (9, 5, 5)]
    assert [eng.request(r).max_new_tokens for r in rids] == [9, 5, 5] and eng.request(99) is None
    lengths = []
    while eng.step():
        lengths.append([len(r.out) for r in sorted(eng._decoding(), key=lambda r: r.rid)])
    # after pass 1: A and B hold their first tokens; after pass 2: A at 5, C in B's slot with nothing on the host yet
    assert lengths == [[1, 1], [5, 0]]
    assert [len(eng.done[r]) for r in rids] == [9, 5, 5]
    after = [c.value() for c in counters] + [admitted("idle"), admitted("chunk")]
    assert [a - b for a, b in zip(after, before)] == [2, 4, 48, 2, 1]
    assert eng.phase._name is None  # a bare engine leaves no phase open between passes
    assert eng.request(rids[0]) is None  # done: the engine holds it no longer


def read_spans(tmp_path):
    return [json.loads(line) for p in tmp_path.glob("*.jsonl") for line in open(p).read().splitlines()]


def test_span_chain_has_the_four_stages_with_the_stamped_boundaries(params, tmp_path, monkeypatch):
    tracer = obs_trace.Tracer("trace-1", "serve:0", str(tmp_path))
    monkeypatch.setattr(obs_trace, "_tracer", tracer)
    ttfts = observed(monkeypatch, serving_http._TTFT)
    srv = EngineServer(engine(params)).start()
    try:
        out = srv.submit([5, 6, 7], max_tokens=6, request_id="req-7")
        assert len(finish(out)) == 6
    finally:
        assert srv.stop()
    tracer.close()
    by_name = {s["name"]: s for s in read_spans(tmp_path)}
    assert set(by_name) == {"serve.request", "serve.queue", "serve.prefill", "serve.emit", "serve.decode"}
    root, r = by_name["serve.request"], out.req
    marks = [out.submitted_s, r.staged_s, r.slot_s, out.submitted_s + ttfts[0][1]]
    for i, label in enumerate(STAGES):
        span = by_name["serve." + label]
        assert span["parent_id"] == root["span_id"] and span["status"] == "ok"
        assert span["start_ms"] == pytest.approx(marks[i] * 1000, abs=2e-3)
        assert span["end_ms"] == pytest.approx(marks[i + 1] * 1000, abs=2e-3)
        assert span["attrs"] == {"rid": "req-7", "prompt_tokens": 3, "prefix_tokens": 0, "chunks": 1, "slot": r.slot}
    decode = by_name["serve.decode"]
    assert decode["parent_id"] == root["span_id"] and decode["start_ms"] >= by_name["serve.emit"]["end_ms"]
    assert decode["attrs"]["ttft_s"] == pytest.approx(ttfts[0][1], abs=1e-6)
    assert root["start_ms"] <= by_name["serve.queue"]["start_ms"] + 1 and root["end_ms"] >= decode["end_ms"]


def test_a_request_that_dies_before_its_first_token_says_in_which_stage(tmp_path, monkeypatch):
    tracer = obs_trace.Tracer("trace-1", "serve:0", str(tmp_path))
    monkeypatch.setattr(obs_trace, "_tracer", tracer)
    stream = RequestStream(request_id="req-9")
    stream.submitted_s -= 1.0
    stream.open_trace()
    stream.req = serving._Request(0, [1, 2, 3], 4, staged_s=stream.submitted_s + 0.5)
    stream.finish_trace("error")  # e.g. its deadline passed while its prefill was queued
    tracer.close()
    spans = {s["name"]: s for s in read_spans(tmp_path)}
    assert set(spans) == {"serve.request", "serve.queue", "serve.prefill"}
    assert (spans["serve.queue"]["status"], spans["serve.prefill"]["status"]) == ("ok", "error")
    assert spans["serve.queue"]["end_ms"] == spans["serve.prefill"]["start_ms"]


# -- the benchmark's readers (plain Python: no job, no trace file) -------------
def snapshot(ttft, stage_sums, phases, chunks, slots=0, prompt_tokens=0):
    n = ttft[1]
    return {"t": 0.0, "metrics": [
        {"name": "tony_serve_ttft_seconds", "type": "histogram",
         "samples": [{"labels": {}, "sum": ttft[0], "count": n}]},
        {"name": "tony_serve_stage_seconds", "type": "histogram",
         "samples": [{"labels": {"stage": k}, "sum": v, "count": n} for k, v in stage_sums.items()]},
        {"name": "tony_serve_engine_seconds_total", "type": "counter",
         "samples": [{"labels": {"phase": k}, "value": v} for k, v in phases.items()]},
        {"name": "tony_serve_engine_chunks_total", "type": "counter", "samples": [{"labels": {}, "value": chunks}]},
        {"name": "tony_serve_decode_slots_total", "type": "counter", "samples": [{"labels": {}, "value": slots}]},
        {"name": "tony_serve_prefill_tokens_total", "type": "counter",
         "samples": [{"labels": {}, "value": prompt_tokens}]},
    ]}


def metric_args(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        return json.load(f)["args"]


def test_registry_delta_reads_the_change_between_two_snapshots():
    snap0 = snapshot((10.0, 20), {"queue": 2.0, "prefill": 6.0, "emit": 2.0},
                     {"intake": 1.0, "admit": 1.0, "dispatch": 2.0, "emit": 1.0, "decode_wait": 14.0,
                      "prefill_wait": 1.0, "idle": 50.0}, 100, slots=3000, prompt_tokens=40000)
    snap1 = snapshot((17.0, 30), {"queue": 3.0, "prefill": 10.0, "emit": 4.0},
                     {"intake": 1.5, "admit": 1.5, "dispatch": 3.0, "emit": 2.0, "decode_wait": 30.0,
                      "prefill_wait": 2.0, "idle": 51.0}, 200, slots=9200, prompt_tokens=52800)
    ctx = {"drive": {"snap0": snap0, "snap1": snap1}}
    means = [registry_delta.read(ctx, **metric_args(m + ".serve"))
             for m in ("queue_wait_ms", "prefill_stage_ms", "first_emit_ms")]
    assert means == pytest.approx([100.0, 400.0, 200.0])
    assert sum(means) == pytest.approx(1000 * (17.0 - 10.0) / (30 - 20))  # the stages close on the TTFT
    assert registry_delta.read(ctx, **metric_args("chunk_period_ms.serve")) == pytest.approx(200.0)  # 20 s / 100
    assert registry_delta.read(ctx, **metric_args("host_share_pct.serve")) == pytest.approx(15.0)  # 3 of 20 s
    assert registry_delta.read(ctx, **metric_args("decode_batch_mean.serve")) == pytest.approx(62.0)  # 6200 / 100
    assert registry_delta.read(ctx, **metric_args("prefill_tok_per_chunk.serve")) == pytest.approx(128.0)
    # a program from before the instruments, a missing snapshot, a window with no first token: nothing
    old = {"t": 0.0, "metrics": snap0["metrics"][:1]}
    for drive in ({"snap0": old, "snap1": old}, {"snap0": None, "snap1": snap1}, {"snap0": snap1, "snap1": snap1}):
        assert registry_delta.read({"drive": drive}, **metric_args("queue_wait_ms.serve")) is None


def test_admit_overlap_is_the_share_of_the_windows_admissions_made_under_a_chunk():
    def snap(chunk, idle):
        return {"t": 0.0, "metrics": [{"name": "tony_serve_admissions_total", "type": "counter", "samples": [
            {"labels": {"under": "chunk"}, "value": chunk}, {"labels": {"under": "idle"}, "value": idle}]}]}

    args = metric_args("admit_overlap_pct.serve")
    read = lambda snap0, snap1: registry_delta.read({"drive": {"snap0": snap0, "snap1": snap1}}, **args)
    assert read(snap(10, 64), snap(490, 64)) == pytest.approx(100.0)  # the ramp's idle admissions lie before the window
    assert read(snap(10, 4), snap(40, 14)) == pytest.approx(75.0)     # 30 of the window's 40
    # a program from before the counter (the parent), or a window that admitted nobody: nothing, and no error
    old = snapshot((1.0, 1), {}, {}, 1)
    assert read(old, old) is None and read(snap(5, 5), snap(5, 5)) is None and read(None, snap(5, 5)) is None


def test_gap_by_span_lays_the_gaps_under_the_phase_that_covers_them():
    # phases as the engine's clock writes them: each ends where the next starts
    phases = [("dispatch", 4.0, 5.0), ("admit", 0.0, 1.0), ("prefill_wait", 1.0, 3.0), ("admit", 3.0, 4.0),
              ("decode_wait", 5.0, 9.0), ("emit", 9.0, 10.0)]
    gaps = [(0.5, 1.5), (3.5, 4.5), (6.0, 6.25), (9.5, 11.0)]
    by = gap_by_span.gap_seconds_by_phase(gaps, phases)
    assert by == pytest.approx({"admit": 1.0, "prefill_wait": 0.5, "dispatch": 0.5, "decode_wait": 0.25,
                                "emit": 0.5, gap_by_span.NONE: 1.0})
    assert sum(by.values()) == pytest.approx(sum(e - s for s, e in gaps))
    # a device that is busy except for those gaps, with a `while` over everything (left out, as in reduce.py)
    busy = [(0.0, 0.5), (1.5, 3.5), (4.5, 6.0), (6.25, 9.5), (11.0, 12.0)]
    ops = {"/device:TPU:0": [("%fusion.1 = f32[8]{0} fusion(%p)", s, e) for s, e in busy]
           + [("%while.2 = (s32[]) while(%t), body=%b", 0.0, 12.0)]}
    got = gap_by_span.summarise(ops, phases)
    assert got["window_s"] == pytest.approx(12.0) and got["gap_s"] == pytest.approx(by)
    assert gap_by_span.summarise(ops, []) is None and gap_by_span.summarise({}, phases) is None
    assert gap_by_span.read({"trace": None}) is None


# phases as the engine's clock writes them, a device busy except for four gaps, and three handler threads whose
# writes overlap each other, the gaps and the phases
PHASES = [("dispatch", 4.0, 5.0), ("admit", 0.0, 1.0), ("prefill_wait", 1.0, 3.0), ("admit", 3.0, 4.0),
          ("decode_wait", 5.0, 9.0), ("emit", 9.0, 10.0)]
GAPS = [(0.5, 1.5), (3.5, 4.5), (6.0, 6.25), (9.5, 11.0)]
BUSY = [(0.0, 0.5), (1.5, 3.5), (4.5, 6.0), (6.25, 9.5), (11.0, 12.0)]
WRITES = [[(0.25, 0.75), (3.75, 4.25), (10.5, 10.75)], [(0.5, 1.25), (6.0, 6.5)], [(0.625, 0.875), (9.0, 9.75)]]


def fake_profile(writes):
    """What `ProfileData.from_file` returns, as far as the readers look: planes, lines, events."""
    def event(name, s, e):
        return types.SimpleNamespace(name=name, start_ns=round(s * 1e9), duration_ns=round((e - s) * 1e9))

    def line(name, events):
        return types.SimpleNamespace(name=name, events=events)

    device = types.SimpleNamespace(name="/device:TPU:0", lines=[
        line("XLA Ops", [event("%fusion.1 = f32[8]{0} fusion(%p)", s, e) for s, e in BUSY]
             + [event("%while.2 = (s32[]) while(%t), body=%b", 0.0, 12.0)]),
        line("XLA Modules", [event("jit_decode_steps", 0.0, 12.0)])])
    host = types.SimpleNamespace(name="/host:CPU", lines=[
        line("engine", [event("tony.serve." + n, s, e) for n, s, e in PHASES] + [event("_value", 5.0, 9.0)])]
        + [line(f"handler-{i}", [event("tony.stream.write", s, e) for s, e in ws] + [event("sendall", 0.0, 12.0)])
           for i, ws in enumerate(writes)])
    return types.SimpleNamespace(planes=[device, host])


def test_the_writers_annotations_leave_gap_by_span_exactly_as_it_was(monkeypatch):
    """`gap_by_span` sweeps every `tony.serve.*` event as ONE thread's phases;
    256 overlapping events under that prefix would corrupt it. The writers'
    are `tony.stream.write`: with or without them it reads the same."""
    assert not gap_under_writes.WRITE.startswith(gap_by_span.PREFIX)
    got = {}
    for key, writes in (("without", []), ("with", WRITES)):
        monkeypatch.setattr(jax.profiler.ProfileData, "from_file", staticmethod(lambda path, w=writes: fake_profile(w)))
        ops, phases = gap_by_span.read_xplane("a.xplane.pb")
        assert sorted(phases) == sorted(PHASES)
        got[key] = gap_by_span.summarise(ops, phases)
    assert got["with"] == got["without"]
    by = got["with"]["gap_s"]
    assert by == pytest.approx({"admit": 1.0, "prefill_wait": 0.5, "dispatch": 0.5, "decode_wait": 0.25, "emit": 0.5,
                                gap_by_span.NONE: 1.0})
    host_gap_pct = 100.0 * sum(v for k, v in by.items() if k not in (gap_by_span.NONE, "decode_wait", "prefill_wait")) / 12.0
    assert host_gap_pct == pytest.approx(100.0 * 2.0 / 12.0)
    # and the writers' reader finds them on every handler thread's line, beside the same phases
    ops, writes, phases = gap_under_writes.read_xplane("a.xplane.pb")
    assert sorted(writes) == sorted(w for ws in WRITES for w in ws) and sorted(phases) == sorted(PHASES)
    assert gap_under_writes.summarise(ops, writes, phases)["gap_s"] == pytest.approx(
        gap_under_writes.split(GAPS, writes, PHASES))


def test_gap_under_writes_splits_the_idle_time_by_who_was_at_work():
    assert gap_under_writes.intersect([(0.0, 2.0), (3.0, 5.0), (1.0, 2.5)], [(2.25, 3.5), (0.5, 1.0), (4.0, 9.0)]) == [
        (0.5, 1.0), (2.25, 2.5), (3.0, 3.5), (4.0, 5.0)]
    writes = [w for ws in WRITES for w in ws]  # their union: 0.25-1.25, 3.75-4.25, 6.0-6.5, 9.0-9.75, 10.5-10.75
    by = gap_under_writes.split(GAPS, writes, PHASES)
    # gap 0.5-1.5: admit to 1.0, under a write all the way (both 0.5), then prefill_wait (waiting 0.5, write or no write)
    # gap 3.5-4.5: admit and dispatch; a write over 3.75-4.25 (both 0.5, host alone 0.5)
    # gap 6.0-6.25: decode_wait, under a write: the device's own all the same (waiting 0.25)
    # gap 9.5-11.0: emit to 10.0, a write to 9.75 (both 0.25, host alone 0.25); then no phase: a write over
    #               10.5-10.75 (writes alone 0.25), the rest under neither (0.75)
    assert by == pytest.approx({"both": 1.25, "writes": 0.25, "host": 0.75, "neither": 0.75, "waiting": 0.75})
    assert sum(by.values()) == pytest.approx(sum(e - s for s, e in GAPS))  # the parts are the idle time, once
    # beside gap_by_span on the same lists: its host phases are `both` + `host`, its waiting phases `waiting`
    spans = gap_by_span.gap_seconds_by_phase(GAPS, PHASES)
    assert by["both"] + by["host"] == pytest.approx(spans["admit"] + spans["dispatch"] + spans["emit"])
    assert by["waiting"] == pytest.approx(spans["prefill_wait"] + spans["decode_wait"])
    assert by["writes"] + by["neither"] == pytest.approx(spans[gap_by_span.NONE])
    ops = {"/device:TPU:0": [("%fusion.1 = f32[8]{0} fusion(%p)", s, e) for s, e in BUSY]}
    got = gap_under_writes.summarise(ops, writes, PHASES)
    assert got["window_s"] == pytest.approx(12.0) and got["writes"] == 7 and got["write_s"] == pytest.approx(3.0)
    assert 100.0 * (got["gap_s"]["both"] + got["gap_s"]["writes"]) / got["window_s"] == pytest.approx(12.5)
    # no writers in the trace (a program from before them), no phase, no device plane, no traced run: nothing
    assert gap_under_writes.summarise(ops, [], PHASES) is None and gap_under_writes.summarise(ops, writes, []) is None
    assert gap_under_writes.summarise({}, writes, PHASES) is None and gap_under_writes.read({"trace": None}) is None


NEW_METRICS = {  # metric -> (reader, the cells that listed it at PR 41: a later cell is appended to the list)
    "host_gap_pct.serve_tput": ("gap_by_span", ["minicpm-sala.serve_longdoc", "k-exaone-236b.serve_reason"]),
    "host_offcpu_ms.serve_tput": ("registry_delta", ["mistral-7b.serve_batch", "minicpm-sala.serve_longdoc",
                                                     "k-exaone-236b.serve_reason"]),
    "stream_write_ms.serve_tput": ("registry_delta", ["mistral-7b.serve_batch", "minicpm-sala.serve_longdoc",
                                                      "k-exaone-236b.serve_reason"]),
    "fanout_delay_ms.serve_tput": ("registry_delta", ["mistral-7b.serve_batch", "minicpm-sala.serve_longdoc",
                                                      "k-exaone-236b.serve_reason"]),
    "write_gap_pct.serve_tput": ("gap_under_writes", ["minicpm-sala.serve_longdoc", "k-exaone-236b.serve_reason"]),
}


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_new_metric_names_a_reader_that_is_there_and_cells_that_report_what_it_moves(name):
    reader, cells = NEW_METRICS[name]
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        m = json.load(f)
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert (m["reader"], m["layer"], m["moves"], m["kinds"], m["better"]) == (
        reader, "engine", "serve_out_tok_s", ["serve"], "lower")
    assert os.path.exists(os.path.join(BENCH, "readers", reader + ".py"))
    (entry,) = [x for x in bench["per_layer"] if x["name"] == name]
    assert entry["workloads"][:len(cells)] == cells and (entry["unit"], entry["moves"]) == (m["unit"], m["moves"])
    reports = next(x["workloads"] for x in bench["end_to_end"] if x["name"] == "serve_out_tok_s")
    assert set(entry["workloads"]) <= set(reports)
    if reader == "registry_delta":  # it reads an instrument the program registers, and nothing before the window moved it
        registered = {x["name"] for x in serving_http.obs_metrics.REGISTRY.snapshot()}
        assert {m["args"]["num"]["name"], m["args"]["den"]["name"]} <= registered
        assert registry_delta.read({"drive": {"snap0": None, "snap1": None}}, **m["args"]) is None


def test_the_three_registry_metrics_read_the_change_of_their_instruments():
    def snap(offcpu, chunks, write, delay):
        return {"t": 0.0, "metrics": [
            {"name": "tony_serve_engine_offcpu_seconds_total", "type": "counter",
             "samples": [{"labels": {"phase": k}, "value": v} for k, v in offcpu.items()]},
            {"name": "tony_serve_engine_chunks_total", "type": "counter", "samples": [{"labels": {}, "value": chunks}]},
            {"name": "tony_serve_stream_write_seconds", "type": "histogram",
             "samples": [{"labels": {}, "sum": write[0], "count": write[1]}]},
            {"name": "tony_serve_fanout_delay_seconds", "type": "histogram",
             "samples": [{"labels": {}, "sum": delay[0], "count": delay[1]}]}]}

    snap0 = snap({"intake": 0.5, "dispatch": 1.0, "admit": 1.0, "emit": 2.0, "decode_wait": 40.0, "idle": 9.0},
                 100, (1.0, 1000), (3.0, 1000))
    snap1 = snap({"intake": 0.5, "dispatch": 2.0, "admit": 1.5, "emit": 4.5, "decode_wait": 70.0, "idle": 9.0},
                 300, (3.0, 5000), (11.0, 5000))
    ctx = {"drive": {"snap0": snap0, "snap1": snap1}}
    assert registry_delta.read(ctx, **metric_args("host_offcpu_ms.serve_tput")) == pytest.approx(20.0)  # 4 s over 200 passes
    assert registry_delta.read(ctx, **metric_args("stream_write_ms.serve_tput")) == pytest.approx(0.5)  # 2 s over 4000 events
    assert registry_delta.read(ctx, **metric_args("fanout_delay_ms.serve_tput")) == pytest.approx(2.0)
    old = {"t": 0.0, "metrics": snap0["metrics"][1:2]}  # the parent: chunks, and none of the new instruments
    for name in ("host_offcpu_ms", "stream_write_ms", "fanout_delay_ms"):
        assert registry_delta.read({"drive": {"snap0": old, "snap1": old}}, **metric_args(name + ".serve_tput")) is None
