"""A job's start-up accounts for itself (PR 55).

The stamps a chip-holding child takes (obs/startup.py) and how they reach the
`.jhist` once a task, gang epoch and stamp taken (executor's spawn stamp, the
AM's TASK_STARTUP_STAMPS); the compile listener on the CPU (every compile by its
source, a cached call counted nowhere, a second process's hit of the persistent
cache under `cache_load`); and the benchmark's three new readers and fifteen new
metric files on hand-built ledgers, snapshots and span files, with `None` on the
shapes the parent commit gives. The ledger's own arithmetic is in test_goodput.py;
the stage metrics of a real job are asserted in the families' rehearsals.
"""

import importlib
import json
import os
import subprocess
import sys
import time
import types

import pytest

from tony_tpu import constants
from tony_tpu.cluster.events import Event, EventType
from tony_tpu.config import TonyConfig
from tony_tpu.obs import goodput as obs_goodput
from tony_tpu.obs import metrics as obs_metrics
from tony_tpu.obs import startup as obs_startup
from tony_tpu.obs import trace as obs_trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

#: PHASE_ORDER as the parent commit (3966ea8) has it
PARENT_PHASES = ("productive", "queue_wait", "startup", "registration", "compile", "checkpoint", "input_wait",
                 "restart_rework", "preempt_drain", "resize", "takeover", "drain", "other")


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules by name, benchmark/ on the path while this file runs."""
    before = list(sys.path)
    sys.path.insert(0, BENCH)
    import spec

    yield {"spec": spec}
    sys.path[:] = before


# -- the child's stamps ---------------------------------------------------------
class TestStamps:
    def test_stamps_are_taken_once_in_order_and_published_next_to_the_step_report(self, tmp_path, monkeypatch):
        path = str(tmp_path / "worker_0.json")
        monkeypatch.setenv(constants.ENV_TRAIN_METRICS_FILE, path)
        monkeypatch.setenv(constants.ENV_CHILD_SPAWNED_MS, "1234")
        t0 = int(time.time() * 1000)
        obs_startup.begin("train")
        assert obs_startup.read_report(path) == obs_startup.report()
        obs_startup.stamp("devices_ready")
        first = obs_startup.report()["stamps"]["devices_ready"]
        time.sleep(0.002)
        obs_startup.stamp("devices_ready")  # the first taking stands
        obs_startup.stamp("weights_ready")
        rep = obs_startup.read_report(path)
        assert rep["kind"] == "train" and list(rep["stamps"]) == [
            "child_spawned", "main_entered", "devices_ready", "weights_ready"]
        st = rep["stamps"]
        assert st["child_spawned"] == 1234 and st["devices_ready"] == first
        assert t0 <= st["main_entered"] <= st["devices_ready"] <= st["weights_ready"] <= int(time.time() * 1000)
        assert not os.path.exists(path + obs_startup.FILE_SUFFIX + ".tmp")
        # a loop entered again in the same process starts the account anew
        obs_startup.begin("train")
        assert set(obs_startup.report()["stamps"]) == {"child_spawned", "main_entered"}

    def test_outside_a_container_nothing_is_written_and_nothing_raises(self, tmp_path, monkeypatch):
        monkeypatch.delenv(constants.ENV_TRAIN_METRICS_FILE, raising=False)
        monkeypatch.delenv(constants.ENV_CHILD_SPAWNED_MS, raising=False)
        monkeypatch.chdir(tmp_path)
        obs_startup.begin("serve")
        obs_startup.stamp("devices_ready")
        assert set(obs_startup.report()["stamps"]) == {"main_entered", "devices_ready"}
        assert os.listdir(tmp_path) == [] and obs_startup.read_report(None) is None
        assert obs_startup.read_report(str(tmp_path / "nothing.json")) is None

    def test_with_a_tracer_each_stage_closes_one_backdated_span(self, tmp_path, monkeypatch):
        monkeypatch.setenv(constants.ENV_CHILD_SPAWNED_MS, str(int(time.time() * 1000) - 5000))
        monkeypatch.delenv(constants.ENV_TRAIN_METRICS_FILE, raising=False)
        obs_startup.begin("serve")  # before the tracer is up, as in both entries
        tracer = obs_trace.init_tracing("app", "serve:0:train", str(tmp_path), parent_id="executor-root")
        try:
            for name in ("devices_ready", "weights_ready", "registered", "registered"):
                obs_startup.stamp(name)
        finally:
            obs_trace.shutdown()
        spans = [json.loads(line) for line in open(os.path.join(str(tmp_path), "serve_0_train.spans.jsonl"))]
        assert [s["name"] for s in spans] == ["startup.interpreter", "startup.runtime_init", "startup.weights",
                                              "startup.warmup"]
        st = obs_startup.report()["stamps"]
        assert spans[0]["start_ms"] == st["child_spawned"] and spans[0]["end_ms"] == st["main_entered"]
        assert spans[0]["end_ms"] - spans[0]["start_ms"] >= 5000 and spans[3]["end_ms"] == st["registered"]
        assert all(s["parent_id"] == "executor-root" for s in spans) and tracer.trace_id == "app"


# -- executor and AM: the channel that exists -------------------------------------
def test_the_executor_stamps_the_spawn_and_clears_a_stale_report(tmp_path):
    from tony_tpu.cluster.executor import TaskExecutor

    path = str(tmp_path / "worker_0.json")
    with open(path + obs_startup.FILE_SUFFIX, "w") as f:
        f.write('{"kind": "train", "stamps": {"main_entered": 1}}')  # the previous attempt's
    fake = types.SimpleNamespace(config=TonyConfig({}), staging_dir=str(tmp_path), _train_metrics_path=path)
    env = dict(os.environ)
    t0 = int(time.time() * 1000)
    child = TaskExecutor.launch_child(fake, "exit 0", env)
    assert child.wait(timeout=30) == 0
    assert t0 <= int(env[constants.ENV_CHILD_SPAWNED_MS]) <= int(time.time() * 1000)
    assert obs_startup.read_report(path) is None


class TestTheAmWritesStampsOnce:
    @pytest.fixture
    def am(self, tmp_path):
        from tony_tpu.cluster.appmaster import ApplicationMaster

        stage = tmp_path / "stage"
        stage.mkdir()
        with open(stage / constants.SUBMIT_INFO_FILE, "w") as f:
            json.dump({"submitted_ms": 4242}, f)
        am = ApplicationMaster(TonyConfig({"tony.worker.instances": "1"}), "app_startup", str(stage))
        am.emitted = []
        am.events.emit = lambda type_, **payload: am.emitted.append((type_, payload))
        yield am
        am.rpc.stop()
        am.events.stop()
        am.rm.shutdown()

    def test_once_a_task_epoch_and_stamp_taken_and_never_in_a_snapshot(self, am):
        am.register_worker_spec("worker", 0, "127.0.0.1", 1234, attempt=0)
        am.emitted.clear()
        first = {"kind": "train", "stamps": {"main_entered": 10, "devices_ready": 20}}
        for _ in range(3):  # executors push the same report until the child takes another stamp
            assert am.push_metrics("worker", 0, {"cpu_seconds": 1.0, "startup": dict(first)}, attempt=0)["ack"]
        grown = {"kind": "train", "stamps": {**first["stamps"], "weights_ready": 30}}
        am.push_metrics("worker", 0, {"startup": grown}, attempt=0)
        am.push_metrics("worker", 0, {"startup": grown}, attempt=0)
        am.push_metrics("worker", 0, {"cpu_seconds": 2.0}, attempt=0)              # a push without a report
        am.push_metrics("worker", 0, {"startup": {"kind": "train", "stamps": {}}}, attempt=0)  # or an empty one
        assert am.push_metrics("worker", 0, {"startup": grown}, attempt=7) == {"ack": False, "stale": True}
        assert am.emitted == [
            (EventType.TASK_STARTUP_STAMPS, {"task": "worker:0", "attempt": 0, "kind": "train", "stamps": first["stamps"]}),
            (EventType.TASK_STARTUP_STAMPS, {"task": "worker:0", "attempt": 0, "kind": "train", "stamps": grown["stamps"]}),
        ]
        # what METRICS_SNAPSHOT copies into the .jhist every period holds no stamps
        assert all("startup" not in t["metrics"] for t in am.session.task_infos())
        # the same identity in the next gang epoch is written again
        am._restart_attempt = 1
        am.session.register_worker_spec("worker", 0, "127.0.0.1", 1234)
        am.push_metrics("worker", 0, {"startup": grown}, attempt=1)
        assert am.emitted[-1][1]["attempt"] == 1 and len(am.emitted) == 3

    def test_the_clients_stamp_rides_application_inited(self, am, tmp_path):
        assert am._submit_stamp() == {"submitted_ms": 4242}
        os.remove(os.path.join(am.staging_dir, constants.SUBMIT_INFO_FILE))
        assert am._submit_stamp() == {}  # an older client's job opens at the AM's first event, as before
        # and an older reader keeps a newer stream whole
        ev = Event.from_json(json.dumps({"type": "SOMETHING_LATER", "timestamp_ms": 5, "payload": {}}))
        assert ev.type.value == "SOMETHING_LATER"
        assert Event.from_json(Event(EventType.TASK_STARTUP_STAMPS, {"task": "w:0"}, 7).to_json()).type \
            is EventType.TASK_STARTUP_STAMPS


# -- every compile counted, by its source -----------------------------------------
def _compile_counters():
    out = {}
    for m in obs_metrics.REGISTRY.snapshot():
        if m["name"] in ("tony_compile_seconds_total", "tony_compiles_total"):
            for s in m["samples"]:
                out[m["name"] + "/" + "/".join(s["labels"].values())] = s["value"]
    return out


def test_a_fresh_jit_counts_under_backend_and_a_cached_call_counts_nowhere():
    import jax
    import jax.numpy as jnp

    from tony_tpu import runtime

    runtime.enable_compile_cache()
    runtime.enable_compile_cache()  # the listeners are registered once a process
    x = jnp.ones((8, 8))
    jax.block_until_ready(x)
    before = _compile_counters()

    @jax.jit
    def fresh(a):
        return jax.jit(lambda b: jnp.tanh(b) * 3)(a @ a.T).sum()  # an inner jit: traced inside the outer's trace

    fresh(x).block_until_ready()
    after = _compile_counters()
    assert after["tony_compiles_total/backend"] == before.get("tony_compiles_total/backend", 0) + 1
    for stage in ("trace", "lower", "backend"):
        key = "tony_compile_seconds_total/" + stage
        assert after[key] > before.get(key, 0.0), stage
    assert after.get("tony_compiles_total/cache", 0) == before.get("tony_compiles_total/cache", 0)
    fresh(x).block_until_ready()
    assert _compile_counters() == after  # a call that compiles nothing moves nothing


_CACHE_SCRIPT = """
import json, os, sys
import jax, jax.numpy as jnp
from tony_tpu import runtime
from tony_tpu.obs import metrics, trace
trace.init_tracing("app", "proc", sys.argv[1])
assert runtime.enable_compile_cache() == os.environ["JAX_COMPILATION_CACHE_DIR"]
def outer(x):
    return jax.jit(lambda y: jnp.tanh(y @ y.T))(x).sum() * 3
jax.jit(outer)(jnp.ones((8, 8))).block_until_ready()
trace.shutdown()
print(json.dumps({m["name"] + "/" + "/".join(s["labels"].values()): s["value"]
                  for m in metrics.REGISTRY.snapshot() if m["name"].startswith("tony_compile") for s in m["samples"]}))
"""


def test_a_second_processes_hit_of_the_persistent_cache_counts_under_cache_load(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT, "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0", "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1"}
    runs = []
    for i in (0, 1):
        proc = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT, str(tmp_path / f"trace{i}")], env=env, cwd=ROOT,
                              capture_output=True, text=True, timeout=170)
        assert proc.returncode == 0, proc.stderr[-2000:]
        counters = json.loads(proc.stdout.strip().splitlines()[-1])
        spans = [json.loads(line) for line in open(tmp_path / f"trace{i}" / "proc.spans.jsonl")]
        runs.append((counters, [s for s in spans if s["name"] == "runtime.compile"]))
    (cold, cold_spans), (warm, warm_spans) = runs
    n = cold["tony_compiles_total/backend"]
    assert n >= 2 and "tony_compiles_total/cache" not in cold and "tony_compile_seconds_total/cache_load" not in cold
    # the same programs, read back: a hit counts under `cache` only, its read under cache_load, and what
    # is left of the call around it (the key's hashing) under backend, far below a compile
    assert warm["tony_compiles_total/cache"] == n and "tony_compiles_total/backend" not in warm
    assert warm["tony_compile_seconds_total/cache_load"] > 0
    assert warm["tony_compile_seconds_total/backend"] < cold["tony_compile_seconds_total/backend"]
    for key in ("trace", "lower"):  # Python's part is spared by no cache
        assert warm["tony_compile_seconds_total/" + key] > 0
    # the spans say the same, with the function's name, which the counters leave out
    for counters, spans in runs:
        by_stage = {}
        for s in spans:
            assert s["end_ms"] >= s["start_ms"] and set(s["attrs"]) == {"stage", "fun_name"}
            by_stage[s["attrs"]["stage"]] = by_stage.get(s["attrs"]["stage"], 0.0) + (s["end_ms"] - s["start_ms"]) / 1000
        for stage, seconds in by_stage.items():
            assert seconds == pytest.approx(counters["tony_compile_seconds_total/" + stage], abs=0.02), stage
    assert {s["attrs"]["stage"] for s in warm_spans} == {"trace", "lower", "backend", "cache_load"}
    assert {s["attrs"]["stage"] for s in cold_spans} == {"trace", "lower", "backend"}
    names = {s["attrs"]["fun_name"] for s in cold_spans if s["attrs"]["stage"] == "trace"}
    assert "outer" in names and "<lambda>" not in names  # the inner jit's trace lies inside the outer's: not counted twice


# -- the benchmark's readers --------------------------------------------------------
def _write_job(tmp_path, events, spans=()):
    """A job's directory as the harness finds it: <staging>/<app>/ with the
    `.jhist` under <staging>/history and the span sink under <app>/trace."""
    app = "application_1_startup"
    app_dir = tmp_path / app
    (app_dir / "trace").mkdir(parents=True)
    hist = tmp_path / "history" / constants.HISTORY_INTERMEDIATE_DIR
    hist.mkdir(parents=True)
    with open(hist / (app + constants.HISTORY_SUFFIX), "w") as f:
        f.writelines(e.to_json() + "\n" for e in events)
    if spans:
        with open(app_dir / "trace" / "worker_0_train.spans.jsonl", "w") as f:
            f.writelines(json.dumps(s) + "\n" for s in spans)
    return str(app_dir)


def _serve_events(stamped=True):
    ev = lambda t, ts, **p: Event(EventType(t), p, ts)  # noqa: E731
    events = [ev("APPLICATION_INITED", 1000, **({"submitted_ms": 400} if stamped else {})),
              ev("TASK_REGISTERED", 1900, task="serve:0"), ev("GANG_COMPLETE", 2000),
              ev("TASK_URL_REGISTERED", 9000, task="serve:0", url="u")]
    if stamped:
        events.append(ev("TASK_STARTUP_STAMPS", 11000, task="serve:0", attempt=0, kind="serve",
                         stamps={"child_spawned": 2100, "main_entered": 4000, "devices_ready": 6000, "weights_ready": 8500}))
    return events + [ev("APPLICATION_FINISHED", 60000, status="KILLED")]


def test_goodput_phase_reads_the_ledger_up_to_the_window(bench, tmp_path, monkeypatch):
    reader = importlib.import_module("readers.goodput_phase")
    ctx = {"kind": "serve", "app_dir": _write_job(tmp_path, _serve_events()), "drive": {"t_open": 30.0}}
    got = {p: reader.read(ctx, phase=p) for p in ("submit", "startup", "registration", "runtime_init", "weights", "warmup")}
    assert got == {"submit": 0.6, "startup": 0.9, "registration": 0.1, "runtime_init": 4.0, "weights": 2.5, "warmup": 0.5}
    assert reader.read(ctx, phase="compile") == 0.0 and reader.read(ctx, phase="no_such_phase") is None
    assert reader.read(ctx, phase="productive") == 21.0  # clipped where the window opens, not where the job ended
    # a training cell's window opens at a step line: ctx["t_open"]
    assert reader.read({"kind": "train", "app_dir": ctx["app_dir"], "t_open": 7.0}, phase="weights") == 1.0
    # the parent's shapes: a program whose PHASE_ORDER lacks the name, and a .jhist without stamps
    monkeypatch.setattr(obs_goodput, "PHASE_ORDER", PARENT_PHASES)
    assert [reader.read(dict(ctx), phase=p) for p in ("submit", "runtime_init", "weights", "warmup")] == [None] * 4
    monkeypatch.undo()
    (tmp_path / "older").mkdir()
    old = {"kind": "serve", "app_dir": _write_job(tmp_path / "older", _serve_events(stamped=False)), "drive": {"t_open": 30.0}}
    assert [reader.read(old, phase=p) for p in ("startup", "registration", "compile", "runtime_init")] == [None] * 4


def test_registry_at_open_and_the_delta_over_the_window(bench):
    at_open = importlib.import_module("readers.registry_at_open")
    delta = importlib.import_module("readers.registry_delta")

    def snap(seconds, chunks):
        return {"t": 1.0, "metrics": [
            {"name": "tony_compile_seconds_total", "samples": [
                {"labels": {"stage": k}, "value": v} for k, v in seconds.items()]},
            {"name": "tony_serve_engine_chunks_total", "samples": [{"labels": {}, "value": chunks}]}]}

    drive = {"snap0": snap({"trace": 3.0, "lower": 2.0, "backend": 40.0, "cache_load": 1.5}, 10),
             "snap1": snap({"trace": 3.0, "lower": 2.0, "backend": 40.0, "cache_load": 1.5}, 510)}
    spec = bench["spec"]
    read = lambda name, d=drive: importlib.import_module("readers." + spec.metric(name)["reader"]).read(  # noqa: E731
        {"drive": d}, **spec.metric(name)["args"])
    assert (read("setup_compile_s.serve"), read("setup_cache_load_s.serve"), read("setup_trace_lower_s.serve")) == (40.0, 1.5, 5.0)
    assert read("compile_ms_per_pass.serve") == 0.0
    late = {**drive, "snap1": snap({"trace": 3.2, "lower": 2.1, "backend": 41.2, "cache_load": 1.5}, 510)}
    assert read("compile_ms_per_pass.serve", late) == pytest.approx(3.0)  # 1.5 s of compiling over 500 passes
    # a run from an empty cache read nothing back: 0, not nothing
    cold = {"snap0": snap({"trace": 3.0, "backend": 40.0}, 10), "snap1": None}
    assert read("setup_cache_load_s.serve", cold) == 0.0 and read("compile_ms_per_pass.serve", cold) is None
    # the parent's registry has no such instrument, and a snapshot can be missing (Fleet.snapshot's 5 s)
    parent = {"t": 1.0, "metrics": [{"name": "tony_serve_engine_chunks_total", "samples": [{"labels": {}, "value": 1}]}]}
    for d in ({"snap0": parent, "snap1": parent}, {"snap0": None, "snap1": None}):
        assert [read(n, d) for n in ("setup_compile_s.serve", "setup_cache_load_s.serve", "setup_trace_lower_s.serve",
                                     "compile_ms_per_pass.serve")] == [None] * 4
    assert at_open.read({"drive": drive}, term={"name": "tony_serve_engine_chunks_total"}, scale=2.0) == 20.0
    assert delta.total(drive["snap0"], "tony_compile_seconds_total") == 46.5


def test_span_seconds_by_stage_before_the_window(bench, tmp_path):
    spec = bench["spec"]

    def span(name, start, end, **attrs):
        return {"name": name, "start_ms": start, "end_ms": end, **({"attrs": attrs} if attrs else {})}

    spans = [span("runtime.compile", 1000.0, 1500.0, stage="trace", fun_name="train_step"),
             span("runtime.compile", 1500.0, 1750.0, stage="lower", fun_name="train_step"),
             span("runtime.compile", 1750.0, 9750.0, stage="backend", fun_name="train_step"),
             span("runtime.compile", 9800.0, 9900.0, stage="cache_load", fun_name=""),
             span("runtime.compile", 31000.0, 33000.0, stage="backend", fun_name="late"),   # inside the window
             span("train.input_wait", 2000.0, 2100.0), span("startup.weights", 500.0, 900.0)]
    lines = [{"step": s, "ts_ms": 20000.0 + 1000.0 * s} for s in range(10, 60, 10)]
    ctx = {"kind": "train", "app_dir": _write_job(tmp_path, [], spans), "t_open": 30.0, "lines": lines}
    read = lambda name, c=ctx: importlib.import_module("readers." + spec.metric(name)["reader"]).read(  # noqa: E731
        c, **spec.metric(name)["args"])
    assert (read("setup_compile_s.train"), read("setup_cache_load_s.train"), read("setup_trace_lower_s.train")) == (8.0, 0.1, 0.75)
    assert read("compile_pct.train") == pytest.approx(100.0 * 2.0 / 40.0)  # the late compile, over first line -> last
    # the parent's traced run: a sink with spans, none of them a compile's; and an untraced run: no sink
    (tmp_path / "parent").mkdir()
    parent = {**ctx, "app_dir": _write_job(tmp_path / "parent", [], spans[-2:])}
    assert [read(n, parent) for n in ("setup_compile_s.train", "setup_cache_load_s.train", "setup_trace_lower_s.train")] == [None] * 3
    assert read("compile_pct.train", parent) == 0.0
    (tmp_path / "off").mkdir()
    off = {**ctx, "app_dir": _write_job(tmp_path / "off", [])}
    assert [read(n, off) for n in ("setup_compile_s.train", "compile_pct.train")] == [None, None]


# -- the metric files ---------------------------------------------------------------
NEW_METRICS = {
    # name: (reader, cells that list it, what of the program it reads)
    "submit_to_am_s": ("goodput_phase", "all", "submit"),
    "allocate_s": ("goodput_phase", "all", "startup"),
    "register_s": ("goodput_phase", "all", "registration"),
    "runtime_init_s": ("goodput_phase", "all", "runtime_init"),
    "weights_s": ("goodput_phase", "all", "weights"),
    "first_step_s.train": ("goodput_phase", "train", "compile"),
    "replica_warmup_s.serve": ("goodput_phase", "serve", "warmup"),
    "setup_compile_s.serve": ("registry_at_open", "serve", "tony_compile_seconds_total"),
    "setup_cache_load_s.serve": ("registry_at_open", "serve", "tony_compile_seconds_total"),
    "setup_trace_lower_s.serve": ("registry_at_open", "serve", "tony_compile_seconds_total"),
    "setup_compile_s.train": ("span_seconds", "train", "runtime.compile"),
    "setup_cache_load_s.train": ("span_seconds", "train", "runtime.compile"),
    "setup_trace_lower_s.train": ("span_seconds", "train", "runtime.compile"),
    "compile_ms_per_pass.serve": ("registry_delta", "tput", "tony_compile_seconds_total"),
    "compile_pct.train": ("span_share", "train", "runtime.compile"),
}


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_new_metric_file_names_a_reader_its_cells_and_something_the_program_registers(bench, name):
    spec = bench["spec"]
    B = spec.benchmark()
    reader, cells, reads = NEW_METRICS[name]
    m = spec.metric(name)
    entry = next(e for e in B["per_layer"] if e["name"] == name)
    assert m["reader"] == reader and callable(importlib.import_module("readers." + reader).read)
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"} and entry["better"] == "lower"
    every = [w["name"] for w in B["workloads"]]
    want = {"all": every, "train": [c for c in every if ".train" in c], "serve": [c for c in every if ".serve" in c],
            "tput": next(e for e in B["end_to_end"] if e["name"] == "serve_out_tok_s")["workloads"]}[cells]
    assert entry["workloads"] == want and len(want) >= {"all": 10, "train": 2, "serve": 8, "tput": 7}[cells]   # a later cell joins its kind's lists
    # every cell that lists it reports the end-to-end metric it moves
    for cell in entry["workloads"]:
        assert entry["moves"] in {e["name"] for e in spec.cell_metrics(B, cell, "end_to_end")}, cell
    assert m.get("kinds", ["train", "serve"]) == {"all": ["train", "serve"], "train": ["train"]}.get(cells, ["serve"])
    # appended after everything the benchmark had then, in the issue's order (a later PR's metrics follow them)
    names = [e["name"] for e in B["per_layer"]]
    first = names.index(next(iter(NEW_METRICS)))
    assert names[first:first + len(NEW_METRICS)] == list(NEW_METRICS) and first >= 45
    # what it reads is something the program has: a phase of the ledger, a registered instrument
    # with that label, or a span name the program writes
    if reader == "goodput_phase":
        assert m["args"] == {"phase": reads} and reads in obs_goodput.PHASE_ORDER and entry["source"] == "program_span"
    elif reader in ("registry_at_open", "registry_delta"):
        from tony_tpu import runtime

        runtime.enable_compile_cache()
        registered = {x["name"]: x for x in obs_metrics.REGISTRY.snapshot()}
        terms = [m["args"]["term"]] if reader == "registry_at_open" else [m["args"]["num"], m["args"]["den"]]
        assert terms[0]["name"] == reads and entry["source"] == "program_counter"
        for term in terms:
            if term["name"].startswith("tony_serve_"):
                import tony_tpu.models.serving  # noqa: F401 - the engine's instruments register at import
                registered = {x["name"]: x for x in obs_metrics.REGISTRY.snapshot()}
            assert term["name"] in registered, term
            for label, values in term.get("where", {}).items():
                assert label in registered[term["name"]]["labelnames"]
                assert set(values) <= {"trace", "lower", "backend", "cache_load"}
    else:
        assert m["args"]["span"] == reads and entry["source"] == "program_span"
        with open(os.path.join(ROOT, "tony_tpu", "runtime", "__init__.py")) as f:
            assert f'"{reads}"' in f.read()
