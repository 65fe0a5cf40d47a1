#!/usr/bin/env python3
"""The chip check: `tony submit` and `tony serve` end to end on the TPU.

Run plainly (``python chip_smoke.py``) it needs one TPU chip and drives the
two entry points users launch, through the normal path (client -> AM ->
executor -> a child that owns the chip), at llama-1b width:

  train   `tony submit` of examples/llama/pretrain.py --preset llama-1b on a
          one-chip local pool: the real loop (token loader, prefetch thread,
          logging-window sync, an async checkpoint); loss finite and lower at
          the last logged step than at the first.
  serve   `tony serve --preset llama-1b --replicas 1` with --kv unset (the
          server must resolve it to paged on the chip): completions through
          the fleet router, one streamed, a repeated greedy request returning
          the same tokens, /stats, then an interrupt that drains to exit 0.
  check   a child of its own, started after the server has gone: the plain
          path (llama.forward, XLA attention, same seed-made weights) teacher-
          forced over what the engine returned, and the train-step and decode
          programs lowered on the chip and searched for their Pallas kernels.

``--chips 4`` runs instead (and only) the four-chip phase: the same job with
fsdp over four chips, against the same global batch and seed as a one-chip
job on the same host, then a 4 x 1-chip gang joined by jax.distributed.

This process never imports JAX: a parent that touched JAX would hold the chip
and the child that needs it would fail. The device facts in the last line come
from the processes that held the chip (their logs, /stats). The script never
picks the CPU, interpret mode or a smaller size by itself; a CPU rehearsal is
the caller's ask: ``JAX_PLATFORMS=cpu python chip_smoke.py --size tiny``.

Last stdout line: {"ok": true, "device": {"platform", "kind", "count"}}.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, ".tony", "chip_smoke")  # .tony/ is ignored by git
TONY = [sys.executable, "-m", "tony_tpu.cli.main"]

#: what each size runs. "full" is the contract; "tiny" rehearses the control
#: flow on whatever backend the caller chose.
SIZES = {
    "full": dict(preset="llama-1b", seq=2048, batch=8, steps=12, log_every=2,
                 ckpt_every=6, slots=64, max_len=2048, prompt=200, new=16, vocab=32_000),
    "tiny": dict(preset="tiny", seq=128, batch=8, steps=12, log_every=2,
                 ckpt_every=6, slots=4, max_len=256, prompt=24, new=8, vocab=256),
}
#: each engine token's reference logit must be within this of the reference's
#: maximum at its position (teacher-forced; bf16 weights and activations on
#: random weights leave near-ties that flip an argmax, so tokens are not
#: compared for equality)
LOGIT_TOL = 0.25
#: the four-chip job's loss against the one-chip job's, at each logged step
#: (bf16 matmuls reduce in another order once the batch is split four ways)
LOSS_RTOL = 2e-2


class PhaseFailed(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def child_env() -> dict:
    env = dict(os.environ)
    env["TONY_ROOT"] = os.path.join(WORK, "staging")
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return env


def job_procs() -> dict[int, str]:
    """Every live process started under this run (they all inherit our
    TONY_ROOT): pid -> command line."""
    marker = ("TONY_ROOT=" + os.path.join(WORK, "staging")).encode()
    out = {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        if int(pid) == os.getpid():
            continue
        try:
            with open(f"/proc/{pid}/environ", "rb") as f:
                if marker not in f.read().split(b"\0"):
                    continue
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                out[int(pid)] = f.read().replace(b"\0", b" ").decode(errors="replace").strip()
        except OSError:
            continue
    return out


def launcher_procs_off_the_chip() -> str:
    """One process for each chip: only the child the executor started may
    have the accelerator's library mapped. Returns a line for the report."""
    holders, others = [], 0
    for pid, cmd in job_procs().items():
        try:
            with open(f"/proc/{pid}/maps") as f:
                maps = f.read()
        except OSError:
            continue
        if "libtpu" in maps or "jaxlib" in maps:
            holders.append(cmd)
        else:
            others += 1
    bad = [c for c in holders if "pretrain.py" not in c and "serving_http" not in c]
    if bad:
        raise PhaseFailed(f"a launcher-side process has JAX loaded: {bad}")
    return f"{others} launcher process(es) off JAX, {len(holders)} chip-holding child(ren)"


def reap(what: str, wait_s: float = 30.0) -> None:
    """The chip-holding child of a finished job must be gone before the next
    phase starts: a lingering replica holds the chip."""
    deadline = time.time() + wait_s
    while (left := job_procs()) and time.time() < deadline:
        time.sleep(0.5)
    if left:
        kill_all()
        raise PhaseFailed(f"{what}: processes outlived the job: {left}")


def kill_all() -> None:
    for sig in (signal.SIGTERM, signal.SIGKILL):
        procs = job_procs()
        if not procs:
            return
        for pid in procs:
            try:
                os.kill(pid, sig)
            except OSError:
                pass
        time.sleep(2.0)


def newest_app(before: set[str]) -> str:
    staging = os.path.join(WORK, "staging")
    new = sorted(set(os.listdir(staging)) - before - {"history"})
    if len(new) != 1:
        raise PhaseFailed(f"expected one new application under {staging}, found {new}")
    return os.path.join(staging, new[0])


def read_logs(app_dir: str, task: str) -> str:
    """stdout+stderr of one task (every restart attempt), for parsing."""
    text = []
    logs = os.path.join(app_dir, "logs")
    for d in sorted(os.listdir(logs)):
        if d == task or d.startswith(task + "_r"):
            for name in ("stdout.log", "stderr.log"):
                path = os.path.join(logs, d, name)
                if os.path.exists(path):
                    with open(path, errors="replace") as f:
                        text.append(f.read())
    return "\n".join(text)


def tail(text: str, n: int = 25) -> str:
    return "\n".join(text.splitlines()[-n:])


def device_of(log: str) -> dict:
    m = re.search(r"\[train\] device (\{.*?\}) compile_cache=", log)
    if not m:
        raise PhaseFailed("the training child logged no device line")
    return json.loads(m.group(1))


def write_data(cfg: dict, seed: int) -> str:
    """Token shards from the seed, with structure a model can learn in a few
    steps (a Zipf unigram draw: the loss falls from ~ln V towards the
    distribution's entropy)."""
    import numpy as np

    sys.path.insert(0, ROOT)
    from tony_tpu.data.dataset import write_token_shard

    d = os.path.join(WORK, "data")
    os.makedirs(d, exist_ok=True)
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, cfg["vocab"] + 1) ** 1.2
    n = cfg["batch"] * (cfg["seq"] + 1) * (cfg["steps"] + 4)
    for i in range(2):
        toks = rng.choice(cfg["vocab"], size=n // 2 + cfg["seq"] + 1, p=p / p.sum())
        write_token_shard(os.path.join(d, f"shard{i:02d}.tonytok"), toks)
    return d


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------
def train_job(name: str, cfg: dict, data_dir: str, *, host_chips: int, chips: int,
              instances: int = 1, timeout_s: float = 900.0, need_platform: str = "") -> dict:
    """One `tony submit` of the llama pretraining example; returns what the
    child(ren) logged. Raises PhaseFailed unless the job SUCCEEDED."""
    ckpt = os.path.join(WORK, f"ckpt-{name}")
    shutil.rmtree(ckpt, ignore_errors=True)
    conf = {
        "tony.application.name": f"chip-smoke-{name}",
        "tony.application.framework": "jax",
        "tony.worker.instances": instances,
        "tony.worker.memory": "16g",
        "tony.worker.chips": chips,
        "tony.tpu.pool": f"local:v5e-{host_chips}" if host_chips else "local:cpu",
        "tony.checkpoint.dir": ckpt,
        "tony.checkpoint.interval-steps": cfg["ckpt_every"],
    }
    conf_path = os.path.join(WORK, f"{name}.json")
    with open(conf_path, "w") as f:
        json.dump(conf, f)
    executes = (
        f"{sys.executable} examples/llama/pretrain.py --preset {cfg['preset']} "
        f"--steps {cfg['steps']} --batch_size {cfg['batch']} --seq_len {cfg['seq']} "
        f"--log_every {cfg['log_every']} --warmup_steps 2 --learning_rate 1e-3 "
        f"--data_dir {data_dir} --data_seed 0")
    staging = os.path.join(WORK, "staging")
    os.makedirs(staging, exist_ok=True)
    before = set(os.listdir(staging))
    t0 = time.time()
    out_path = os.path.join(WORK, f"{name}.out")
    with open(out_path, "w") as out_f:
        proc = subprocess.Popen(
            [*TONY, "submit", "--conf_file", conf_path, "--executes", executes],
            cwd=ROOT, env=child_env(), stdout=out_f, stderr=subprocess.STDOUT,
            start_new_session=True)
    platform_seen = False
    while proc.poll() is None:
        time.sleep(1.0)
        problem = None
        if time.time() - t0 > timeout_s:
            problem = f"{name}: `tony submit` ran past {timeout_s:.0f}s"
        elif (need_platform and not platform_seen
              and len(set(os.listdir(staging)) - before - {"history"}) == 1):
            # fail as soon as the child says where it runs: the full size on
            # a CPU would grind for an hour before the last check caught it
            logs = os.path.join(newest_app(before), "logs")
            m = re.search(r'\[train\] device \{"platform": "(\w+)"',
                          read_logs(os.path.dirname(logs), "worker_0") if os.path.isdir(logs) else "")
            platform_seen = m is not None
            if m and m.group(1) != need_platform:
                problem = (f"{name}: no {need_platform.upper()}: the child found platform "
                           f"{m.group(1)!r}. The full size needs the chip; a rehearsal is "
                           "`JAX_PLATFORMS=cpu python chip_smoke.py --size tiny`")
        if problem:
            kill_all()
            proc.kill()
            with open(out_path) as f:
                raise PhaseFailed(f"{problem}\n{tail(f.read())}")
    with open(out_path) as f:
        out = f.read()
    wall = time.time() - t0
    app_dir = newest_app(before)
    log = "\n".join(read_logs(app_dir, f"worker_{i}") for i in range(instances))
    if proc.returncode != 0 or "finished: SUCCEEDED" not in out:
        saw = sorted(set(re.findall(r"\[train\] device (\{.*?\}) compile_cache=", log)))
        raise PhaseFailed(
            f"{name}: `tony submit` exit {proc.returncode}; its child(ren) saw {saw}\n{tail(out)}\n"
            f"--- worker log\n{tail(log, 40)}")
    reap(name)
    lines = []
    for ln in log.splitlines():
        m = re.search(r'\{"step": .*\}', ln)
        if m:
            lines.append(json.loads(m.group(0)))
    # a gang logs every line once for each member: keep one of each step
    steps = {ln["step"]: ln for ln in lines}
    losses = [steps[s]["loss"] for s in sorted(steps)]
    if len(losses) < 2 or not all(math.isfinite(x) for x in losses):
        raise PhaseFailed(f"{name}: losses not finite or too few: {losses}\n{tail(log, 40)}")
    first = re.search(r"first step \(compile included\) ([\d.]+)s", log)
    data = re.search(r"\[train\] data: \d+ shards, (\d+) tokens", log)
    per_dev = re.search(r"param bytes per device: (\{.*\})", log)
    saved = sorted(d for d in os.listdir(ckpt) if d.isdigit()) if os.path.isdir(ckpt) else []
    last = steps[max(steps)]
    return {
        "name": name, "app": os.path.basename(app_dir), "wall_s": round(wall, 1),
        "device": device_of(log), "losses": losses, "steps": sorted(steps),
        "first_step_s": float(first.group(1)) if first else None,
        "tokens_per_sec": last.get("tokens_per_sec"), "mfu": last.get("mfu"),
        "data_tokens": int(data.group(1)) if data else None,
        "param_bytes_per_device": json.loads(per_dev.group(1)) if per_dev else None,
        "checkpoints": saved, "log": log,
    }


def report_train(r: dict, cfg: dict) -> None:
    say(f"[train:{r['name']}] `tony submit` SUCCEEDED app={r['app']} wall={r['wall_s']}s "
        f"preset={cfg['preset']} batch={cfg['batch']} seq={cfg['seq']} steps={cfg['steps']}")
    say(f"[train:{r['name']}] device={json.dumps(r['device'])} data_tokens={r['data_tokens']} "
        f"first_step_s={r['first_step_s']} (compile included) "
        f"tokens_per_sec(last window)={r['tokens_per_sec']} mfu={r['mfu']}")
    say(f"[train:{r['name']}] loss by logged step {dict(zip(r['steps'], r['losses']))} "
        f"checkpoints={r['checkpoints']} param_bytes_per_device={r['param_bytes_per_device']}")


def phase_train(cfg: dict, data_dir: str, host_chips: int, need_platform: str) -> dict:
    r = train_job("train", cfg, data_dir, host_chips=host_chips, chips=1 if host_chips else 0,
                  need_platform=need_platform)
    report_train(r, cfg)
    if not r["losses"][-1] < r["losses"][0]:
        raise PhaseFailed(f"train: loss did not fall: {r['losses']}")
    if not r["checkpoints"]:
        raise PhaseFailed("train: no checkpoint was written")
    if not r["data_tokens"]:
        raise PhaseFailed("train: the loop did not say what data it read")
    return r


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
def post(url: str, body: dict, timeout: float = 300.0) -> dict:
    req = urllib.request.Request(
        url + "/v1/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        if not body.get("stream"):
            return json.loads(resp.read())
        tokens, finished = [], None
        for raw in resp:
            line = raw.decode().strip()
            if line.startswith("data:"):
                ev = json.loads(line[5:])
                if ev.get("finished"):
                    finished = ev["tokens"]
                else:
                    tokens += ev.get("tokens", [])
        return {"tokens": finished if finished is not None else tokens, "streamed": tokens}


def phase_serve(cfg: dict, seed: int, host_chips: int) -> dict:
    import random

    rnd = random.Random(seed)
    prompts = [[rnd.randrange(1, cfg["vocab"]) for _ in range(cfg["prompt"])] for _ in range(3)]
    staging = os.path.join(WORK, "staging")
    os.makedirs(staging, exist_ok=True)
    before = set(os.listdir(staging))
    cmd = [*TONY, "serve", "--preset", cfg["preset"], "--replicas", "1",
           "--slots", str(cfg["slots"]), "--max_len", str(cfg["max_len"]),
           "--seed", str(seed), "--url_timeout_s", "600",
           "--conf", f"tony.tpu.pool={'local:v5e-%d' % host_chips if host_chips else 'local:cpu'}",
           "--conf", f"tony.serve.chips={1 if host_chips else 0}",
           "--conf", "tony.serve.memory=16g"]
    t0 = time.time()
    out_path = os.path.join(WORK, "serve.out")
    with open(out_path, "w") as out_f:
        proc = subprocess.Popen(cmd, cwd=ROOT, env=child_env(), stdout=out_f,
                                stderr=subprocess.STDOUT, start_new_session=True)
    endpoint = None
    try:
        while time.time() - t0 < 900 and proc.poll() is None and endpoint is None:
            with open(out_path) as f:
                m = re.search(r"fleet router (http://\S+)", f.read())
            endpoint = m.group(1) if m else None
            time.sleep(0.5)
        if endpoint is None:
            with open(out_path) as f:
                raise PhaseFailed(f"serve: no fleet router endpoint\n{tail(f.read())}")
        up_s = time.time() - t0
        app_dir = newest_app(before)
        results = {}
        t1 = time.time()
        results["greedy"] = post(endpoint, {"prompt_tokens": prompts[0], "max_tokens": cfg["new"]})
        first_req_s = time.time() - t1
        results["greedy_again"] = post(endpoint, {"prompt_tokens": prompts[0], "max_tokens": cfg["new"]})
        # several decode chunks long, so that token events precede the last one
        results["streamed"] = post(endpoint, {"prompt_tokens": prompts[1],
                                              "max_tokens": 3 * cfg["new"], "stream": True})
        results["sampled"] = post(endpoint, {"prompt_tokens": prompts[2], "max_tokens": cfg["new"],
                                             "temperature": 0.8, "top_k": 40})
        results["short"] = post(endpoint, {"prompt_tokens": prompts[2][:5], "max_tokens": 4})
        for name, r in results.items():
            want = {"short": 4, "streamed": 3 * cfg["new"]}.get(name, cfg["new"])
            if len(r.get("tokens", [])) != want:
                raise PhaseFailed(f"serve: request {name!r} returned {r}")
        if results["greedy"]["tokens"] != results["greedy_again"]["tokens"]:
            raise PhaseFailed(f"serve: a repeated greedy request changed its tokens: "
                              f"{results['greedy']['tokens']} vs {results['greedy_again']['tokens']}")
        if not results["streamed"]["streamed"]:
            raise PhaseFailed("serve: the streamed request delivered no token event")
        serve_log = read_logs(app_dir, "serve_0")
        m = re.search(r"\[tony-serve\] (http://\S+) role=", serve_log)
        if not m:
            raise PhaseFailed(f"serve: replica logged no endpoint\n{tail(serve_log)}")
        with urllib.request.urlopen(m.group(1) + "/stats", timeout=30) as resp:
            stats = json.loads(resp.read())
        procs_line = launcher_procs_off_the_chip()
        # the interrupt a user sends: the client kills the job, the AM
        # SIGTERMs the replica, the replica drains and exits 0
        os.killpg(proc.pid, signal.SIGINT)
        rc = proc.wait(timeout=120)
    except Exception:
        kill_all()
        if proc.poll() is None:
            proc.kill()
        raise
    reap("serve")
    serve_log = read_logs(app_dir, "serve_0")
    drained = ("[tony-serve] draining" in serve_log and "drain timed out" not in serve_log
               and "Traceback" not in serve_log)
    cache = re.search(r"compile_cache=(\S+)", serve_log)
    say(f"[serve] `tony serve` up in {up_s:.1f}s (compile cache: {cache.group(1) if cache else '?'}) "
        f"preset={cfg['preset']} slots={cfg['slots']} max_len={cfg['max_len']} kv={stats.get('kv')} "
        f"pages_total={stats.get('pages_total')} first_request_s={first_req_s:.1f}")
    say(f"[serve] {len(results)} requests answered through the router "
        f"(1 streamed in {len(results['streamed']['streamed'])} tokens of events, greedy repeat identical); "
        f"/stats requests_done={stats['requests_done']} tokens_out={stats['tokens_out']} "
        f"device={json.dumps(stats['device'])}")
    say(f"[serve] {procs_line}; interrupt -> `tony serve` exit {rc}, replica drained cleanly={drained}")
    if rc != 0 or not drained:
        raise PhaseFailed(f"serve: drain failed (exit {rc}, drained={drained})\n{tail(serve_log)}")
    if stats["requests_done"] < len(results):
        raise PhaseFailed(f"serve: /stats counts {stats['requests_done']} requests")
    return {"stats": stats, "prompt": prompts[0], "tokens": results["greedy"]["tokens"]}


# ---------------------------------------------------------------------------
# check: the one part that needs JAX, run as a child after the jobs have gone
# ---------------------------------------------------------------------------
def phase_check(cfg: dict, seed: int, served: dict) -> dict:
    kv = served["stats"]["kv"]
    spec = {"preset": cfg["preset"], "seed": seed, "prompt": served["prompt"],
            "tokens": served["tokens"], "batch": cfg["batch"], "seq": cfg["seq"],
            "slots": cfg["slots"], "max_len": cfg["max_len"], "kv": kv}
    env = child_env()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--_check", json.dumps(spec)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        raise PhaseFailed(f"check: child exit {proc.returncode}\n{tail(proc.stdout)}\n{tail(proc.stderr)}")
    r = json.loads(proc.stdout.strip().splitlines()[-1])
    say(f"[check] plain llama.forward (XLA attention, same seed) teacher-forced over the engine's "
        f"{len(served['tokens'])} greedy tokens: worst logit gap {r['worst_gap']:.4f} "
        f"(tolerance {LOGIT_TOL}), argmax agrees at {r['argmax_agree']}/{len(served['tokens'])}, "
        f"first token in the reference's top-5: {r['first_in_top5']}")
    say(f"[check] kernels in the programs lowered on {r['device']['platform']}: "
        f"train step tpu_custom_call x{r['train_custom_calls']}, "
        f"decode step ({kv}) tpu_custom_call x{r['decode_custom_calls']}")
    if not (r["worst_gap"] <= LOGIT_TOL and r["first_in_top5"] and math.isfinite(r["worst_gap"])):
        raise PhaseFailed(f"check: the engine disagrees with the plain forward: {r}")
    return r


def _check_child(spec: dict) -> int:
    import dataclasses
    import functools

    import jax
    import jax.numpy as jnp

    from tony_tpu.models import llama, serving
    from tony_tpu.models.paged_cache import init_paged_cache
    from tony_tpu.parallel import MeshSpec
    from tony_tpu.runtime import device_facts, enable_compile_cache
    from tony_tpu.train import OptimizerConfig, make_train_step
    from tony_tpu.train.trainer import TrainState

    enable_compile_cache()
    cfg = llama.PRESETS[spec["preset"]]
    params = llama.init(jax.random.PRNGKey(spec["seed"]), cfg)
    prompt, toks = spec["prompt"], spec["tokens"]
    seq = jnp.asarray([prompt + toks], jnp.int32)
    plain = dataclasses.replace(cfg, attn_impl="reference", remat=False)
    logits = jax.jit(functools.partial(llama.forward, cfg=plain))(params, seq)[0].astype(jnp.float32)
    # position len(prompt)-1+i predicts generated token i
    rows = logits[len(prompt) - 1: len(prompt) - 1 + len(toks)]
    chosen = rows[jnp.arange(len(toks)), jnp.asarray(toks)]
    gaps = rows.max(axis=-1) - chosen
    top5 = jnp.argsort(rows[0])[-5:]
    out = {
        "worst_gap": float(gaps.max()),
        "argmax_agree": int((rows.argmax(axis=-1) == jnp.asarray(toks)).sum()),
        "first_in_top5": bool(toks[0] in [int(t) for t in top5]),
        "device": device_facts(),
    }
    # the programs the two jobs ran, lowered on this backend: are the Pallas
    # kernels in them? (lowering only: nothing is compiled or run)
    mesh = MeshSpec.auto(1).build(devices=jax.devices()[:1])
    opt = OptimizerConfig(warmup_steps=2, total_steps=12).build()
    abstract = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    state = TrainState(abstract, jax.eval_shape(opt.init, abstract),
                       jax.ShapeDtypeStruct((), jnp.int32))
    batch = {"tokens": jax.ShapeDtypeStruct((spec["batch"], spec["seq"] + 1), jnp.int32)}
    step = make_train_step(functools.partial(llama.loss_fn, cfg=cfg, mesh=mesh), opt)
    out["train_custom_calls"] = step.lower(state, batch).as_text().count("tpu_custom_call")
    if spec["kv"] == "paged":
        page_len = 256
        cache = jax.eval_shape(
            lambda: init_paged_cache(cfg, spec["slots"], spec["max_len"], page_len,
                                     spec["slots"] * (spec["max_len"] // page_len) + 1))
    else:
        cache = jax.eval_shape(lambda: serving.init_slot_cache(cfg, spec["slots"], spec["max_len"]))
    attn = "ragged" if spec["kv"] == "paged" else "bucketed"
    lowered = serving.decode_steps.lower(
        abstract, cache, jax.ShapeDtypeStruct((spec["slots"],), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32), cfg, 8, 0.0, 0, attn)
    out["decode_custom_calls"] = lowered.as_text().count("tpu_custom_call")
    print(json.dumps(out), flush=True)
    return 0


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def phase_four_chips(cfg: dict, data_dir: str, need_platform: str) -> dict:
    four = train_job("fsdp4", cfg, data_dir, host_chips=4, chips=4, need_platform=need_platform)
    report_train(four, cfg)
    one = train_job("one-of-four", cfg, data_dir, host_chips=4, chips=1)
    report_train(one, cfg)
    if four["device"]["count"] != 4 or one["device"]["count"] != 1:
        raise PhaseFailed(f"four chips: the 4-chip child saw {four['device']['count']} device(s), "
                          f"the 1-chip child {one['device']['count']} (want 4 and 1)")
    spread = four["param_bytes_per_device"] or {}
    if len(spread) != 4 or min(spread.values()) <= 0 or max(spread.values()) > 1.5 * min(spread.values()):
        raise PhaseFailed(f"four chips: parameters are not spread over four devices: {spread}")
    if four["steps"] != one["steps"]:
        raise PhaseFailed(f"four chips: logged steps differ: {four['steps']} vs {one['steps']}")
    worst = max(abs(a - b) / abs(b) for a, b in zip(four["losses"], one["losses"]))
    say(f"[four] fsdp over 4 chips vs 1 chip, same global batch and seed: worst relative loss "
        f"difference over {len(one['losses'])} logged steps {worst:.5f} (tolerance {LOSS_RTOL}); "
        f"param bytes per device {spread}")
    if worst > LOSS_RTOL:
        raise PhaseFailed(f"four chips: losses disagree: {four['losses']} vs {one['losses']}")
    # a gang of four one-chip processes joined by jax.distributed: does this
    # installation form one 4-device mesh on one host? Established, not emulated.
    try:
        gang = train_job("gang4x1", cfg, data_dir, host_chips=4, chips=1, instances=4,
                         timeout_s=300.0)
        report_train(gang, cfg)
        worst_g = max(abs(a - b) / abs(b) for a, b in zip(gang["losses"], one["losses"]))
        say(f"[four] 4 x 1-chip gang: global devices={gang['device']['count']} "
            f"local={gang['device']['local']} worst relative loss difference {worst_g:.5f}")
        if gang["device"]["count"] != 4 or worst_g > LOSS_RTOL:
            raise PhaseFailed(f"four chips: the gang formed {gang['device']['count']} device(s), "
                              f"losses {gang['losses']}")
    except PhaseFailed as e:
        kill_all()
        msg = str(e)
        say(f"[four] 4 x 1-chip gang joined by jax.distributed did NOT form one mesh here "
            f"(left for ROADMAP R0, not emulated): {msg[:300]} ... {msg[-600:]}")
    return four


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    p.add_argument("--size", choices=sorted(SIZES), default="full",
                   help="full: llama-1b, needs the TPU. tiny: a rehearsal of the control flow")
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the four-chip phase and its one-chip comparison")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--_check", default=None, help=argparse.SUPPRESS)
    args = p.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "tony_tpu")):
        print(f"chip_smoke: {ROOT} holds no tony_tpu package: run it from a checkout", file=sys.stderr)
        return 2
    if args._check is not None:
        sys.path.insert(0, ROOT)
        return _check_child(json.loads(args._check))
    if os.environ.get("TONY_PALLAS_INTERPRET") == "1" and args.size == "full":
        print("chip_smoke: TONY_PALLAS_INTERPRET=1 is set: the full size runs compiled kernels "
              "on the chip; unset it (interpret mode is for `--size tiny` rehearsals)", file=sys.stderr)
        return 2
    cfg = SIZES[args.size]
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    t0 = time.time()
    try:
        data_dir = write_data(cfg, args.seed)
        # on the CPU (a rehearsal) the pool has no chips to place
        rehearsal_cpu = args.size == "tiny" and os.environ.get("JAX_PLATFORMS") == "cpu"
        need_platform = "tpu" if args.size == "full" else ""
        if args.chips == 4:
            device = phase_four_chips(cfg, data_dir, need_platform)["device"]
        else:
            host_chips = 0 if rehearsal_cpu else 1
            trained = phase_train(cfg, data_dir, host_chips, need_platform)
            served = phase_serve(cfg, args.seed, host_chips)
            kv = served["stats"]["kv"]
            checked = phase_check(cfg, args.seed, served)
            device = trained["device"]
            seen = {json.dumps({k: d[k] for k in ("platform", "kind", "count")}, sort_keys=True)
                    for d in (device, served["stats"]["device"], checked["device"])}
            if len(seen) != 1:
                raise PhaseFailed(f"the phases ran on different devices: {seen}")
            if args.size == "full":
                if kv != "paged":
                    raise PhaseFailed(f"serve: --kv unset resolved to {kv!r}, not paged")
                if not (checked["train_custom_calls"] and checked["decode_custom_calls"]):
                    raise PhaseFailed(f"check: a program holds no Pallas kernel: {checked}")
    except PhaseFailed as e:
        kill_all()
        print(f"chip_smoke: FAILED after {time.time() - t0:.0f}s: {e}", file=sys.stderr)
        return 1
    finally:
        kill_all()
    say(f"[done] {time.time() - t0:.0f}s")
    print(json.dumps({"ok": True, "device": {k: device[k] for k in ("platform", "kind", "count")}}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
