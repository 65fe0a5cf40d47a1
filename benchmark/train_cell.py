"""A training cell: `tony submit` of entry/train_lm.py, measured from its step lines.

Set-up is everything up to the window: data written from the seed, the job
submitted, the weights drawn, compilation, and the loop's first `warm_lines`
logging windows. The comparison with the reference is no part of it: it runs
in a child of its own once the job has been stopped and the chip is free.
The window opens at a step line and closes
`--seconds` later; throughput is the tokens of the whole steps between the
first and last step line inside it over the time between those two lines, by
the child's own clock (each line is stamped after the loop's sync on the loss).
"""

from __future__ import annotations

import json
import math
import os
import shlex
import struct
import sys
import time

import numpy as np

import jobs
import reduce as trace_reduce
from spec import CHECK_TAIL


def write_data(run, d: str) -> None:
    """Token shards from the seed in the program's shard format (8-byte magic,
    u32 dtype code, u64 count, little-endian payload)."""
    os.makedirs(d, exist_ok=True)
    data = run.w["data"]
    rng = np.random.default_rng(run.seed)
    vocab = run.sizes["vocab"]
    prob = 1.0 / np.arange(1, vocab + 1) ** data["zipf_exponent"]
    prob /= prob.sum()
    for i in range(data["shards"]):
        toks = rng.choice(vocab, size=data["tokens"] // data["shards"], p=prob)
        code, dtype = (0, "<u2") if vocab <= 0x10000 else (1, "<i4")
        with open(os.path.join(d, f"shard{i:02d}.tonytok"), "wb") as f:
            f.write(b"TONYTOK1" + struct.pack("<IQ", code, toks.size) + toks.astype(dtype).tobytes())


def step_lines(app_dir: str) -> list[dict]:
    """The loop's step reports from its structured log: step, loss, ts_ms."""
    out = {}
    for path in jobs.find_files(os.path.join(app_dir, "logs"), ".log.jsonl"):
        for rec in jobs.read_jsonl(path):
            if isinstance(rec.get("step"), int) and "loss" in rec and "tokens_per_sec" in rec:
                out.setdefault(rec["step"], rec)  # a gang logs each line once a member
    return [out[s] for s in sorted(out)]


def run(run) -> dict:
    w = run.w
    data_dir = os.path.join(run.work, "data")
    write_data(run, data_dir)
    tokens_per_step = w["batch_size"] * w["seq_len"]
    conf = {
        "tony.application.name": f"bench-{run.cell}",
        "tony.application.framework": "jax",
        "tony.worker.instances": 1,
        "tony.worker.memory": "16g",
        "tony.worker.chips": 0 if run.cpu_rehearsal else run.chips,
        "tony.tpu.pool": run.pool,
        "tony.checkpoint.dir": os.path.join(run.work, "ckpt"),
        "tony.checkpoint.interval-steps": 0,
    }
    if run.trace:
        conf.update({"tony.task.profile": "true",
                     "tony.task.profile.start-step": w["profile"]["start_step"],
                     "tony.task.profile.num-steps": w["profile"]["num_steps"],
                     "tony.trace.enabled": "true",
                     "tony.train.input-wait-span-ms": 0})
    conf_path = os.path.join(run.work, "job.json")
    with open(conf_path, "w") as f:
        json.dump(conf, f)
    spec_path = os.path.join(run.work, "bench_spec.json")
    with open(spec_path, "w") as f:
        json.dump({"config": w["config"], "deployment": w["deployment"], "seed": run.seed,
                   "out_dir": run.out_dir}, f)
    axes = " ".join(f"--{k} {v}" for k, v in sorted(w.get("axes", {}).items()))
    loop_flags = (
        f"--steps {w['steps']} "
        f"--schedule_steps {w['schedule_steps']} --batch_size {w['batch_size']} --seq_len {w['seq_len']} "
        f"--log_every {w['log_every']} --warmup_steps {w['warmup_steps']} "
        f"--learning_rate {w['learning_rate']} --data_dir {data_dir} "
        f"--data_seed {run.seed % (2 ** 31)} {axes}").strip()
    executes = f"{sys.executable} benchmark/entry/train_lm.py {loop_flags}"
    t_submit = time.time()
    out_path = os.path.join(run.work, "submit.out")
    proc = jobs.launch([*jobs.TONY, "submit", "--conf_file", conf_path, "--executes", executes],
                       run.staging, out_path, {"BENCH_SPEC": spec_path})

    def fail(msg: str) -> jobs.JobFailed:
        with open(out_path) as f:
            out = f.read()
        apps = jobs.app_dirs(run.staging)
        log = jobs.read_logs(apps[0], "worker_0") if apps else ""
        return jobs.JobFailed(f"{msg}\n--- submit\n{jobs.tail(out, 15)}\n--- worker\n{jobs.tail(log, 40)}")

    # set-up: until the loop has logged `warm_lines` windows
    device, t_device, lines, app = None, None, [], None
    deadline = t_submit + 1100  # a first run compiles: the contract gives it 1200 s
    # a traced run goes on until the program's profile window has closed
    last_step = w["profile"]["start_step"] + w["profile"]["num_steps"] + 1 if run.trace else 0
    t_open = None
    while True:
        if proc.poll() is not None:
            raise fail(f"`tony submit` ended (exit {proc.returncode}) before the window closed")
        if time.time() > deadline:
            raise fail("set-up ran past its limit")
        if device is None:
            device = jobs.read_json(os.path.join(run.out_dir, "device.json"))
            if device is not None:
                t_device = time.time()
                run.check_device(device)
        apps = jobs.app_dirs(run.staging)
        if apps:
            app = apps[0]
            lines = step_lines(app)
        if t_open is None and len(lines) >= w["warm_lines"]:
            t_open = lines[w["warm_lines"] - 1]["ts_ms"] / 1000.0
            deadline = t_open + run.seconds + 120
        if t_open is not None and lines and lines[-1]["ts_ms"] / 1000.0 >= t_open + run.seconds and lines[-1]["step"] >= last_step:
            break
        time.sleep(0.1)
    holders, others = jobs.chip_holders(run.staging)
    off_jax = all("train_lm.py" in c for c in holders)
    rc = jobs.stop_job(proc, run.staging, "train", app_dir=app)
    device = jobs.read_json(os.path.join(run.out_dir, "device.json")) or device
    run.check_device(device)

    window = [ln for ln in lines if t_open <= ln["ts_ms"] / 1000.0 <= t_open + run.seconds]
    steps = window[-1]["step"] - window[0]["step"]
    span_s = (window[-1]["ts_ms"] - window[0]["ts_ms"]) / 1000.0
    tok_s_chip = steps * tokens_per_step / span_s / run.chips
    setup_s = t_open - run.t_start
    say = jobs.say
    say(f"[train] app={os.path.basename(app)} client exit {rc} after the kill; device={json.dumps(device)}; "
        f"{others} launcher process(es) off JAX, {len(holders)} chip-holding child(ren)")
    say(f"[train] set-up {setup_s:.2f}s (submit -> device line {t_device - t_submit:.2f}s); window {span_s:.3f}s, "
        f"{steps} steps of {tokens_per_step} tokens between step lines {window[0]['step']}..{window[-1]['step']}")
    per_step = [((b["ts_ms"] - a["ts_ms"]) / 1000.0 / (b["step"] - a["step"]), b["step"]) for a, b in zip(window, window[1:])]
    say(f"[train] seconds a step by logging window: median {sorted(x for x, _ in per_step)[len(per_step) // 2]:.4f}, "
        f"slowest {max(per_step)[0]:.4f} (ending at step {max(per_step)[1]})")
    shown = lines if len(lines) <= 8 else lines[:4] + lines[-4:]
    say(f"[train] loss by logged step ({len(lines)} lines, ends shown) {[(ln['step'], ln['loss']) for ln in shown]}")
    say(f"[train] the loop's own figures, last line: tokens_per_sec={lines[-1].get('tokens_per_sec')} "
        f"mfu={lines[-1].get('mfu')} (its count, not the benchmark's)")

    # correct: the loop ran sound, and the program agrees with the reference
    losses = [ln["loss"] for ln in lines]
    limits = w["limits"]
    seeded = (jobs.read_json(os.path.join(run.out_dir, "weights.json")) or {}).get("from_seed") == run.seed
    compared = [
        f"loss finite at every line: {all(math.isfinite(x) for x in losses)}; "
        f"last {losses[-1]} below first {losses[0]}: {losses[-1] < losses[0]}",
        f"launcher processes off JAX: {off_jax}; the loop trained the seed's weights (entry/train_lm.py's "
        f"replacement of sharded_init ran): {seeded}",
    ]
    ok = all(math.isfinite(x) for x in losses) and losses[-1] < losses[0] and off_jax and seeded
    chk = jobs.compare_in_child(run, {"kind": "train", "loop_argv": shlex.split(loop_flags),
                                      "grad": "grad_rel_rms" in limits}, "train")
    for name, what in (("logit_rel_rms", f"last {CHECK_TAIL} positions' logits of one sequence"),
                       ("grad_rel_rms", "gradient of the step batch's mean loss by "
                                        f"{', '.join(chk.get('grad_rel_rms_by_leaf', {}))}, the worst of them")):
        if name not in limits:
            continue
        v = chk.get(name)
        compared.append(f"{name} = {v!r} (limit {limits[name]}; {what}, program against float32 reference)")
        ok = ok and v is not None and math.isfinite(v) and v <= limits[name]
        if "control_" + name in chk:
            compared.append(f"control_{name} = {chk['control_' + name]!r} (the control: must lie above the limit)")
    say(f"[train] the comparison's child took {chk.get('seconds')}s in {chk.get('tries')} tries (its peak on the fullest "
        f"chip {chk.get('child_memory_peak_bytes')} bytes): forward program {chk.get('program_s')}s, "
        f"reference {chk.get('reference_s')}s" + (
            f"; gradient program {chk['grad_program_s']}s, reference {chk['grad_reference_s']}s; gradient error by leaf "
            f"{chk['grad_rel_rms_by_leaf']}, the control's {chk.get('control_grad_rel_rms_by_leaf')}"
            if "grad_rel_rms" in chk else ""))
    # shown, not compared: the mean loss averages rounding away, so no lower precision fails it
    say(f"[also] loss through the program's chunked cross-entropy over the step's batch {chk.get('program_loss')!r}, "
        f"reference over its first sequence {chk.get('reference_loss')!r}, gap {chk.get('loss_gap')!r}; the control's gap {chk.get('control_loss_gap')!r}")

    ctx = {"run": run, "kind": "train", "app_dir": app, "t_open": t_open, "seconds": run.seconds,
           "launch_s": t_device - t_submit, "tokens_per_step": tokens_per_step,
           "device": device, "lines": lines, "trace": None, "tok_s_chip": tok_s_chip}
    dev_line = {k: device[k] for k in ("platform", "kind", "count")}
    dev_line["memory_peak_bytes"] = device.get("memory_peak_bytes", 0)
    breakdown = None
    if run.trace:
        tr = ctx["trace"] = trace_reduce.reduced_or_fail(run.staging, run.work, run.cpu_rehearsal)
        say(f"[trace] leaf operations cover {tr.get('module_cover')} of the captured programs' time")
        dev_line.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        breakdown = tr.get("breakdown")
    return {"correct": ok, "attempted": len(window), "failed": 0 if ok else 1,
            "end_to_end": {"train_tok_s_chip": tok_s_chip, "setup_s": setup_s},
            "compared": compared, "device": dev_line, "ctx": ctx, "breakdown": breakdown}
