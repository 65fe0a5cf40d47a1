"""Goodput-headline workload: jax-free stepper with a deliberate straggler.

Every rank advances one step per tick, publishing the train step report at
$TONY_TRAIN_METRICS_FILE and a registry snapshot (with a cumulative
``tony_train_step_seconds`` histogram) at the ``.obs`` sibling — exactly the
piggyback contract the real train loop honors — so the AM's goodput tick
sees live per-rank step times. The rank named by ``slow_rank`` sleeps
``slow_mult``× the base step, making it a detectable straggler. A tiny
step-counter "checkpoint" is persisted to the shared dir every
``ckpt_every`` steps and resumed after a gang restart, so the restart loses
a provable amount of work (the rework the ledger must attribute).

The ledger derives that rework from the AM's METRICS_SNAPSHOT events: the
resumed epoch's first recorded step against the steps the lost epoch had
recorded. With ``doomed_step`` (the N of the test's ``@step+N`` chaos gate)
the fixture owns both ends instead of racing the AM's snapshot timer: the
first attempt runs step N only once the AM has recorded step N-1, and stops
there (the gate opens on it; nothing checkpoints past it), and a restarted
attempt runs its second step only once the AM has recorded its first.

Usage: goodput_train.py <shared_dir> <steps> <base_ms> <slow_rank> <slow_mult> <ckpt_every> [doomed_step]
"""

import json
import os
import sys
import time

from tony_tpu.obs import artifacts as obs_artifacts
from tony_tpu.obs import goodput as obs_goodput
from tony_tpu.obs import metrics as obs_metrics

began_ms = int(time.time() * 1000)
shared, steps, base_ms, slow_rank, slow_mult, ckpt_every = (
    sys.argv[1], int(sys.argv[2]), float(sys.argv[3]), int(sys.argv[4]),
    float(sys.argv[5]), int(sys.argv[6]))
doomed_step = int(sys.argv[7]) if len(sys.argv) > 7 else 0
rank = int(os.environ["TASK_INDEX"])
metrics_path = os.environ["TONY_TRAIN_METRICS_FILE"]
attempt = int(os.environ.get("TONY_RESTART_ATTEMPT", "0"))

step_s = base_ms / 1000.0 * (slow_mult if rank == slow_rank else 1.0)
hist = obs_metrics.histogram(
    "tony_train_step_seconds", "per-step wall time")

ckpt_path = os.path.join(shared, "ckpt.json")
start = 0
try:
    with open(ckpt_path) as f:
        start = int(json.load(f)["step"])
    print(f"fixture: rank {rank} resumed from checkpoint step {start}")
except (OSError, ValueError, KeyError):
    pass


def drop(path, obj):
    tmp = f"{path}.tmp{rank}"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def wait_recorded(step, since_ms):
    """Block until the job's event stream holds a METRICS_SNAPSHOT, written at
    or after ``since_ms``, in which some task is at ``step`` or past it (read
    as the ledger reads it)."""
    staging_root = os.path.dirname(os.environ["TONY_STAGING_DIR"].rstrip("/"))
    while True:
        events, _ = obs_artifacts.index(staging_root, os.environ["TONY_APP_ID"]).read_events()
        if any(
            ev.type.value == "METRICS_SNAPSHOT" and ev.timestamp_ms >= since_ms
            and max(obs_goodput._snapshot_steps(ev).values(), default=0) >= step
            for ev in events
        ):
            return
        time.sleep(0.05)


for s in range(start + 1, steps + 1):
    time.sleep(step_s)
    hist.observe(step_s)
    drop(metrics_path, {
        "step": s,
        "loss": round(2.0 / s, 4),
        "mfu": round(0.4 + 0.001 * s, 4),
        "tokens_per_sec": 1000.0 + s,
    })
    drop(metrics_path + ".obs",
         [m for m in obs_metrics.REGISTRY.snapshot() if m["samples"]])
    if rank == 0 and s % ckpt_every == 0:
        drop(ckpt_path, {"step": s})
    if not doomed_step:
        continue
    if attempt > 0 and s == start + 1:
        wait_recorded(s, began_ms)
    elif attempt == 0 and s == doomed_step - 1:
        wait_recorded(s, 0)
    elif attempt == 0 and s == doomed_step:
        print(f"fixture: rank {rank} holds at step {s} for the chaos gate", flush=True)
        while True:
            time.sleep(1.0)

print(f"fixture: rank {rank} attempt {attempt} finished at step {steps}")
