"""Fixture: the headline elastic-chaos workload (preempt → shrink → resume).

A data-parallel training gang WITHOUT cross-process XLA (the CPU backend in
the test image cannot compile multi-process computations): every rank draws
its own slice of the global stream through the real ``TokenLoader``
global-order contract and records a content hash per consumed local batch;
rank 0 additionally runs a real (single-device) train state with Orbax
checkpoints through ``restore_or_init`` and persists the consumption cursor.

A file-based handshake emulates the per-step collective of a real SPMD gang,
preserving its two elastic-critical invariants: (a) no rank runs more than
one step ahead of rank 0, so the AM's ``@step+N`` gate (fed from pushed
metrics) cannot open before the step-gated checkpoint is finalized, and
(b) rank 0 saves checkpoint ``s`` only after EVERY rank has published step
``s`` — a restored checkpoint therefore proves the whole gang consumed all
global batches below it, which is exactly what the test's exactly-once
accounting replays.

Attempt 0 gets an oversized step budget so the chaos
``preempt:worker:*@step+4`` faults always fire mid-run; after the AM's
shrink-on-preempt rebuild, the resumed attempt re-reads rank 0's published
resume step, validates the consumption cursor, and finishes at the SMALLER
world size.

Usage: elastic_chaos_train.py <data_dir> <shared_dir> <steps>
"""

import hashlib
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

from tony_tpu import constants  # noqa: E402
from tony_tpu.data import TokenLoader  # noqa: E402
from tony_tpu.data.dataset import ConsumptionCursor  # noqa: E402
from tony_tpu.train.checkpoint import restore_or_init  # noqa: E402

data_dir, shared_dir, total_steps = sys.argv[1], sys.argv[2], int(sys.argv[3])
attempt = int(os.environ.get("TONY_RESTART_ATTEMPT", "0"))
rank = int(os.environ[constants.ENV_JAX_PROCESS_ID])
world = int(os.environ[constants.ENV_JAX_NUM_PROCESSES])
GLOBAL_BATCH, SEQ, SEED = 4, 64, 0
local_rows = GLOBAL_BATCH // world
ckpt_dir = os.path.join(shared_dir, "ckpt")
os.makedirs(shared_dir, exist_ok=True)

# attempt 0 exists to BE preempted: a 10x budget guarantees the step-gated
# faults fire mid-run; resumed (post-shrink) attempts train to the target
steps = total_steps * 10 if attempt == 0 else total_steps


def _publish(path: str, step: int) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"step": step}, f)
    os.replace(tmp, path)


def _read_step(path: str, default: int) -> int:
    try:
        with open(path) as f:
            return int(json.load(f)["step"])
    except (OSError, ValueError, KeyError):
        return default


def _wait(cond, what: str) -> None:
    deadline = time.monotonic() + 120
    while not cond():
        if time.monotonic() > deadline:
            raise RuntimeError(f"timed out waiting for {what}")
        time.sleep(0.01)


def _progress(r: int) -> str:
    return os.path.join(shared_dir, f"progress-a{attempt}-r{r}.json")


# -- resume point: rank 0 restores (corruption-tolerant) and PUBLISHES the
# step; peers wait for it so every rank replays from the same global batch
resume_file = os.path.join(shared_dir, f"resume-{attempt}.json")
if rank == 0:
    state, mgr, start = restore_or_init(
        ckpt_dir, lambda: {"w": np.zeros(4, np.float64)}, use_async=False)
    if start:
        print(f"[train] resumed from checkpoint step {start}", flush=True)
        cursor = ConsumptionCursor.load(ckpt_dir, start)
        if cursor is not None:
            cursor.validate_resume(GLOBAL_BATCH, SEED, start)
            print(f"[train] data cursor validated: resuming the global stream "
                  f"at batch {start} (written at world size "
                  f"{cursor.world_size}, now {world})", flush=True)
    _publish(resume_file, start)
else:
    state, mgr = None, None
    _wait(lambda: os.path.exists(resume_file), "rank 0's resume step")
    start = _read_step(resume_file, 0)

loader = TokenLoader(
    sorted(Path(data_dir).glob("*.tonytok")), local_rows, SEQ,
    shard_id=rank, num_shards=world, seed=SEED, start_index=start,
)
record = open(os.path.join(shared_dir, f"consumed-a{attempt}-r{rank}.jsonl"), "a", buffering=1)
metrics_file = os.environ.get(constants.ENV_TRAIN_METRICS_FILE)

loss = float("nan")
try:
    for t in range(start, steps):
        if rank != 0:
            # the collective-lockstep bound: never run >1 step ahead of the
            # checkpointing rank, so a step the AM sees reported implies the
            # gated checkpoint below it is already finalized
            _wait(lambda: _read_step(_progress(0), start) >= t, f"rank 0 to reach step {t}")
        batch = loader.next()  # [local_rows, SEQ+1] rows of global batch t
        record.write(json.dumps({
            "attempt": attempt, "world": world, "rank": rank, "t": t,
            "sha1": hashlib.sha1(np.ascontiguousarray(batch).tobytes()).hexdigest(),
        }) + "\n")
        if rank == 0:
            # a real (single-device) optimizer step + periodic checkpoint,
            # so resume-from-the-smaller-gang restores genuine Orbax state
            state["w"] = state["w"] * 0.9 + float(batch.mean()) * 0.1
            loss = float(np.abs(state["w"]).mean())
            if (t + 1) % 2 == 0:
                # the collective invariant: a checkpoint at step s exists
                # only once EVERY rank has consumed the batches below s
                _wait(
                    lambda: all(_read_step(_progress(r), start) >= t + 1 for r in range(1, world)),
                    f"the gang to finish step {t + 1}",
                )
                # the cursor first (a file a step): a rank killed between the
                # two must not leave a checkpoint whose cursor is missing
                ConsumptionCursor(
                    global_batch_index=t + 1, global_batch_size=GLOBAL_BATCH,
                    seed=SEED, world_size=world,
                ).save(ckpt_dir)
                mgr.save(t + 1, state, force=True)
        _publish(_progress(rank), t + 1)
        if metrics_file:
            # the executor piggybacks this on its heartbeat — the AM's chaos
            # context feeds @step+N gates from exactly this report
            tmp = metrics_file + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"step": t + 1, "loss": loss}, f)
            os.replace(tmp, metrics_file)
        time.sleep(0.05)  # paces the run so mid-run preemption lands mid-run
finally:
    loader.close()
    record.close()
    if mgr is not None:
        mgr.close()

print(f"elastic-chaos attempt {attempt}: rank={rank} step={steps} world={world}", flush=True)
sys.exit(0)
