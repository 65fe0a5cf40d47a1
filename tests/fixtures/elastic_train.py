"""Fixture: the elastic workload for the node-lost → run-smaller E2E.

Attempt 0: a 2-process gang trains 4 steps (checkpoints at 2 and 4), then
sleeps so the test can SIGKILL one node for good. The AM's capacity re-check
downsizes the gang (tony.worker.min-instances=1) and attempt 1 — ONE process
— resumes from the checkpoint onto the smaller mesh and trains to step 8.
The global-order loader replays the exact sample stream across the shard-
count change (data/loader.py contract), so the final loss matches an
uninterrupted fixed-shape reference run up to reduction-order noise.

Usage: elastic_train.py <data_dir> <ckpt_dir>
"""

import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

# 2 virtual CPU devices per process: attempt 0 meshes over 4 global devices,
# the downsized attempt 1 over 2 — a REAL cross-shape restore
os.environ["JAX_PLATFORMS"] = "cpu"
flags = re.sub(
    r"--xla_force_host_platform_device_count=\d+", "", os.environ.get("XLA_FLAGS", "")
).strip()
os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=2").strip()

from tony_tpu.models import llama  # noqa: E402
from tony_tpu.train.loop import LoopConfig, run_lm_training  # noqa: E402

data_dir, ckpt_dir = sys.argv[1], sys.argv[2]
attempt = int(os.environ.get("TONY_RESTART_ATTEMPT", "0"))
steps = 4 if attempt == 0 else 8
out = run_lm_training(
    llama, llama.LLAMA_TINY,
    LoopConfig(
        steps=steps, schedule_steps=8, batch_size=4, seq_len=64, log_every=1,
        warmup_steps=0, data_dir=data_dir, checkpoint_dir=ckpt_dir,
        checkpoint_every=2,
    ),
)
import jax  # noqa: E402

print(
    f"elastic attempt {attempt}: step={int(out['step'])} "
    f"loss={out['loss']:.6f} procs={jax.process_count()}",
    flush=True,
)
if attempt == 0:
    time.sleep(600)  # hold the gang so the test can kill a node mid-run
sys.exit(0)
