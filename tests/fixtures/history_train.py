"""History-server workload: quick jax-free steps publishing train metrics.

Each step it atomically drops ``{"step": N, "loss": ..., "mfu": ...,
"tokens_per_sec": ...}`` at $TONY_TRAIN_METRICS_FILE; the executor's metrics
push feeds the AM, whose METRICS_SNAPSHOT events become the series the
history server distills — so the e2e can assert a real MFU trend across two
ingested runs.

Usage: history_train.py <steps> <mfu_base>
"""

import json
import os
import sys
import time

steps, mfu_base = int(sys.argv[1]), float(sys.argv[2])
metrics_path = os.environ["TONY_TRAIN_METRICS_FILE"]

for s in range(1, steps + 1):
    tmp = metrics_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({
            "step": s,
            "loss": round(2.0 / s, 4),
            "mfu": round(mfu_base + 0.002 * s, 4),
            "tokens_per_sec": 1000.0 + 10 * s,
        }, f)
    os.replace(tmp, metrics_path)
    time.sleep(0.12)

# the executor pushes the step report on its own clock and makes no last
# push when the child exits: wait until the AM holds the final step (a loaded
# box can starve the push thread past any fixed sleep), then finish
from tony_tpu.cluster.rpc import RpcClient, RpcError  # noqa: E402

rpc = RpcClient(os.environ["TONY_AM_HOST"], int(os.environ["TONY_AM_PORT"]),
                secret=os.environ.get("TONY_AM_SECRET", ""), timeout_s=5.0)
deadline = time.time() + 60
while time.time() < deadline:
    try:
        seen = [(t.get("metrics") or {}).get("train") or {} for t in rpc.call("get_task_infos")]
    except (RpcError, OSError):
        seen = []
    if any(m.get("step") == steps for m in seen):
        break
    time.sleep(0.1)

print(f"fixture: history worker finished {steps} steps")
