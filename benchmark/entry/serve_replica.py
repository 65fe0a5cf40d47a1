"""The replica the benchmark's fleet runs: serving_http.main() with a
configuration registered from benchmark/configs/ and weights from the seed.

The configuration's family (families/<module>.py: its `serve_install`) puts
the cell's configuration where the program looks for it and hands the engine
the seed's weights (its reference's, one jitted call on the device), or says
that the program cannot serve that family yet; this entry then starts two
control threads in the process that holds the chip, because only that process
can trace it or read its memory and its registry:

  <out_dir>/device.json        device facts + peak memory, once a second
  <out_dir>/ctl/trace.req      {"seconds": s}: capture a jax.profiler trace of s
                               seconds into <out_dir>/trace, then write trace.done
  <out_dir>/ctl/snap.<id>.req  write the metrics registry to snap.<id>.json, stamped
                               `t` as it is read

The snapshots have a thread of their own (`snapshots`): a capture's
`stop_trace` exports for tens of seconds and the thread that called it answers
nothing meanwhile, which is how a traced run of the cell with the most slots
lost its registry (PERF.md section 6, PR 64).

BENCH_SPEC names the JSON the harness wrote (config, deployment, seed, out_dir,
and the workload's `engine` block).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from chipside import MemoryPeak, write_json  # noqa: E402


def snapshots(ctl: str) -> None:
    """Answer every snap.<id>.req with the registry as it stands, whatever the other thread is held by."""
    from tony_tpu.obs import metrics as obs_metrics

    while os.path.isdir(ctl):  # the run's directory goes with the run
        for name in sorted(os.listdir(ctl)):
            if name.startswith("snap.") and name.endswith(".req"):
                os.remove(os.path.join(ctl, name))
                write_json(os.path.join(ctl, name[:-4] + ".json"),
                           {"t": time.time(), "metrics": obs_metrics.REGISTRY.snapshot()})
        time.sleep(0.02)


def control(out_dir: str) -> None:
    import jax

    from tony_tpu.runtime import device_facts

    ctl = os.path.join(out_dir, "ctl")
    next_device, memory, facts = 0.0, MemoryPeak(), device_facts()
    while True:
        now = time.time()
        memory.sample()
        if now >= next_device:
            write_json(os.path.join(out_dir, "device.json"), {**facts, "memory_peak_bytes": memory.peak})
            next_device = now + 1.0
        path = os.path.join(ctl, "trace.req")
        if os.path.exists(path):
            with open(path) as f:
                req = json.load(f)
            os.remove(path)
            t0 = time.time()
            jax.profiler.start_trace(os.path.join(out_dir, "trace"))
            time.sleep(float(req["seconds"]))
            t1 = time.time()
            jax.profiler.stop_trace()
            write_json(os.path.join(ctl, "trace.done"), {"start": t0, "stop": t1, "end": time.time()})
        time.sleep(0.05)


def main() -> int:
    with open(os.environ["BENCH_SPEC"]) as f:
        bench = json.load(f)
    import families
    import spec

    sizes = spec.model_sizes(spec.config(bench["config"]), bench["deployment"])
    families.load(sizes["module"]).serve_install(sizes, bench)
    ctl = os.path.join(bench["out_dir"], "ctl")
    os.makedirs(ctl, exist_ok=True)
    threading.Thread(target=snapshots, args=(ctl,), daemon=True).start()
    threading.Thread(target=control, args=(bench["out_dir"],), daemon=True).start()
    from tony_tpu.models import serving_http

    return serving_http.main()


if __name__ == "__main__":
    sys.exit(main())
