"""The replica the benchmark's fleet runs: serving_http.main() with a
configuration registered from benchmark/configs/ and weights from the seed.

The configuration's family (families/<module>.py: its `serve_install`) puts
the cell's configuration where the program looks for it and hands the engine
the seed's weights (its reference's, one jitted call on the device), or says
that the program cannot serve that family yet; this entry then starts a
control thread in the process that holds the chip, because only that process
can trace it or read its memory:

  <out_dir>/device.json        device facts + peak memory, once a second
  <out_dir>/ctl/trace.req      {"seconds": s}: capture a jax.profiler trace of s
                               seconds into <out_dir>/trace, then write trace.done
  <out_dir>/ctl/snap.<id>.req  write the metrics registry to snap.<id>.json

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


def control(out_dir: str) -> None:
    import jax

    from tony_tpu.obs import metrics as obs_metrics
    from tony_tpu.runtime import device_facts

    ctl = os.path.join(out_dir, "ctl")
    os.makedirs(ctl, exist_ok=True)
    next_device, memory, facts = 0.0, MemoryPeak(), device_facts()
    while True:
        now = time.time()
        memory.sample()
        if now >= next_device:
            write_json(os.path.join(out_dir, "device.json"), {**facts, "memory_peak_bytes": memory.peak})
            next_device = now + 1.0
        for name in sorted(os.listdir(ctl)):
            path = os.path.join(ctl, name)
            if name == "trace.req":
                with open(path) as f:
                    req = json.load(f)
                os.remove(path)
                t0 = time.time()
                jax.profiler.start_trace(os.path.join(out_dir, "trace"))
                time.sleep(float(req["seconds"]))
                jax.profiler.stop_trace()
                write_json(os.path.join(ctl, "trace.done"), {"start": t0, "end": time.time()})
            elif name.startswith("snap.") and name.endswith(".req"):
                os.remove(path)
                write_json(os.path.join(ctl, name[:-4] + ".json"),
                           {"t": time.time(), "metrics": obs_metrics.REGISTRY.snapshot()})
        time.sleep(0.05)


def main() -> int:
    with open(os.environ["BENCH_SPEC"]) as f:
        bench = json.load(f)
    import families
    import spec

    sizes = spec.model_sizes(spec.config(bench["config"]), bench["deployment"])
    families.load(sizes["module"]).serve_install(sizes, bench)
    threading.Thread(target=control, args=(bench["out_dir"],), daemon=True).start()
    from tony_tpu.models import serving_http

    return serving_http.main()


if __name__ == "__main__":
    sys.exit(main())
