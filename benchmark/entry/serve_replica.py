"""The replica the benchmark's fleet runs: serving_http.main() with a
configuration registered from benchmark/configs/ and weights from the seed.

`serving_http` takes a model as a preset name and draws its weights with
`llama.init`; this entry registers the cell's configuration under its name in
`llama.PRESETS` (the dict serving_http imported), hands the engine the seed's
weights (reference.init_weights, one jitted call on the device), and starts a
control thread in the process that holds the chip, because only that process
can trace it or read its memory:

  <out_dir>/device.json        device facts + peak memory, once a second
  <out_dir>/ctl/trace.req      {"seconds": s}: capture a jax.profiler trace of s
                               seconds into <out_dir>/trace, then write trace.done
  <out_dir>/ctl/snap.<id>.req  write the metrics registry to snap.<id>.json

BENCH_SPEC names the JSON the harness wrote (config, deployment, seed, out_dir).
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
    import jax

    import reference
    import spec
    from tony_tpu.models import llama, serving_http

    sizes = spec.model_sizes(spec.config(bench["config"]), bench["deployment"])
    if sizes["module"] != "llama":
        raise SystemExit("serving_http builds llama-family weights only: a Mixtral replica waits for the program")
    llama.PRESETS[bench["config"]] = llama.config_from_dict(
        spec.program_config_fields(sizes, bench["max_len"]))
    key = reference.seed_key(bench["seed"])
    # the key is an argument of the jitted draw: one program for every seed, so
    # the compile cache holds it after the first run (22-29 s to compile, 0.04 s to run)
    serving_http.init = lambda _key, _cfg: jax.jit(lambda k: reference.init_weights(k, sizes))(key)
    threading.Thread(target=control, args=(bench["out_dir"],), daemon=True).start()
    return serving_http.main()


if __name__ == "__main__":
    sys.exit(main())
