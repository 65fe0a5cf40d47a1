"""The training job the benchmark submits: `tony submit --executes "python
benchmark/entry/train_lm.py <loop flags>"`.

A training job is a user's script by design (the examples' pretrain.py is
twenty lines around run_lm_training); this is the benchmark's. It differs from
the examples in two ways, neither of them inside the program: the model's
sizes come from benchmark/configs/<name>.json (the program takes presets by
name only) through the configuration's family (families/<module>.py: its
`program`), and the weights come from --seed through the family's reference
(the loop's own are PRNGKey(0) whatever the seed: they are replaced once
drawn, and <out_dir>/weights.json says that they were). The comparison with
the reference is not here: the harness runs it in a child of its own once
this job has been stopped (check.py), on the same seed's weights.

BENCH_SPEC names a JSON file the harness wrote: config, deployment, seed,
out_dir. The device's facts and peak memory go to <out_dir>/device.json once
a second, for the harness to read after the job has been stopped.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from chipside import MemoryPeak, sharded_weights, write_json  # noqa: E402


def device_report(out_dir: str, stop: threading.Event) -> None:

    from tony_tpu.runtime import device_facts

    memory, facts = MemoryPeak(), device_facts()
    while True:
        for _ in range(5):
            memory.sample()
            if stop.wait(0.2):
                return
        write_json(os.path.join(out_dir, "device.json"), {**facts, "memory_peak_bytes": memory.sample()})


def main() -> int:
    with open(os.environ["BENCH_SPEC"]) as f:
        bench = json.load(f)
    import families
    import spec
    from tony_tpu.runtime import enable_compile_cache
    from tony_tpu.train.loop import parse_loop_args, run_lm_training

    loop, _ = parse_loop_args()
    sizes = spec.model_sizes(spec.config(bench["config"]), bench["deployment"])
    module, cfg = families.load(sizes["module"]).program(sizes, loop.seq_len)
    enable_compile_cache()
    stop = threading.Event()
    threading.Thread(target=device_report, args=(bench["out_dir"], stop), daemon=True).start()

    # The program's loop, handed the seed's weights in place of its PRNGKey(0).
    # The loop draws its weights inside a jit that takes no argument, so a key
    # from the seed would be a constant of that program and every new seed
    # would compile it again (22-29 s at this width, against 0.04 s to run it:
    # my chip run, PR 24). So the loop's own sharded_init runs as it is, with
    # its fixed key (one program, found in the compile cache), and the
    # parameters it returns are replaced by the seed's, drawn by a program
    # that takes the key as an argument. Adam's state starts at zero either way.
    # If the program renames or inlines that call, this replacement does
    # nothing and the loop trains other weights than the comparison reads:
    # so the name has to exist here, and the harness takes a run for correct
    # only if weights.json says the replacement ran.
    from tony_tpu.train import loop as loop_module

    program_sharded_init = loop_module.sharded_init  # AttributeError: the program changed, mend this script

    def sharded_init_from_seed(init_fn, rules, mesh, optimizer):
        state = program_sharded_init(init_fn, rules, mesh, optimizer)
        state = dataclasses.replace(state, params=sharded_weights(module, cfg, mesh, sizes, bench["seed"]))
        write_json(os.path.join(bench["out_dir"], "weights.json"), {"from_seed": bench["seed"]})
        return state

    loop_module.sharded_init = sharded_init_from_seed
    run_lm_training(module, cfg, loop)
    stop.set()
    return 0


if __name__ == "__main__":
    sys.exit(main())
