#!/usr/bin/env python3
"""Builder-side: the readings a training cell's limits are set from, in one process.

    python benchmark/limits.py --workload <cell> --seeds 12 --control-seeds 3 [--first-seed n]

"How `correct` is decided" asks for the largest number that sound runs give
over a dozen seeds and the smallest that the control gives over three, at the
cell's own size, before any limit is set. This process holds the chip and runs
the comparison of check.py (the same programs the harness's child runs) for
each seed, prints a line a seed and the two readings at the end. No job is
launched and nothing is timed: the benchmark's own runs never call it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=2_500_000_011)
    p.add_argument("--grad", type=int, choices=(0, 1), default=1)
    args = p.parse_args()
    import check
    import spec
    from tony_tpu.runtime import device_facts, enable_compile_cache

    enable_compile_cache()
    w = spec.workload(args.workload)
    axes = [x for k, v in sorted(w.get("axes", {}).items()) for x in (f"--{k}", str(v))]
    comparison, weights, rows = check.train_setup(
        w["config"], w["deployment"], ["--batch_size", str(w["batch_size"]), "--seq_len", str(w["seq_len"]), *axes])
    print(json.dumps(device_facts()), flush=True)
    names = ["logit_rel_rms"] + (["grad_rel_rms"] if args.grad else [])
    sound, control = {n: [] for n in names}, {n: [] for n in names}
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        r = comparison.run(weights(seed), seed, rows, control=i < args.control_seeds, grad=bool(args.grad))
        print(json.dumps({"seed": seed, **r}), flush=True)
        for n in names:
            sound[n].append(r[n])
            if "control_" + n in r:
                control[n].append(r["control_" + n])
    for n in names:
        print(f"{n}: sound runs' largest {max(sound[n])!r} over {len(sound[n])} seeds; the control's smallest "
              f"{min(control[n]) if control[n] else None!r} over {len(control[n])} seeds", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
