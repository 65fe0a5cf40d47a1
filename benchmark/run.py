#!/usr/bin/env python3
"""The benchmark's command: one run of one cell.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell is a file under workloads/ (its kind, deployment and traffic), over a
configuration under configs/, whose `module` names its family under families/
(sizes, program, serving hook, reference, counts). `train` cells submit benchmark/entry/train_lm.py
through `tony submit`; `serve` cells bring a fleet up through the `tony serve`
path (entry/serve_launch.py) and drive it from this process. This process
never imports JAX: the job's child owns the chip, and what the last line says
of the device is what that child reported.

With --trace 0 the last line carries the cell's end-to-end metrics, with
--trace 1 its per-layer metrics (metrics/<name>.json -> readers/<reader>.py)
and the device's busy time and breakdown from the profiler's trace.

A cell listed in BENCHMARK.json runs on the TPU with the chips it asks for or
fails: no fallback. A workload file that BENCHMARK.json does not list (the
tiny-* rehearsals) runs wherever the caller's JAX_PLATFORMS puts it, and the
last line says where that was.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import time

T_START = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))  # the program's launcher-side modules (no JAX)

import families  # noqa: E402
import jobs  # noqa: E402
import spec  # noqa: E402


say = jobs.say


class Run:
    """What one run knows: arguments, files, the work directory."""

    def __init__(self, args: argparse.Namespace):
        self.bench = spec.benchmark()
        self.w = spec.workload(args.workload)
        self.cell = args.workload
        self.listed = any(e["name"] == self.cell for e in self.bench["workloads"])
        self.cfg = spec.config(self.w["config"])
        self.sizes = spec.model_sizes(self.cfg, self.w["deployment"])
        self.seed, self.seconds, self.trace = args.seed, args.seconds, bool(args.trace)
        self.control = bool(args.control)
        self.chips = int(self.w["chips"])
        self.work = os.path.join(spec.ROOT, ".bench_work", self.cell)
        shutil.rmtree(self.work, ignore_errors=True)
        self.staging = os.path.join(self.work, "staging")
        self.out_dir = os.path.join(self.work, "out")
        os.makedirs(self.out_dir)
        os.makedirs(self.staging)
        self.t_start = T_START
        self.cpu_rehearsal = (not self.listed) and os.environ.get("JAX_PLATFORMS") == "cpu"
        self.pool = "local:cpu" if self.cpu_rehearsal else f"local:v5e-{self.chips}"
        self.peaks = spec.load_json("peaks.json")

    def check_device(self, device: dict) -> None:
        """A listed cell runs on the TPU with its chips, or not at all."""
        if self.cpu_rehearsal:
            return
        if device.get("platform") != "tpu" or device.get("count") != self.chips:
            raise jobs.JobFailed(
                f"{self.cell} needs {self.chips} TPU chip(s); the job's child found "
                f"{device.get('platform')!r} x {device.get('count')}. A rehearsal is a tiny-* workload "
                "under JAX_PLATFORMS=cpu")


def finish(run: Run, result: dict) -> int:
    """Metrics by name, the comparison beside its limits, the last line."""
    section = "per_layer" if run.trace else "end_to_end"
    wanted = spec.cell_metrics(run.bench, run.cell, section) if run.listed else None
    ctx = result["ctx"]
    values: dict[str, float] = {} if run.trace else dict(result["end_to_end"])
    if run.trace:
        names = [m["name"] for m in wanted] if wanted is not None else sorted(
            f[:-5] for f in os.listdir(os.path.join(HERE, "metrics")) if f.endswith(".json"))
        for name in names:
            m = spec.metric(name)
            if wanted is None and run.w["kind"] not in m.get("kinds", [run.w["kind"]]):
                continue
            reader = importlib.import_module("readers." + m["reader"])
            v = reader.read(ctx, **m.get("args", {}))
            if v is not None:
                values[name] = float(v)
    units = {m["name"]: m["unit"] for m in run.bench["end_to_end"] + run.bench["per_layer"]}
    metrics = {}
    for name, v in values.items():
        if wanted is not None and name not in {m["name"] for m in wanted}:
            say(f"[also] {name} = {v!r}")
            continue
        unit = units.get(name) or spec.metric(name)["unit"]
        metrics[name] = {"value": v, "unit": unit}
    for line in result["compared"]:
        say(f"[correct] {line}")
        print(f"[correct] {line}", file=sys.stderr)  # of a run that is not correct the driver keeps stderr's end
    last = {"correct": bool(result["correct"]), "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": result["device"]}
    if run.trace and result.get("breakdown"):
        last["breakdown"] = result["breakdown"]
    print(json.dumps(last), flush=True)
    return 0


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", type=int, choices=(0, 1), default=0,
                   help="1: also compute the control of the comparison (the family's reference at its control's "
                        "precision, in the program's place) and print it; for setting limits, never in a check's runs")
    args = p.parse_args()
    if not os.path.isdir(os.path.join(spec.ROOT, "tony_tpu")):
        print(f"benchmark: {spec.ROOT} holds no tony_tpu package: nothing to measure", file=sys.stderr)
        return 2
    try:
        run = Run(args)
    except families.NoFamily as e:  # before any job is launched, not 20 s later in the job's child
        print(f"benchmark: {args.workload}: {e}", file=sys.stderr)
        return 2
    kind = importlib.import_module(run.w["kind"] + "_cell")
    try:
        result = kind.run(run)
    except jobs.JobFailed as e:
        jobs.kill_all(run.staging)
        print(f"benchmark: {run.cell} FAILED after {time.time() - T_START:.0f}s: {e}", file=sys.stderr)
        return 1
    finally:
        jobs.kill_all(run.staging)
    return finish(run, result)


if __name__ == "__main__":
    sys.exit(main())
