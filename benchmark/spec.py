"""What the data files say: BENCHMARK.json, configurations, workloads, metrics.

Everything that belongs to one configuration, one traffic mix, one per-layer
metric or one model family is a file found by its name, so a later PR adds
files and entries and edits nothing here:

  configs/<config>.json      the published sizes (HF key names), depth by deployment,
                             and `module`: the family that reads them
  workloads/<cell>.json      kind (train|serve), deployment, and the traffic parameters
  metrics/<metric>.json      the reader module under readers/ and its arguments
  families/<module>.py       a family's sizes, program, serving hook, reference and counts
                             (what each has to be: families/__init__.py)

A new cell of a configuration that is here: workloads/<config>.<traffic>.json
and its BENCHMARK.json entry; a per-layer metric that no file reads yet adds
metrics/<metric>.json and, where no reader fits, readers/<reader>.py. A new
configuration of a family that is here adds configs/<config>.json besides. A
new family adds families/<module>.py, the reference and the counts it names
(its own files, or a family's that are here), and a `tiny-*` configuration and
workloads at a size the CPU holds, which its tests under tests/ rehearse. A
workload of a new `kind` adds <kind>_cell.py.

No JAX here: run.py imports this while a child holds the chip.
"""

from __future__ import annotations

import json
import os
import re

import families

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
#: positions at the end of the sequence whose logits the training comparison reads
#: (past the sliding window's edge when the sequence is longer than the band)
CHECK_TAIL = 256


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def workload(name: str) -> dict:
    """The cell's file merged over its BENCHMARK.json entry (config, chips)."""
    if not NAME_RE.match(name):
        raise ValueError(f"not a workload name: {name!r}")
    w = load_json("workloads", name + ".json")
    entry = next((e for e in benchmark()["workloads"] if e["name"] == name), None)
    if entry is not None:
        for k in ("config", "chips"):
            if k in w and w[k] != entry[k]:
                raise ValueError(f"{name}: {k} is {w[k]!r} in its file and {entry[k]!r} in BENCHMARK.json")
            w[k] = entry[k]
    w["name"] = name
    return w


def config(name: str) -> dict:
    if not NAME_RE.match(name):
        raise ValueError(f"not a configuration name: {name!r}")
    return load_json("configs", name + ".json")


def metric(name: str) -> dict:
    return load_json("metrics", name + ".json")


def model_sizes(cfg: dict, deployment: str) -> dict:
    """The sizes the family's program, reference and counts work from, as plain
    numbers: the configuration's `module` names the family that reads them."""
    return families.load(cfg["module"]).sizes(cfg, deployment)


def cell_metrics(bench: dict, cell: str, section: str) -> list[dict]:
    """The metrics of one section that this cell reports: those that name it
    under `workloads`, and those with no such key (every cell)."""
    return [m for m in bench[section] if cell in m.get("workloads", [cell])]
