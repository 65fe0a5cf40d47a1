"""What the data files say: BENCHMARK.json, configurations, workloads, metrics.

Everything that belongs to one configuration, one traffic mix or one per-layer
metric is a file found by its name, so a later PR adds files and entries and
edits nothing here:

  configs/<config>.json      the published sizes (HF key names), depth by deployment
  workloads/<cell>.json      kind (train|serve), deployment, and the traffic parameters
  metrics/<metric>.json      the reader module under readers/ and its arguments

No JAX here: run.py imports this while a child holds the chip.
"""

from __future__ import annotations

import json
import os
import re

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
    """The sizes counts.py and reference.py work from, as plain numbers."""
    depth = cfg["num_hidden_layers"]
    if isinstance(depth, dict):
        if deployment not in depth:
            raise KeyError(f"configuration has no depth for deployment {deployment!r}: {sorted(depth)}")
        depth = depth[deployment]
    return {
        "module": cfg["module"],
        "vocab": cfg["vocab_size"],
        "d_model": cfg["hidden_size"],
        "layers": int(depth),
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
        "d_ff": cfg["intermediate_size"],
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "window": int(cfg.get("sliding_window") or 0),
        "experts": int(cfg.get("num_local_experts") or 0),
        "top_k": int(cfg.get("num_experts_per_tok") or 0),
        "dtype": cfg.get("torch_dtype", "bfloat16"),
    }


def program_config_fields(sizes: dict, max_seq: int) -> dict:
    """The same sizes under the field names of the program's config dataclass
    (models/llama.py LlamaConfig, models/mixtral.py MixtralConfig)."""
    fields = {
        "vocab_size": sizes["vocab"], "d_model": sizes["d_model"], "n_layers": sizes["layers"],
        "n_heads": sizes["heads"], "n_kv_heads": sizes["kv_heads"], "d_ff": sizes["d_ff"],
        "max_seq": max_seq, "rope_theta": sizes["rope_theta"], "norm_eps": sizes["norm_eps"],
        "dtype": sizes["dtype"], "sliding_window": sizes["window"],
    }
    if sizes["module"] == "mixtral":
        fields.update(num_experts=sizes["experts"], top_k=sizes["top_k"])
    return fields


def cell_metrics(bench: dict, cell: str, section: str) -> list[dict]:
    """The metrics of one section that this cell reports: those that name it
    under `workloads`, and those with no such key (every cell)."""
    return [m for m in bench[section] if cell in m.get("workloads", [cell])]
