"""The exaone_moe family: K-EXAONE, a decoder with a leading dense layer, then
layers whose FFN is routed (sigmoid scores, a choosing bias, a shared expert),
window attention on three layers in four and full attention on the fourth; it
runs through `tony_tpu/models/exaone_moe.py`. What a family answers for:
families/__init__.py. No JAX at the top level.

The published configuration gives every size and switch and no equation. What
it does not give stands under the configuration's `assumed`, each entry
{"value", "why"}: choices this family computes one value of and refuses any
other. A key that is cut for a deployment is {"source": ..., "<deployment>": ...}.
"""

from __future__ import annotations

import os

from families import NoFamily, depth, known_keys_only

REFERENCE = "families.exaone_moe_reference"
COUNTS = "families.exaone_moe_counts"

#: published keys whose value names the model and changes no arithmetic here
DESCRIBES = ("model_type", "max_position_embeddings", "sliding_window_pattern", "torch_dtype")
#: published keys this family computes one value of, and what that value is
FIXED = {"hidden_act": "silu", "tie_word_embeddings": False, "scoring_func": "sigmoid", "norm_topk_prob": True,
         "n_group": 1, "topk_group": 1}
SIZES = ("vocab_size", "hidden_size", "num_hidden_layers", "layer_types", "mlp_layer_types", "sliding_windows",
         "sliding_window", "num_attention_heads", "num_key_value_heads", "head_dim", "intermediate_size",
         "moe_intermediate_size", "num_experts", "num_experts_per_tok", "num_shared_experts", "routed_scaling_factor",
         "first_k_dense_replace", "rope_parameters", "rms_norm_eps", "num_nextn_predict_layers", "mtp_layer_types",
         "mtp_sliding_windows")
#: `assumed` entries that are choices: the one value of each that this family computes
ASSUMED_CHOICES = {"block": "pre_norm", "qk_norm": "rmsnorm_per_head", "rope": "sliding_layers_only",
                   "router_bias": "chooses_does_not_weigh", "mtp": "proj_of_two_norms_then_one_full_routed_layer"}
KINDS = ("sliding_attention", "full_attention")

#: the program's model module, beside which this family's files mean anything
PROGRAM = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
                       "tony_tpu", "models", "exaone_moe.py")


def _by_deployment(cfg: dict, key: str, deployment: str):
    value = cfg[key]
    if isinstance(value, dict) and "source" in value:
        if deployment not in value:
            raise KeyError(f"configuration has no {key} for deployment {deployment!r}: {sorted(value)}")
        return value[deployment]
    return value


def sizes(cfg: dict, deployment: str) -> dict:
    if not os.path.isfile(PROGRAM):
        # a checkout older than the model (the benchmark's files laid over a parent commit): say so in
        # run.py's own process, which then exits 2 at once, before a fleet is launched that cannot come up
        raise NoFamily(f"the program has no {PROGRAM}: the exaone_moe family's configurations run from the commit "
                       "that adds that model module")
    known_keys_only(cfg, SIZES + DESCRIBES + tuple(FIXED), "exaone_moe")
    for key, value in FIXED.items():
        if cfg.get(key, value) != value:
            raise ValueError(f"the exaone_moe family computes {key}={value!r} only, the configuration has {cfg[key]!r}")
    assumed = cfg.get("assumed", {})
    missing = [k for k in ASSUMED_CHOICES if "value" not in assumed.get(k, {})]
    if missing:
        raise KeyError(f"the configuration's `assumed` lacks {missing}: what the source does not give is written down, "
                       "as {\"value\": ..., \"why\": ...}, not left to a default in the code")
    for key, value in ASSUMED_CHOICES.items():
        if assumed[key]["value"] != value:
            raise ValueError(f"the exaone_moe family computes {key}={value!r} only, `assumed` has {assumed[key]['value']!r}")
    layers = depth(cfg, deployment)
    kinds, ffns, windows = (list(_by_deployment(cfg, k, deployment)) for k in ("layer_types", "mlp_layer_types", "sliding_windows"))
    dense = cfg["first_k_dense_replace"]
    if not (len(kinds) == len(ffns) == len(windows) == layers) or set(kinds) - set(KINDS):
        raise ValueError(f"layer_types, mlp_layer_types and sliding_windows for {deployment!r} name {len(kinds)}, {len(ffns)} "
                         f"and {len(windows)} layers of kinds {sorted(set(kinds))}: {layers} layers of {KINDS} are wanted")
    if ffns != ["dense"] * dense + ["sparse"] * (layers - dense):
        raise ValueError(f"mlp_layer_types is not first_k_dense_replace = {dense} dense layers and then sparse ones")
    if windows != [cfg["sliding_window"] if k == KINDS[0] else 0 for k in kinds]:
        raise ValueError(f"sliding_windows is not sliding_window = {cfg['sliding_window']} on the sliding layers and 0 elsewhere")
    rope = cfg["rope_parameters"]
    if set(rope) != {"rope_theta", "rope_type"} or rope["rope_type"] != "default":
        raise ValueError(f"the exaone_moe family computes rope_type 'default' with a rope_theta only, got {rope}")
    held = _by_deployment(cfg, "num_experts", deployment)
    return {
        "module": cfg["module"],
        "vocab": _by_deployment(cfg, "vocab_size", deployment),
        "d_model": cfg["hidden_size"],
        "layers": layers,
        "windows": tuple(windows),
        "dense_layers": dense,
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "d_ff": cfg["intermediate_size"],
        "d_expert": cfg["moe_intermediate_size"],
        "num_experts": _by_deployment(cfg, "num_experts", "source"),
        # the share: this replica is the first of the chips that share a layer, and holds the first `held` experts
        "held": (0, held),
        "top_k": cfg["num_experts_per_tok"],
        "shared_experts": cfg["num_shared_experts"],
        "routed_scale": float(cfg["routed_scaling_factor"]),
        "mtp_layers": _by_deployment(cfg, "num_nextn_predict_layers", deployment),
        "rope_theta": float(rope["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "dtype": cfg.get("torch_dtype", "bfloat16"),
    }


def program(sizes: dict, max_seq: int):
    from tony_tpu.models import exaone_moe

    return exaone_moe, exaone_moe.ExaoneMoeConfig(
        vocab_size=sizes["vocab"], d_model=sizes["d_model"], n_heads=sizes["heads"], n_kv_heads=sizes["kv_heads"],
        head_dim=sizes["head_dim"], windows=sizes["windows"], dense_layers=sizes["dense_layers"], d_ff=sizes["d_ff"],
        d_expert=sizes["d_expert"], num_experts=sizes["num_experts"], held=tuple(sizes["held"]), top_k=sizes["top_k"],
        routed_scale=sizes["routed_scale"], shared_experts=sizes["shared_experts"], mtp_layers=sizes["mtp_layers"],
        max_seq=max_seq, rope_theta=sizes["rope_theta"], norm_eps=sizes["norm_eps"], dtype=sizes["dtype"])


def serve_install(sizes: dict, bench: dict) -> None:
    """`serving_http` looks a `--preset` up in the registry of the program's
    model modules and draws weights through its `init`: register the cell's
    configuration under its name in the module's `PRESETS`, hand the engine the
    seed's weights in `init`'s place, and pass on the two engine settings the
    fleet's command line does not carry (argparse keeps a flag's last value)."""
    import sys

    from chipside import seed_weights
    from tony_tpu.models import serving_http

    module, cfg = program(sizes, bench["engine"]["max_len"])
    module.PRESETS[bench["config"]] = cfg
    serving_http.init = lambda _key, _cfg: seed_weights(sizes, bench["seed"])
    for flag in ("prefill_chunk", "decode_chunk"):
        if flag in bench["engine"]:
            sys.argv += ["--" + flag.replace("_", "-"), str(bench["engine"][flag])]
