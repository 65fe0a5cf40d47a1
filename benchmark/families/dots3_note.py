"""The dots3_note family: dots3-note-prev's language model, latent attention (MLA)
on every layer in two kinds (full layers read through a learned indexer, window
layers under a band, each kind with widths of its own), a routed FFN after a
leading dense layer; it runs through `tony_tpu/models/dots3_note.py`. What a
family answers for: families/__init__.py. No JAX at the top level.

The published configuration gives every size and switch and no equation. What it
does not give stands under the configuration's `assumed`, each entry {"value",
"why"}: choices this family computes one value of and refuses any other. A key
that is cut for a deployment is {"source": ..., "<deployment>": ...}.
"""

from __future__ import annotations

import os

from families import NoFamily, depth, known_keys_only

REFERENCE = "families.dots3_note_reference"
COUNTS = "families.dots3_note_counts"

#: published keys whose value names the model and changes no arithmetic here
DESCRIBES = ("model_type", "max_position_embeddings", "torch_dtype")
#: published keys this family computes one value of, and what that value is
FIXED = {"apply_mla_qkv_lora_rescale": True, "attention_bias": False, "attention_gate_type": "headwise",
         "swa_attention_gate_type": "headwise", "hidden_act": "silu", "moe_layer_freq": 1, "norm_topk_prob": True,
         "rope_scaling": None, "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_method": "noaux_tc"}
SIZES = ("vocab_size", "hidden_size", "num_hidden_layers", "layer_types", "first_k_dense_replace", "intermediate_size",
         "moe_intermediate_size", "n_routed_experts", "n_shared_experts", "num_experts_per_tok", "routed_scaling_factor",
         "num_attention_heads", "num_key_value_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
         "v_head_dim", "rope_theta", "swa_num_attention_heads", "swa_num_key_value_heads", "swa_q_lora_rank",
         "swa_kv_lora_rank", "swa_qk_nope_head_dim", "swa_qk_rope_head_dim", "swa_v_head_dim", "swa_rope_theta",
         "sliding_window_size", "index_n_heads", "index_head_dim", "index_topk", "rms_norm_eps")
#: `assumed` entries that are choices: the one value of each that this family computes
ASSUMED_CHOICES = {
    "block": "pre_norm",
    "latent_norm": "rmsnorm_on_q_and_kv_latents",
    "rope": "rotate_half_on_rope_dims_one_key_for_all_heads",
    "softmax_scale": "inverse_sqrt_of_nope_plus_rope",
    "lora_rescale": "normed_latents_times_sqrt_hidden_over_rank",
    "attention_gate": "sigmoid_of_x_wg_per_head_before_o_proj",
    "indexer": "sum_over_heads_of_w_relu_q_dot_k_from_the_q_latent_rope_on_first_dims",
    "window": "counts_itself",
    "router_bias": "chooses_does_not_weigh",
    "router_groups": "one_group",
    "mtp": "none",
}
KINDS = ("full_attention", "sliding_attention")

#: the program's model module, beside which this family's files mean anything
PROGRAM = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
                       "tony_tpu", "models", "dots3_note.py")


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
        raise NoFamily(f"the program has no {PROGRAM}: the dots3_note family's configurations run from the commit "
                       "that adds that model module")
    known_keys_only(cfg, SIZES + DESCRIBES + tuple(FIXED), "dots3_note")
    for key, value in FIXED.items():
        if cfg.get(key, value) != value:
            raise ValueError(f"the dots3_note family computes {key}={value!r} only, the configuration has {cfg[key]!r}")
    assumed = cfg.get("assumed", {})
    missing = [k for k in ASSUMED_CHOICES if "value" not in assumed.get(k, {})]
    if missing:
        raise KeyError(f"the configuration's `assumed` lacks {missing}: what the source does not give is written down, "
                       "as {\"value\": ..., \"why\": ...}, not left to a default in the code")
    for key, value in ASSUMED_CHOICES.items():
        if assumed[key]["value"] != value:
            raise ValueError(f"the dots3_note family computes {key}={value!r} only, `assumed` has {assumed[key]['value']!r}")
    for heads in ("num", "swa_num"):
        if cfg[f"{heads}_key_value_heads"] != cfg[f"{heads}_attention_heads"]:
            raise ValueError(f"latent attention builds a key a head: {heads}_key_value_heads must equal {heads}_attention_heads")
    layers = depth(cfg, deployment)
    kinds = list(_by_deployment(cfg, "layer_types", deployment))
    if len(kinds) != layers or set(kinds) - set(KINDS):
        raise ValueError(f"layer_types for {deployment!r} names {len(kinds)} layers of kinds {sorted(set(kinds))}: "
                         f"{layers} layers of {KINDS} are wanted")
    return {
        "module": cfg["module"],
        "vocab": _by_deployment(cfg, "vocab_size", deployment),
        "d_model": cfg["hidden_size"],
        "layers": layers,
        "kinds": tuple(kinds),
        "dense_layers": cfg["first_k_dense_replace"],
        "d_ff": cfg["intermediate_size"],
        "d_expert": cfg["moe_intermediate_size"],
        "num_experts": _by_deployment(cfg, "n_routed_experts", "source"),
        # the share: this replica is the first of the chips that share a layer, and holds the first `held` experts
        "held": (0, _by_deployment(cfg, "n_routed_experts", deployment)),
        "top_k": cfg["num_experts_per_tok"],
        "shared_experts": cfg["n_shared_experts"],
        "routed_scale": float(cfg["routed_scaling_factor"]),
        "heads": cfg["num_attention_heads"], "q_rank": cfg["q_lora_rank"], "kv_rank": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"], "v_dim": cfg["v_head_dim"],
        "rope_theta": float(cfg["rope_theta"]),
        "swa_heads": cfg["swa_num_attention_heads"], "swa_q_rank": cfg["swa_q_lora_rank"],
        "swa_kv_rank": cfg["swa_kv_lora_rank"], "swa_nope": cfg["swa_qk_nope_head_dim"],
        "swa_rope": cfg["swa_qk_rope_head_dim"], "swa_v_dim": cfg["swa_v_head_dim"],
        "swa_rope_theta": float(cfg["swa_rope_theta"]),
        "window": cfg["sliding_window_size"],
        "index_heads": cfg["index_n_heads"], "index_dim": cfg["index_head_dim"], "index_topk": cfg["index_topk"],
        "norm_eps": float(cfg["rms_norm_eps"]),
        "dtype": cfg.get("torch_dtype", "bfloat16"),
    }


def program(sizes: dict, max_seq: int):
    from tony_tpu.models import dots3_note

    s = sizes
    return dots3_note, dots3_note.Dots3NoteConfig(
        vocab_size=s["vocab"], d_model=s["d_model"], layer_types=tuple(s["kinds"]), dense_layers=s["dense_layers"],
        d_ff=s["d_ff"], d_expert=s["d_expert"], num_experts=s["num_experts"], held=tuple(s["held"]), top_k=s["top_k"],
        routed_scale=s["routed_scale"], shared_experts=s["shared_experts"], n_heads=s["heads"], q_rank=s["q_rank"],
        kv_rank=s["kv_rank"], nope=s["nope"], rope=s["rope"], v_dim=s["v_dim"], rope_theta=s["rope_theta"],
        swa_heads=s["swa_heads"], swa_q_rank=s["swa_q_rank"], swa_kv_rank=s["swa_kv_rank"], swa_nope=s["swa_nope"],
        swa_rope=s["swa_rope"], swa_v_dim=s["swa_v_dim"], swa_rope_theta=s["swa_rope_theta"], window=s["window"],
        index_heads=s["index_heads"], index_dim=s["index_dim"], index_topk=s["index_topk"], max_seq=max_seq,
        norm_eps=s["norm_eps"], dtype=s["dtype"])


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
