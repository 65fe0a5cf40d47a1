"""The granite_hybrid family: Granite-4.0-H, a decoder whose layers take turns
between Mamba-2 state-space mixers (`mamba`: a convolution with a bias, a float32
state a head whose decay and step the token chooses, B and C shared by every
head) and grouped-query softmax attention without rotary embedding (`attention`),
nine to one, with a routed FFN (top-k of the logits, softmax over the chosen, a
shared expert) in every layer and muP-style scalars on the embedding, the
branches, the scores and the logits; it runs through
`tony_tpu/models/granite_hybrid.py`. What a family answers for:
families/__init__.py. No JAX at the top level.

The published configuration gives every width and switch and no equation. What
it does not give stands under the configuration's `assumed`, each entry
{"value", "why"}: `head_dim` is a number the program and the reference read from
there, the rest are choices this family computes one value of and refuses any
other (each is one function in the program and one in the reference). A key
that is cut for a deployment is {"source": ..., "<deployment>": ...}.
"""

from __future__ import annotations

import os

from families import NoFamily, depth, known_keys_only

REFERENCE = "families.granite_hybrid_reference"
COUNTS = "families.granite_hybrid_counts"

#: published keys whose value names the model and changes no arithmetic here (`rope_theta`: no layer rotates;
#: `mamba_chunk_size`: the block of the publisher's own kernel, a choice of the program's in ops/ssd.py)
DESCRIBES = ("model_type", "max_position_embeddings", "torch_dtype", "rope_theta", "mamba_chunk_size")
#: published keys this family computes one value of, and what that value is
FIXED = {"hidden_act": "silu", "position_embedding_type": "nope", "attention_bias": False, "mamba_proj_bias": False,
         "mamba_conv_bias": True, "tie_word_embeddings": True, "normalization_function": "rmsnorm", "rope_scaling": None}
SIZES = ("vocab_size", "hidden_size", "intermediate_size", "shared_intermediate_size", "num_hidden_layers", "layer_types",
         "num_attention_heads", "num_key_value_heads", "num_local_experts", "num_experts_per_tok", "mamba_n_heads",
         "mamba_d_head", "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_n_groups", "attention_multiplier",
         "embedding_multiplier", "residual_multiplier", "logits_scaling", "rms_norm_eps")
#: `assumed` entries that are choices: the one value of each that this family computes
ASSUMED_CHOICES = {
    "block": "pre_norm_scaled_branch",
    "in_proj_order": "z|xBC|dt",
    "ssm_output": "gate_then_rmsnorm_over_the_inner_width",
    "dt_limits": "none",
    "state_dtype": "float32",
    "ssm_init": "A_log=log_U(1,16);dt_bias=softplus_inverse(exp_U(log_0.001,log_0.1));D=1",
    "embed_init": "fan_in=embedding_multiplier^2*hidden_size",
    "expert_width": "intermediate_size",
    "router_scoring": "topk_of_logits_then_softmax_over_the_chosen",
    "shared_expert": "added_to_the_routed_sum",
}
KINDS = ("mamba", "attention")

#: the program's model module, beside which this family's files mean anything
PROGRAM = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
                       "tony_tpu", "models", "granite_hybrid.py")


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
        raise NoFamily(f"the program has no {PROGRAM}: the granite_hybrid family's configurations run from the commit "
                       "that adds that model module")
    known_keys_only(cfg, SIZES + DESCRIBES + tuple(FIXED), "granite_hybrid")
    for key, value in FIXED.items():
        if key not in cfg or cfg[key] != value:
            raise ValueError(f"the granite_hybrid family computes {key}={value!r} only, the configuration has {cfg.get(key, 'no such key')!r}")
    assumed = cfg.get("assumed", {})
    missing = [k for k in ("head_dim", *ASSUMED_CHOICES) if "value" not in assumed.get(k, {})]
    if missing:
        raise KeyError(f"the configuration's `assumed` lacks {missing}: what the source does not give is written down, "
                       "as {\"value\": ..., \"why\": ...}, not left to a default in the code")
    for key, value in ASSUMED_CHOICES.items():
        if assumed[key]["value"] != value:
            raise ValueError(f"the granite_hybrid family computes {key}={value!r} only, `assumed` has {assumed[key]['value']!r}")
    layers = depth(cfg, deployment)
    kinds = list(_by_deployment(cfg, "layer_types", deployment))
    if len(kinds) != layers or set(kinds) - set(KINDS):
        raise ValueError(f"layer_types for {deployment!r} names {len(kinds)} layers of kinds {sorted(set(kinds))}: "
                         f"{layers} layers of {KINDS} are wanted")
    if cfg["mamba_n_groups"] != 1:
        raise ValueError(f"mamba_n_groups {cfg['mamba_n_groups']}: B and C are one pair for every head here (one group), "
                         "and the gated norm spans the whole inner width")
    if cfg["mamba_expand"] * cfg["hidden_size"] != cfg["mamba_n_heads"] * cfg["mamba_d_head"]:
        raise ValueError("the state-space layers' inner width is mamba_expand x hidden_size = mamba_n_heads x mamba_d_head")
    return {
        "module": cfg["module"],
        "vocab": _by_deployment(cfg, "vocab_size", deployment),
        "d_model": cfg["hidden_size"],
        "layers": layers,
        "layer_types": tuple(kinds),
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": int(assumed["head_dim"]["value"]),
        "ssm_heads": cfg["mamba_n_heads"],
        "ssm_head_dim": cfg["mamba_d_head"],
        "ssm_state": cfg["mamba_d_state"],
        "conv_taps": cfg["mamba_d_conv"],
        "d_expert": cfg["intermediate_size"],
        "d_shared": cfg["shared_intermediate_size"],
        "num_experts": _by_deployment(cfg, "num_local_experts", "source"),
        # the share: this replica is the first of the chips that share a layer, and holds the first `held` experts
        "held": (0, _by_deployment(cfg, "num_local_experts", deployment)),
        "top_k": cfg["num_experts_per_tok"],
        "attention_multiplier": float(cfg["attention_multiplier"]),
        "embedding_multiplier": float(cfg["embedding_multiplier"]),
        "residual_multiplier": float(cfg["residual_multiplier"]),
        "logits_scaling": float(cfg["logits_scaling"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "dtype": cfg.get("torch_dtype", "bfloat16"),
    }


def program(sizes: dict, max_seq: int, page_len: int = 256):
    from tony_tpu.models import granite_hybrid

    s = sizes
    return granite_hybrid, granite_hybrid.GraniteHybridConfig(
        vocab_size=s["vocab"], d_model=s["d_model"], layer_types=tuple(s["layer_types"]), n_heads=s["heads"],
        n_kv_heads=s["kv_heads"], head_dim=s["head_dim"], ssm_heads=s["ssm_heads"], ssm_head_dim=s["ssm_head_dim"],
        ssm_state=s["ssm_state"], conv_taps=s["conv_taps"], d_expert=s["d_expert"], num_experts=s["num_experts"],
        held=tuple(s["held"]), top_k=s["top_k"], d_shared=s["d_shared"], embedding_multiplier=s["embedding_multiplier"],
        residual_multiplier=s["residual_multiplier"], attention_multiplier=s["attention_multiplier"],
        logits_scaling=s["logits_scaling"], max_seq=max_seq, norm_eps=s["norm_eps"], page_len=page_len, dtype=s["dtype"])


def serve_install(sizes: dict, bench: dict) -> None:
    """`serving_http` looks a `--preset` up in the registry of the program's
    model modules and draws weights through its `init`: register the cell's
    configuration under its name in the module's `PRESETS` (with the engine
    block's page length, whose power-of-two multiples are this family's prefill
    buckets), hand the engine the seed's weights in `init`'s place, and pass on
    the two engine settings the fleet's command line does not carry (argparse
    keeps a flag's last value)."""
    import sys

    from chipside import seed_weights
    from tony_tpu.models import serving_http

    engine = bench["engine"]
    module, cfg = program(sizes, engine["max_len"], engine["page_len"])
    module.PRESETS[bench["config"]] = cfg

    serving_http.init = lambda _key, _cfg: seed_weights(sizes, bench["seed"])
    for flag in ("prefill_chunk", "decode_chunk"):
        if flag in engine:
            sys.argv += ["--" + flag.replace("_", "-"), str(engine[flag])]
