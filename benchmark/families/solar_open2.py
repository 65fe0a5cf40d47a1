"""The solar_open2 family: Solar Open 2, a decoder whose layers take turns between
Kimi Delta Attention (`kda`: a convolution, a float32 state a head that forgets by a
gate a CHANNEL and corrects what it holds of a key before it writes) and gated
grouped-query softmax attention without rotary embedding (`attention`), three to
one, with a routed FFN (sigmoid scores, a choosing bias, a shared expert) in every
layer; it runs through `tony_tpu/models/solar_open2.py`. What a family answers for:
families/__init__.py. No JAX at the top level.

The published configuration gives every width and switch and no equation. What it
does not give stands under the configuration's `assumed`, each entry {"value",
"why"}: `gate_rank` and `router_bias_scale` are numbers the program and the
reference read from there, the rest are choices this family computes one value of
and refuses any other (each is one function in the program and one in the
reference). A key that is cut for a deployment is {"source": ..., "<deployment>": ...}.
"""

from __future__ import annotations

import os

from families import NoFamily, depth, known_keys_only
from families.granite_hybrid import _by_deployment  # a cut key's value for a deployment: {"source": ..., "<deployment>": ...}

REFERENCE = "families.solar_open2_reference"
COUNTS = "families.solar_open2_counts"

#: published keys whose value names the model and changes no arithmetic here (`rope_theta` and
#: `partial_rotary_factor`: no layer rotates; `intermediate_size`: the width of a dense FFN, and first_k_dense_replace
#: 0 leaves no layer one: the name's 250 B adds up only with the shared expert moe_intermediate_size wide)
DESCRIBES = ("model_type", "max_position_embeddings", "torch_dtype", "rope_theta", "partial_rotary_factor", "intermediate_size")
#: published keys this family computes one value of, and what that value is
FIXED = {"use_rope": False, "use_gqa_gate": True, "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
         "tie_word_embeddings": False, "norm_topk_prob": True, "first_k_dense_replace": 0}
SIZES = ("vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads", "head_dim", "num_key_value_heads",
         "moe_intermediate_size", "rms_norm_eps", "gqa_interval", "gqa_layers", "n_routed_experts", "n_shared_experts",
         "routed_scaling_factor", "num_experts_per_tok", "linear_attn_config")
#: `assumed` entries that are choices: the one value of each that this family computes
ASSUMED_CHOICES = {
    "block": "pre_norm",
    "kda_inputs": "conv_then_silu_then_l2_norm",
    "kda_decay": "g=-exp(A_log[head])*softplus(x*W_fa*W_fb+dt_bias)_a_channel",
    "kda_gates": "low_rank",
    "kda_output": "rmsnorm_a_head_times_sigmoid_gate",
    "kda_init": "A_log=log_U(1,16);dt_bias=softplus_inverse(exp_U(log_0.001,log_1))",
    "state_dtype": "float32",
    "gqa_gate": "sigmoid_of_the_layers_input_elementwise_before_w_o",
    "qk_norm": "none",
    "router": "sigmoid_scores;top_k_of_score+bias;gates=chosen_scores_over_their_sum",
    "shared_expert": "added_to_the_routed_sum",
}
#: `assumed` entries that are numbers
ASSUMED_NUMBERS = ("gate_rank", "router_bias_scale")
KINDS = ("kda", "attention")

#: the program's model module, beside which this family's files mean anything
PROGRAM = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
                       "tony_tpu", "models", "solar_open2.py")


def sizes(cfg: dict, deployment: str) -> dict:
    if not os.path.isfile(PROGRAM):
        # a checkout older than the model (the benchmark's files laid over a parent commit): say so in
        # run.py's own process, which then exits 2 at once, before a fleet is launched that cannot come up
        raise NoFamily(f"the program has no {PROGRAM}: the solar_open2 family's configurations run from the commit "
                       "that adds that model module")
    known_keys_only(cfg, SIZES + DESCRIBES + tuple(FIXED), "solar_open2")
    for key, value in FIXED.items():
        if key not in cfg or cfg[key] != value:
            raise ValueError(f"the solar_open2 family computes {key}={value!r} only, the configuration has {cfg.get(key, 'no such key')!r}")
    assumed = cfg.get("assumed", {})
    missing = [k for k in (*ASSUMED_NUMBERS, *ASSUMED_CHOICES) if "value" not in assumed.get(k, {})]
    if missing:
        raise KeyError(f"the configuration's `assumed` lacks {missing}: what the source does not give is written down, "
                       "as {\"value\": ..., \"why\": ...}, not left to a default in the code")
    for key, value in ASSUMED_CHOICES.items():
        if assumed[key]["value"] != value:
            raise ValueError(f"the solar_open2 family computes {key}={value!r} only, `assumed` has {assumed[key]['value']!r}")
    layers = depth(cfg, deployment)
    gqa = list(_by_deployment(cfg, "gqa_layers", deployment))
    if gqa != [i for i in range(layers) if i % (cfg["gqa_interval"] + 1) == 0]:
        raise ValueError(f"gqa_layers for {deployment!r} is {gqa}: one attention layer, then gqa_interval = {cfg['gqa_interval']} "
                         f"recurrent ones, over {layers} layers is wanted")
    lin = cfg["linear_attn_config"]
    if set(lin) != {"short_conv_kernel_size", "head_dim", "num_heads", "num_kv_heads"} or lin["num_kv_heads"] not in (None, lin["num_heads"]):
        raise ValueError(f"linear_attn_config {lin}: heads of one width for queries, keys and values alike (num_kv_heads null) are wanted")
    return {
        "module": cfg["module"],
        "vocab": _by_deployment(cfg, "vocab_size", deployment),
        "d_model": cfg["hidden_size"],
        "layers": layers,
        "layer_types": tuple(KINDS[1] if i in gqa else KINDS[0] for i in range(layers)),
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "kda_heads": lin["num_heads"],
        "kda_head_dim": lin["head_dim"],
        "conv_taps": lin["short_conv_kernel_size"],
        "gate_rank": int(assumed["gate_rank"]["value"]),
        "d_expert": cfg["moe_intermediate_size"],
        "d_shared": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        "num_experts": _by_deployment(cfg, "n_routed_experts", "source"),
        # the share: this replica is the first of the chips that share a layer, and holds the first `held` experts
        "held": (0, _by_deployment(cfg, "n_routed_experts", deployment)),
        "top_k": cfg["num_experts_per_tok"],
        "routed_scale": float(cfg["routed_scaling_factor"]),
        "router_bias_scale": float(assumed["router_bias_scale"]["value"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "dtype": cfg.get("torch_dtype", "bfloat16"),
    }


def program(sizes: dict, max_seq: int, page_len: int = 256):
    from tony_tpu.models import solar_open2

    s = sizes
    return solar_open2, solar_open2.SolarOpen2Config(
        vocab_size=s["vocab"], d_model=s["d_model"], layer_types=tuple(s["layer_types"]), n_heads=s["heads"],
        n_kv_heads=s["kv_heads"], head_dim=s["head_dim"], kda_heads=s["kda_heads"], kda_head_dim=s["kda_head_dim"],
        conv_taps=s["conv_taps"], gate_rank=s["gate_rank"], d_expert=s["d_expert"], num_experts=s["num_experts"],
        held=tuple(s["held"]), top_k=s["top_k"], routed_scale=s["routed_scale"], d_shared=s["d_shared"], max_seq=max_seq,
        norm_eps=s["norm_eps"], page_len=page_len, dtype=s["dtype"])


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
