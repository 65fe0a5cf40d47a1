"""The falcon_h1 family: Falcon-H1, a decoder in which EVERY layer runs a Mamba-2
state-space mixer (a convolution with a bias, a float32 state a head whose decay
and step the token chooses, B and C in `mamba_n_groups` groups) and rotary
grouped-query attention side by side on one normed input and adds both, then a
dense SwiGLU, with muP scalars on the embedding, on each branch's way in and out,
on the keys, on the five segments of the state-space projection, on the FFN's
gate and output and on the logits; it runs through `tony_tpu/models/falcon_h1.py`.
What a family answers for: families/__init__.py. No JAX at the top level.

The published configuration gives every width, switch and scalar and no equation.
What it does not give stands under the configuration's `assumed`, each entry
{"value", "why"}: choices this family computes one value of and refuses any other
(each is one function in the program and one in the reference). A key that is
cut for a deployment is {"source": ..., "<deployment>": ...}.
"""

from __future__ import annotations

import os

from families import NoFamily, depth, known_keys_only
from families.granite_hybrid import _by_deployment  # a cut key's value for a deployment: {"source": ..., "<deployment>": ...}

REFERENCE = "families.falcon_h1_reference"
COUNTS = "families.falcon_h1_counts"

#: published keys whose value names the model and changes no arithmetic here (`mamba_expand` and
#: `mlp_expansion_factor`: the inner widths are `mamba_d_ssm` and `intermediate_size`, whatever these two would give;
#: `mamba_chunk_size`: the block of the publisher's own kernel, a choice of the program's in ops/ssd.py;
#: `num_logits_to_keep`: the publisher's generation loop)
DESCRIBES = ("model_type", "max_position_embeddings", "torch_dtype", "mamba_chunk_size", "mamba_expand", "mlp_expansion_factor",
             "num_logits_to_keep")
#: published keys this family computes one value of, and what that value is (`attn_layer_indices` null: every layer has attention)
FIXED = {"hidden_act": "silu", "attention_bias": False, "attn_layer_indices": None, "mamba_conv_bias": True, "mamba_proj_bias": False,
         "mamba_rms_norm": True, "mamba_norm_before_gate": False, "mamba_use_mlp": True, "mlp_bias": False, "projectors_bias": False,
         "rope_scaling": None, "tie_word_embeddings": False}
#: the muP scalars, by their published keys (two of them vectors: five and two entries)
SCALARS = ("embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier", "attention_out_multiplier", "key_multiplier",
           "ssm_in_multiplier", "ssm_out_multiplier")
VECTORS = {"ssm_multipliers": 5, "mlp_multipliers": 2}
SIZES = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "head_dim",
         "rope_theta", "mamba_n_heads", "mamba_d_head", "mamba_d_ssm", "mamba_d_state", "mamba_d_conv", "mamba_n_groups", "rms_norm_eps",
         *SCALARS, *VECTORS)
#: `assumed` entries that are choices: the one value of each that this family computes
ASSUMED_CHOICES = {
    "block": "pre_norm;both_mixers_on_one_normed_input;outputs_added",
    "rope": "whole_head;halves_rotated",
    "in_proj_order": "z|x|B|C|dt",
    "ssm_output": "gate_then_rmsnorm_over_each_group",
    "dt_limits": "none",
    "mlp_multipliers_order": "gate_before_silu,down_after_w_down",
    "state_dtype": "float32",
    "ssm_init": "A_log=log_U(1,16);dt_bias=softplus_inverse(exp_U(log_0.001,log_0.1));D=1",
    "matrix_init": "fan_in=input_width*(scalars_on_its_way)^2",
}

#: the program's model module, beside which this family's files mean anything
PROGRAM = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
                       "tony_tpu", "models", "falcon_h1.py")


def sizes(cfg: dict, deployment: str) -> dict:
    if not os.path.isfile(PROGRAM):
        # a checkout older than the model (the benchmark's files laid over a parent commit): say so in
        # run.py's own process, which then exits 2 at once, before a fleet is launched that cannot come up
        raise NoFamily(f"the program has no {PROGRAM}: the falcon_h1 family's configurations run from the commit "
                       "that adds that model module")
    known_keys_only(cfg, SIZES + DESCRIBES + tuple(FIXED), "falcon_h1")
    for key, value in FIXED.items():
        if key not in cfg or cfg[key] != value:
            raise ValueError(f"the falcon_h1 family computes {key}={value!r} only, the configuration has {cfg.get(key, 'no such key')!r}")
    assumed = cfg.get("assumed", {})
    missing = [k for k in ASSUMED_CHOICES if "value" not in assumed.get(k, {})]
    if missing:
        raise KeyError(f"the configuration's `assumed` lacks {missing}: what the source does not give is written down, "
                       "as {\"value\": ..., \"why\": ...}, not left to a default in the code")
    for key, value in ASSUMED_CHOICES.items():
        if assumed[key]["value"] != value:
            raise ValueError(f"the falcon_h1 family computes {key}={value!r} only, `assumed` has {assumed[key]['value']!r}")
    for key, n in VECTORS.items():
        if len(cfg[key]) != n:
            raise ValueError(f"{key} has {len(cfg[key])} entries: {n} are wanted (ssm_multipliers one a segment of z | x | B | C | dt, "
                             "mlp_multipliers gate and down)")
    if cfg["mamba_d_ssm"] != cfg["mamba_n_heads"] * cfg["mamba_d_head"]:
        raise ValueError("the state-space mixer's inner width is mamba_d_ssm = mamba_n_heads x mamba_d_head (mamba_expand does not give it)")
    if cfg["mamba_n_heads"] % cfg["mamba_n_groups"] or cfg["num_attention_heads"] % cfg["num_key_value_heads"]:
        raise ValueError("mamba_n_groups divides mamba_n_heads and num_key_value_heads divides num_attention_heads")
    return {
        "module": cfg["module"],
        "vocab": _by_deployment(cfg, "vocab_size", deployment),
        "d_model": cfg["hidden_size"],
        "layers": depth(cfg, deployment),
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "rope_theta": float(cfg["rope_theta"]),
        "ssm_heads": cfg["mamba_n_heads"],
        "ssm_head_dim": cfg["mamba_d_head"],
        "ssm_state": cfg["mamba_d_state"],
        "ssm_groups": cfg["mamba_n_groups"],
        "conv_taps": cfg["mamba_d_conv"],
        "d_ff": cfg["intermediate_size"],
        **{key: float(cfg[key]) for key in SCALARS},
        **{key: tuple(float(m) for m in cfg[key]) for key in VECTORS},
        "norm_eps": float(cfg["rms_norm_eps"]),
        "dtype": cfg.get("torch_dtype", "bfloat16"),
    }


def program(sizes: dict, max_seq: int, page_len: int = 256):
    from tony_tpu.models import falcon_h1

    s = sizes
    return falcon_h1, falcon_h1.FalconH1Config(
        vocab_size=s["vocab"], d_model=s["d_model"], n_layers=s["layers"], n_heads=s["heads"], n_kv_heads=s["kv_heads"],
        head_dim=s["head_dim"], rope_theta=s["rope_theta"], ssm_heads=s["ssm_heads"], ssm_head_dim=s["ssm_head_dim"],
        ssm_state=s["ssm_state"], ssm_groups=s["ssm_groups"], conv_taps=s["conv_taps"], d_ff=s["d_ff"],
        **{key: s[key] for key in SCALARS}, **{key: tuple(s[key]) for key in VECTORS},
        max_seq=max_seq, norm_eps=s["norm_eps"], page_len=page_len, dtype=s["dtype"])


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
