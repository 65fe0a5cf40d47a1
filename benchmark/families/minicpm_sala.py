"""The minicpm_sala family: MiniCPM-SALA, a decoder whose layers take turns
between block-sparse softmax attention (`minicpm4`, InfLLM-v2) and linear
attention with a per-head decay (`lightning-attn`), on MiniCPM's muP trunk; it
runs through `tony_tpu/models/minicpm_sala.py`. What a family answers for:
families/__init__.py. No JAX at the top level.

The published configuration gives every width and switch. What it does not
give stands under the configuration's `assumed`, each entry {"value", "why"}:
the sparse layer's seven sizes are numbers the program and the reference both
read from here (a later correction is a change of numbers in the file), the
rest are choices this family computes one value of and refuses any other.
"""

from __future__ import annotations

import os

from families import NoFamily, depth, known_keys_only

REFERENCE = "families.minicpm_sala_reference"
COUNTS = "families.minicpm_sala_counts"

#: published keys whose value names the model and changes no arithmetic here
DESCRIBES = ("model_type", "max_position_embeddings", "rand_init", "mup_denominator", "torch_dtype")
#: published keys this family computes one value of, and what that value is
FIXED = {"hidden_act": "silu", "tie_word_embeddings": False, "attention_bias": False, "attn_use_rope": False,
         "lightning_use_rope": True, "lightning_scale": "1/sqrt(d)", "qk_norm": True, "use_output_gate": True,
         "use_output_norm": True, "attn_use_output_gate": True}
SIZES = ("vocab_size", "hidden_size", "num_hidden_layers", "mixer_types", "num_attention_heads", "num_key_value_heads",
         "head_dim", "intermediate_size", "lightning_nh", "lightning_nkv", "lightning_head_dim", "rope_theta",
         "rms_norm_eps", "scale_emb", "scale_depth", "dim_model_base")
#: `assumed` entries that are sizes of the sparse layer: numbers, read by program and reference alike
ASSUMED_SIZES = ("sparse_kernel_size", "sparse_kernel_stride", "sparse_block_size", "sparse_topk",
                 "sparse_init_blocks", "sparse_window", "sparse_dense_len")
#: `assumed` entries that are choices: the one value of each that this family computes
ASSUMED_CHOICES = {"block_score": "max_then_sum", "decay": "exp(-2^(-8(h+1)/H))", "qkv_activation": "none",
                   "output_norm": "per_head"}
MIXERS = ("minicpm4", "lightning-attn")


def _by_deployment(value, deployment: str, key: str):
    if isinstance(value, dict):
        if deployment not in value:
            raise KeyError(f"configuration has no {key} for deployment {deployment!r}: {sorted(value)}")
        return value[deployment]
    return value


#: the program's model module, beside which this family's files mean anything
PROGRAM = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
                       "tony_tpu", "models", "minicpm_sala.py")


def sizes(cfg: dict, deployment: str) -> dict:
    if not os.path.isfile(PROGRAM):
        # a checkout older than the model (the benchmark's files laid over a parent commit): say so in
        # run.py's own process, which then exits 2 at once, before a fleet is launched that cannot come up
        raise NoFamily(f"the program has no {PROGRAM}: the minicpm_sala family's configurations run from the commit "
                       "that adds that model module")
    known_keys_only(cfg, SIZES + DESCRIBES + tuple(FIXED), "minicpm_sala")
    for key, value in FIXED.items():
        if cfg.get(key, value) != value:
            raise ValueError(f"the minicpm_sala family computes {key}={value!r} only, the configuration has {cfg[key]!r}")
    assumed = cfg.get("assumed", {})
    missing = [k for k in ASSUMED_SIZES + tuple(ASSUMED_CHOICES) if "value" not in assumed.get(k, {})]
    if missing:
        raise KeyError(f"the configuration's `assumed` lacks {missing}: what the source does not give is written down, "
                       "as {\"value\": ..., \"why\": ...}, not left to a default in the code")
    for key, value in ASSUMED_CHOICES.items():
        if assumed[key]["value"] != value:
            raise ValueError(f"the minicpm_sala family computes {key}={value!r} only, `assumed` has {assumed[key]['value']!r}")
    layers = depth(cfg, deployment)
    mixers = list(_by_deployment(cfg["mixer_types"], deployment, "mixer_types"))
    if len(mixers) != layers or set(mixers) - set(MIXERS):
        raise ValueError(f"mixer_types for {deployment!r} names {len(mixers)} layers of kinds {sorted(set(mixers))}: "
                         f"{layers} layers of {MIXERS} are wanted")
    if cfg["lightning_nkv"] != cfg["lightning_nh"]:
        raise ValueError("the linear layers' keys and values have a head each (lightning_nkv = lightning_nh) here")
    return {
        "module": cfg["module"],
        "vocab": cfg["vocab_size"],
        "d_model": cfg["hidden_size"],
        "layers": layers,
        "layers_published": depth(cfg, "source"),
        "mixer_types": tuple(mixers),
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "lin_heads": cfg["lightning_nh"],
        "lin_head_dim": cfg["lightning_head_dim"],
        "d_ff": cfg["intermediate_size"],
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "scale_emb": float(cfg["scale_emb"]),
        "scale_depth": float(cfg["scale_depth"]),
        "dim_model_base": cfg["dim_model_base"],
        **{k: int(assumed[k]["value"]) for k in ASSUMED_SIZES},
        "dtype": cfg.get("torch_dtype", "bfloat16"),
    }


def program(sizes: dict, max_seq: int):
    from tony_tpu.models import minicpm_sala
    from tony_tpu.ops.sparse_attention import SparseSpec

    return minicpm_sala, minicpm_sala.SalaConfig(
        vocab_size=sizes["vocab"], d_model=sizes["d_model"], mixer_types=sizes["mixer_types"],
        depth_scale_layers=sizes["layers_published"], n_heads=sizes["heads"], n_kv_heads=sizes["kv_heads"],
        head_dim=sizes["head_dim"], lin_heads=sizes["lin_heads"], lin_head_dim=sizes["lin_head_dim"],
        d_ff=sizes["d_ff"], max_seq=max_seq, rope_theta=sizes["rope_theta"], norm_eps=sizes["norm_eps"],
        scale_emb=sizes["scale_emb"], scale_depth=sizes["scale_depth"], dim_model_base=sizes["dim_model_base"],
        dtype=sizes["dtype"],
        sparse=SparseSpec(kernel=sizes["sparse_kernel_size"], stride=sizes["sparse_kernel_stride"],
                          block=sizes["sparse_block_size"], topk=sizes["sparse_topk"],
                          init_blocks=sizes["sparse_init_blocks"], window=sizes["sparse_window"],
                          dense_len=sizes["sparse_dense_len"]))


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
