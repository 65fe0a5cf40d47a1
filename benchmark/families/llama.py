"""The llama family: dense decoders that run through `tony_tpu/models/llama.py`
(RMSNorm, rotary embedding, grouped-query attention inside an optional sliding
band, SwiGLU), Mistral-7B among them. What a family answers for:
families/__init__.py. No JAX at the top level.
"""

from __future__ import annotations

from families import depth, known_keys_only

REFERENCE = "families.llama_reference"
COUNTS = "families.llama_counts"

#: published keys whose value names the model and changes no arithmetic here
DESCRIBES = ("architectures", "model_type", "max_position_embeddings")
#: published keys this family computes one value of, and what that value is
FIXED = {"hidden_act": "silu", "tie_word_embeddings": False}
SIZES = ("vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
         "intermediate_size", "rope_theta", "rms_norm_eps", "sliding_window", "torch_dtype")


def sizes(cfg: dict, deployment: str) -> dict:
    known_keys_only(cfg, SIZES + DESCRIBES + tuple(FIXED), "llama")
    for key, value in FIXED.items():
        if cfg.get(key, value) != value:
            raise ValueError(f"the llama family computes {key}={value!r} only, the configuration has {cfg[key]!r}")
    return {
        "module": cfg["module"],
        "vocab": cfg["vocab_size"],
        "d_model": cfg["hidden_size"],
        "layers": depth(cfg, deployment),
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
        "d_ff": cfg["intermediate_size"],
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "window": int(cfg.get("sliding_window") or 0),
        "experts": 0,
        "top_k": 0,
        "dtype": cfg.get("torch_dtype", "bfloat16"),
    }


def program_fields(sizes: dict, max_seq: int) -> dict:
    """The sizes under the field names of the program's LlamaConfig."""
    return {
        "vocab_size": sizes["vocab"], "d_model": sizes["d_model"], "n_layers": sizes["layers"],
        "n_heads": sizes["heads"], "n_kv_heads": sizes["kv_heads"], "d_ff": sizes["d_ff"],
        "max_seq": max_seq, "rope_theta": sizes["rope_theta"], "norm_eps": sizes["norm_eps"],
        "dtype": sizes["dtype"], "sliding_window": sizes["window"],
    }


def program(sizes: dict, max_seq: int):
    from tony_tpu.models import llama

    return llama, llama.config_from_dict(program_fields(sizes, max_seq))


def serve_install(sizes: dict, bench: dict) -> None:
    """`serving_http` takes a model as a preset name and draws its weights with
    `llama.init`: register the cell's configuration under its name in
    `llama.PRESETS` (the dict serving_http imported) and hand the engine the
    seed's weights in `init`'s place."""
    from chipside import seed_weights
    from tony_tpu.models import serving_http

    module, cfg = program(sizes, bench["engine"]["max_len"])
    module.PRESETS[bench["config"]] = cfg
    serving_http.init = lambda _key, _cfg: seed_weights(sizes, bench["seed"])
