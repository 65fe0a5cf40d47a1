"""The mistral4 family: Mistral-Small-4's language model, DENSE latent attention
(MLA) on every layer under a YaRN rope with a position-scaled query, and a routed
FFN (softmax over all experts, top-k renormalised, a shared expert) on every
layer; it runs through `tony_tpu/models/mistral4.py`. What a family answers for:
families/__init__.py. No JAX at the top level.

The published configuration gives every size and switch and no equation. What it
does not give stands under the configuration's `assumed`, each entry {"value",
"why"}: choices this family computes one value of and refuses any other. A key
that is cut for a deployment is {"source": ..., "<deployment>": ...}.
"""

from __future__ import annotations

import os

from families import NoFamily, depth, known_keys_only

REFERENCE = "families.mistral4_reference"
COUNTS = "families.mistral4_counts"

#: published keys whose value names the model and changes no arithmetic here (`intermediate_size` is the width of
#: a dense FFN, and with first_k_dense_replace 0 no layer has one)
DESCRIBES = ("model_type", "max_position_embeddings", "torch_dtype", "intermediate_size")
#: published keys this family computes one value of, and what that value is
FIXED = {"attention_bias": False, "mlp_bias": False, "hidden_act": "silu", "first_k_dense_replace": 0, "n_group": 1,
         "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 1, "sliding_window": None,
         "tie_word_embeddings": False, "rope_interleave": True}
SIZES = ("vocab_size", "hidden_size", "num_hidden_layers", "moe_intermediate_size", "n_routed_experts", "n_shared_experts",
         "num_experts_per_tok", "num_attention_heads", "num_key_value_heads", "head_dim", "qk_head_dim", "q_lora_rank",
         "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_parameters", "rms_norm_eps")
#: the keys of `rope_parameters` and nothing else: YaRN's numbers, the base, the query scale's beta
ROPE_KEYS = ("beta_fast", "beta_slow", "factor", "llama_4_scaling_beta", "mscale", "mscale_all_dim",
             "original_max_position_embeddings", "rope_theta", "rope_type", "type")
#: `assumed` entries that are choices: the one value of each that this family computes
ASSUMED_CHOICES = {
    "block": "pre_norm",
    "latent_norm": "rmsnorm_on_q_and_kv_latents",
    "router_scoring": "softmax_over_all_then_topk_then_renormalise",
    "softmax_scale": "inverse_sqrt_qk_head_dim_times_yarn_mscale_all_dim_squared",
    "query_scale": "one_plus_beta_log1p_floor_position_over_original_max",
    "mtp": "none",
    "vision_tower": "none",
}

#: the program's model module, beside which this family's files mean anything
PROGRAM = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
                       "tony_tpu", "models", "mistral4.py")


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
        raise NoFamily(f"the program has no {PROGRAM}: the mistral4 family's configurations run from the commit "
                       "that adds that model module")
    known_keys_only(cfg, SIZES + DESCRIBES + tuple(FIXED), "mistral4")
    for key, value in FIXED.items():
        if key not in cfg or cfg[key] != value:
            raise ValueError(f"the mistral4 family computes {key}={value!r} only, the configuration has {cfg.get(key, 'no such key')!r}")
    assumed = cfg.get("assumed", {})
    missing = [k for k in ASSUMED_CHOICES if "value" not in assumed.get(k, {})]
    if missing:
        raise KeyError(f"the configuration's `assumed` lacks {missing}: what the source does not give is written down, "
                       "as {\"value\": ..., \"why\": ...}, not left to a default in the code")
    for key, value in ASSUMED_CHOICES.items():
        if assumed[key]["value"] != value:
            raise ValueError(f"the mistral4 family computes {key}={value!r} only, `assumed` has {assumed[key]['value']!r}")
    rp = cfg["rope_parameters"]
    if sorted(rp) != sorted(ROPE_KEYS) or rp["rope_type"] != "yarn" or rp["type"] != "yarn":
        raise ValueError(f"rope_parameters {sorted(rp)} / type {rp.get('rope_type')!r}: a YaRN rope's {sorted(ROPE_KEYS)} are wanted")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("latent attention builds a key a head: num_key_value_heads must equal num_attention_heads")
    if not cfg["head_dim"] == cfg["qk_head_dim"] == cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]:
        raise ValueError("head_dim and qk_head_dim are qk_nope_head_dim + qk_rope_head_dim")
    return {
        "module": cfg["module"],
        "vocab": _by_deployment(cfg, "vocab_size", deployment),
        "d_model": cfg["hidden_size"],
        "layers": depth(cfg, deployment),
        "dense_layers": 0,
        "d_expert": cfg["moe_intermediate_size"],
        "num_experts": _by_deployment(cfg, "n_routed_experts", "source"),
        # the share: this replica is the first of the chips that share a layer, and holds the first `held` experts
        "held": (0, _by_deployment(cfg, "n_routed_experts", deployment)),
        "top_k": cfg["num_experts_per_tok"],
        "shared_experts": cfg["n_shared_experts"],
        "heads": cfg["num_attention_heads"], "q_rank": cfg["q_lora_rank"], "kv_rank": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"], "v_dim": cfg["v_head_dim"],
        "rope_theta": float(rp["rope_theta"]),
        # (factor, beta_fast, beta_slow, original_max_position_embeddings, mscale, mscale_all_dim)
        "yarn": (float(rp["factor"]), float(rp["beta_fast"]), float(rp["beta_slow"]), int(rp["original_max_position_embeddings"]),
                 float(rp["mscale"]), float(rp["mscale_all_dim"])),
        "query_scale_beta": float(rp["llama_4_scaling_beta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "dtype": cfg.get("torch_dtype", "bfloat16"),
    }


def program(sizes: dict, max_seq: int):
    from tony_tpu.models import mistral4

    s = sizes
    return mistral4, mistral4.Mistral4Config(
        vocab_size=s["vocab"], d_model=s["d_model"], n_layers=s["layers"], n_heads=s["heads"], q_rank=s["q_rank"],
        kv_rank=s["kv_rank"], nope=s["nope"], rope=s["rope"], v_dim=s["v_dim"], d_expert=s["d_expert"],
        num_experts=s["num_experts"], held=tuple(s["held"]), top_k=s["top_k"], shared_experts=s["shared_experts"],
        rope_theta=s["rope_theta"], yarn=tuple(s["yarn"]), query_scale_beta=s["query_scale_beta"], max_seq=max_seq,
        norm_eps=s["norm_eps"], dtype=s["dtype"])


def serve_install(sizes: dict, bench: dict) -> None:
    """`serving_http` looks a `--preset` up in the registry of the program's
    model modules and draws weights through its `init`: register the cell's
    configuration under its name in the module's `PRESETS`, hand the engine the
    seed's weights in `init`'s place, and pass on the two engine settings the
    fleet's command line does not carry (argparse keeps a flag's last value).

    The harness warms one request a bucket of WHOLE prompt lengths, and this
    family's last prefill chunk has a compiled program a power of two of what
    FOLLOWS the chunks before it (and, after a prefix hit, the shared pages): so
    the engine's `warm_prefill` buckets, where the workload lists them, are run
    here on a scratch staging before the server starts, once the weights are
    drawn. That is set-up: nothing then compiles inside the window."""
    import sys

    from chipside import seed_weights
    from tony_tpu.models import serving_http

    module, cfg = program(sizes, bench["engine"]["max_len"])
    module.PRESETS[bench["config"]] = cfg

    def weights(_key, _cfg):
        params = seed_weights(sizes, bench["seed"])
        _warm_prefill(module, cfg, params, bench["engine"])
        return params

    serving_http.init = weights
    for flag in ("prefill_chunk", "decode_chunk"):
        if flag in bench["engine"]:
            sys.argv += ["--" + flag.replace("_", "-"), str(bench["engine"][flag])]


def _warm_prefill(module, cfg, params, engine: dict) -> None:
    """One prefill chunk of each length in the engine block's `warm_prefill`, through the family's own
    serving programs (the jitted functions the engine will call), on a staging of the engine's `max_len`."""
    import jax
    import jax.numpy as jnp

    progs = module.serving_programs(cfg, "paged")
    for rows in engine.get("warm_prefill", []):
        staging = progs.init_staging(engine["max_len"])
        logits, staging = progs.prefill_chunk(params, jnp.zeros((1, rows), jnp.int32), staging, rows)
        jax.block_until_ready(logits)
