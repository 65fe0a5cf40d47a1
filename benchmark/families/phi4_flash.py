"""The phi4_flash family: Phi-4-mini-flash-reasoning (SambaY with differential
attention, arXiv:2507.06607), a decoder-hybrid-decoder: Mamba-1 mixers beside
512-window differential attention below ONE full-attention layer, whose keys and
values the cross-attention layers above it read again, beside gated memory units
that reuse the last scan's output; it runs through `tony_tpu/models/phi4_flash.py`.
What a family answers for: families/__init__.py. No JAX at the top level.

The published configuration gives the widths, `mb_per_layer` and `sliding_window`
and no equation. What it does not give stands under the configuration's `assumed`,
each entry {"value", "why"}: sizes and choices this family computes one value of
and refuses any other (each is one function in the program and one in the
reference), but for the sizes a tiny configuration has to shrink, which it reads.
"""

from __future__ import annotations

import os

from families import NoFamily, depth, known_keys_only

REFERENCE = "families.phi4_flash_reference"
COUNTS = "families.phi4_flash_counts"

#: published keys whose value names the model and changes no arithmetic here (the two dropouts are training's, and 0)
DESCRIBES = ("model_type", "max_position_embeddings", "torch_dtype", "embd_pdrop", "resid_pdrop")
#: published keys this family computes one value of, and what that value is
FIXED = {"hidden_act": "silu", "mb_per_layer": 2, "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False}
SIZES = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
         "sliding_window", "layer_norm_eps")
#: `assumed` entries that are sizes the source's modelling file defaults: read (a tiny configuration shrinks them)
ASSUMED_SIZES = ("head_dim", "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank", "memory_layer")
#: `assumed` entries that are choices: the one value of each that this family computes
ASSUMED_CHOICES = {
    "mamba_biases": "conv_bias;no_projection_bias",
    "layer_kinds": "mamba_even_to_M;window_odd_below_M;memory_from_M;full_and_cache_from_M+1;gmu_even_above;cross_odd_above",
    "position_term": "none",
    "differential_form": "stripes_even_odd;pair_values;lam0=0.8-0.6exp(-0.3i);rmsnorm_over_pair_times_1-lam0",
    "norm": "layer_norm_with_bias",
    "attention_biases": "qkv_and_out",
    "window_edge": "the_newest_sliding_window_keys_own_among_them",
    "state_dtype": "float32",
    "seeded_draws": "A_log=log(1..N);dt_bias=softplus_inverse(exp_U(log_0.001,log_0.1));D=1;lambda_vectors=N(0,0.1);biases=0.02;embed_fan_in=hidden",
}

#: the program's model module, beside which this family's files mean anything
PROGRAM = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
                       "tony_tpu", "models", "phi4_flash.py")


def sizes(cfg: dict, deployment: str) -> dict:
    if not os.path.isfile(PROGRAM):
        # a checkout older than the model (the benchmark's files laid over a parent commit): say so in
        # run.py's own process, which then exits 2 at once, before a fleet is launched that cannot come up
        raise NoFamily(f"the program has no {PROGRAM}: the phi4_flash family's configurations run from the commit "
                       "that adds that model module")
    known_keys_only(cfg, SIZES + DESCRIBES + tuple(FIXED), "phi4_flash")
    for key, value in FIXED.items():
        if key not in cfg or cfg[key] != value:
            raise ValueError(f"the phi4_flash family computes {key}={value!r} only, the configuration has {cfg.get(key, 'no such key')!r}")
    assumed = cfg.get("assumed", {})
    missing = [k for k in (*ASSUMED_SIZES, *ASSUMED_CHOICES) if "value" not in assumed.get(k, {}) or "why" not in assumed.get(k, {})]
    if missing:
        raise KeyError(f"the configuration's `assumed` lacks {missing}: what the source does not give is written down, "
                       "as {\"value\": ..., \"why\": ...}, not left to a default in the code")
    for key, value in ASSUMED_CHOICES.items():
        if assumed[key]["value"] != value:
            raise ValueError(f"the phi4_flash family computes {key}={value!r} only, `assumed` has {assumed[key]['value']!r}")
    given = {key: assumed[key]["value"] for key in ASSUMED_SIZES}
    layers, memory = depth(cfg, deployment), given["memory_layer"]
    if cfg["hidden_size"] != cfg["num_attention_heads"] * given["head_dim"]:
        raise ValueError("head_dim is hidden_size / num_attention_heads (the row gives none)")
    if memory % 2 or memory < 2 or (layers - memory) % 2 or layers < memory + 4:
        raise ValueError(f"memory_layer {memory} of {layers} layers: even, a (mamba, window) period below it, the full layer and a "
                         "(gmu, cross) period above")
    return {
        "module": cfg["module"],
        "vocab": cfg["vocab_size"],
        "d_model": cfg["hidden_size"],
        "layers": layers,
        "memory_layer": memory,
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": given["head_dim"],
        "window": cfg["sliding_window"],
        "d_ff": cfg["intermediate_size"],
        "d_inner": given["mamba_expand"] * cfg["hidden_size"],
        "ssm_state": given["mamba_d_state"],
        "conv_taps": given["mamba_d_conv"],
        "dt_rank": given["mamba_dt_rank"],
        "norm_eps": float(cfg["layer_norm_eps"]),
        "dtype": cfg.get("torch_dtype", "bfloat16"),
    }


def program(sizes: dict, max_seq: int, page_len: int = 256):
    """`max_seq` is the harness's: no position term, and the pool and the staging take the engine's `max_len`."""
    from tony_tpu.models import phi4_flash

    s = sizes
    return phi4_flash, phi4_flash.Phi4FlashConfig(
        vocab_size=s["vocab"], d_model=s["d_model"], n_layers=s["layers"], memory_layer=s["memory_layer"], n_heads=s["heads"],
        n_kv_heads=s["kv_heads"], head_dim=s["head_dim"], window=s["window"], d_ff=s["d_ff"], d_inner=s["d_inner"],
        ssm_state=s["ssm_state"], conv_taps=s["conv_taps"], dt_rank=s["dt_rank"], norm_eps=s["norm_eps"],
        page_len=page_len, dtype=s["dtype"])


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
