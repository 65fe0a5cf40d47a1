"""The olmo_hybrid family: Olmo-Hybrid, a decoder whose layers take turns between
gated delta-rule layers (`linear_attention`: a convolution, a recurrent float32
state a head, a decay and a write strength the token chooses) and plain
multi-head softmax attention without rotary embedding (`full_attention`), three
to one, on OLMo's reordered norm; it runs through
`tony_tpu/models/olmo_hybrid.py`. What a family answers for:
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

REFERENCE = "families.olmo_hybrid_reference"
COUNTS = "families.olmo_hybrid_counts"

#: published keys whose value names the model and changes no arithmetic here
DESCRIBES = ("model_type", "max_position_embeddings", "torch_dtype")
#: published keys this family computes one value of, and what that value is
FIXED = {"hidden_act": "silu", "attention_bias": False, "tie_word_embeddings": False, "linear_allow_neg_eigval": True,
         "rope_parameters": {"rope_theta": None}}
SIZES = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "layer_types", "num_attention_heads",
         "num_key_value_heads", "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
         "linear_value_head_dim", "linear_conv_kernel_dim", "rms_norm_eps")
#: `assumed` entries that are choices: the one value of each that this family computes
ASSUMED_CHOICES = {
    "norm_placement": "on_each_sublayers_output_then_added",
    "qk_norm": "rmsnorm_over_the_whole_projection_before_the_heads",
    "full_attention_rope": "none",
    "linear_qkv_order": "convolution_then_silu_then_l2norm",
    "output_gate": "rmsnorm_per_head_shared_weight_times_silu_gate",
    "decay_init": "A_log=log_U(1,16);dt_bias=softplus_inverse(exp_U(log_0.001,log_0.1))",
}
KINDS = ("linear_attention", "full_attention")

#: the program's model module, beside which this family's files mean anything
PROGRAM = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
                       "tony_tpu", "models", "olmo_hybrid.py")


def _by_deployment(value, deployment: str, key: str):
    if isinstance(value, dict) and "source" in value:
        if deployment not in value:
            raise KeyError(f"configuration has no {key} for deployment {deployment!r}: {sorted(value)}")
        return value[deployment]
    return value


def sizes(cfg: dict, deployment: str) -> dict:
    if not os.path.isfile(PROGRAM):
        # a checkout older than the model (the benchmark's files laid over a parent commit): say so in
        # run.py's own process, which then exits 2 at once, before a fleet is launched that cannot come up
        raise NoFamily(f"the program has no {PROGRAM}: the olmo_hybrid family's configurations run from the commit "
                       "that adds that model module")
    known_keys_only(cfg, SIZES + DESCRIBES + tuple(FIXED), "olmo_hybrid")
    for key, value in FIXED.items():
        if key not in cfg or cfg[key] != value:
            raise ValueError(f"the olmo_hybrid family computes {key}={value!r} only, the configuration has {cfg.get(key, 'no such key')!r}")
    assumed = cfg.get("assumed", {})
    missing = [k for k in ("head_dim", *ASSUMED_CHOICES) if "value" not in assumed.get(k, {})]
    if missing:
        raise KeyError(f"the configuration's `assumed` lacks {missing}: what the source does not give is written down, "
                       "as {\"value\": ..., \"why\": ...}, not left to a default in the code")
    for key, value in ASSUMED_CHOICES.items():
        if assumed[key]["value"] != value:
            raise ValueError(f"the olmo_hybrid family computes {key}={value!r} only, `assumed` has {assumed[key]['value']!r}")
    layers = depth(cfg, deployment)
    kinds = list(_by_deployment(cfg["layer_types"], deployment, "layer_types"))
    if len(kinds) != layers or set(kinds) - set(KINDS):
        raise ValueError(f"layer_types for {deployment!r} names {len(kinds)} layers of kinds {sorted(set(kinds))}: "
                         f"{layers} layers of {KINDS} are wanted")
    if cfg["linear_num_value_heads"] != cfg["linear_num_key_heads"]:
        raise ValueError("the linear layers' keys and values have a head each (linear_num_value_heads = linear_num_key_heads) here")
    return {
        "module": cfg["module"],
        "vocab": cfg["vocab_size"],
        "d_model": cfg["hidden_size"],
        "layers": layers,
        "layer_types": tuple(kinds),
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": int(assumed["head_dim"]["value"]),
        "lin_heads": cfg["linear_num_key_heads"],
        "lin_key_dim": cfg["linear_key_head_dim"],
        "lin_value_dim": cfg["linear_value_head_dim"],
        "conv_taps": cfg["linear_conv_kernel_dim"],
        "d_ff": cfg["intermediate_size"],
        "norm_eps": float(cfg["rms_norm_eps"]),
        "dtype": cfg.get("torch_dtype", "bfloat16"),
    }


def program(sizes: dict, max_seq: int, page_len: int = 256, snapshots: int = 0):
    from tony_tpu.models import olmo_hybrid

    s = sizes
    return olmo_hybrid, olmo_hybrid.OlmoHybridConfig(
        vocab_size=s["vocab"], d_model=s["d_model"], layer_types=tuple(s["layer_types"]), n_heads=s["heads"],
        n_kv_heads=s["kv_heads"], head_dim=s["head_dim"], lin_heads=s["lin_heads"], lin_key_dim=s["lin_key_dim"],
        lin_value_dim=s["lin_value_dim"], conv_taps=s["conv_taps"], d_ff=s["d_ff"], max_seq=max_seq,
        norm_eps=s["norm_eps"], page_len=page_len, snapshots=snapshots, dtype=s["dtype"])


def serve_install(sizes: dict, bench: dict) -> None:
    """`serving_http` looks a `--preset` up in the registry of the program's
    model modules and draws weights through its `init`: register the cell's
    configuration under its name in the module's `PRESETS` (with the engine
    block's page length, at whose edges this family keeps its state, and its
    `snapshots`: how many the store holds), hand the engine the seed's weights in
    `init`'s place, and pass on the two engine settings the fleet's command line
    does not carry (argparse keeps a flag's last value).

    The harness warms one request a bucket of WHOLE prompt lengths, and this
    family's last prefill chunk has a compiled program a page times a power of
    two of what FOLLOWS the chunks before it (and, after a prefix hit, the shared
    pages): so the engine's `warm_prefill` buckets, where the workload lists
    them, are run here on a scratch staging before the server starts, once the
    weights are drawn. That is set-up: nothing then compiles inside the window."""
    import sys

    from chipside import seed_weights
    from tony_tpu.models import serving_http

    engine = bench["engine"]
    module, cfg = program(sizes, engine["max_len"], engine["page_len"], engine.get("snapshots", 0))
    module.PRESETS[bench["config"]] = cfg

    def weights(_key, _cfg):
        params = seed_weights(sizes, bench["seed"])
        _warm_prefill(module, cfg, params, engine)
        return params

    serving_http.init = weights
    for flag in ("prefill_chunk", "decode_chunk"):
        if flag in engine:
            sys.argv += ["--" + flag.replace("_", "-"), str(engine[flag])]


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
