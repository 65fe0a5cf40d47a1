"""int8 weight-only quality gate: logit error + top-1 agreement vs bf16.

A 643 tok/s int8 serving number without a quality bound is half a result
(VERDICT r3 #7): this measures, on the SAME weights, the serving forward's
logits bf16-vs-int8 — mean/max |Δlogit|, top-1 agreement across positions,
and KL(bf16‖int8) — on the 1B model end-to-end and on the 8B GEOMETRY as a
single-layer gate (full 8B bf16 cannot coexist with int8 on one v5e's HBM;
the per-layer error bounds what each of the 32 layers contributes).

    python examples/serving/quality_int8.py --preset llama-1b --batch 4 --seq 512
    python examples/serving/quality_int8.py --geometry 8b --batch 2 --seq 256

Prints one JSON line per config.
"""

import argparse
import dataclasses
import json
import sys

import jax
import jax.numpy as jnp

from tony_tpu.models import llama
from tony_tpu.models.generate import _forward_with_cache, init_cache
from tony_tpu.ops.quant import quantize_tree


def logits_of(params, tokens, cfg):
    cache = init_cache(cfg, tokens.shape[0], tokens.shape[1])
    logits, _ = jax.jit(_forward_with_cache, static_argnames=("cfg",))(
        params, tokens, cache, cfg
    )
    return logits.astype(jnp.float32)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--preset", default="llama-1b")
    p.add_argument("--geometry", default="", choices=["", "8b"],
                   help="'8b': single-layer gate at the 8B dims instead of a preset")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--seq", type=int, default=512)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    if args.geometry == "8b":
        cfg = dataclasses.replace(llama.LLAMA3_8B, n_layers=1, max_seq=args.seq)
        label = "8b_geometry_1layer"
    else:
        cfg = dataclasses.replace(llama.PRESETS[args.preset], max_seq=args.seq)
        label = args.preset
    key = jax.random.PRNGKey(args.seed)
    params = llama.init(key, cfg)
    tokens = jax.random.randint(
        jax.random.fold_in(key, 1), (args.batch, args.seq), 0, cfg.vocab_size
    )

    ref = logits_of(params, tokens, cfg)
    qparams, before, after = quantize_tree(params)
    got = logits_of(qparams, tokens, cfg)

    d = jnp.abs(got - ref)
    ref_scale = jnp.abs(ref).mean()
    top1 = (jnp.argmax(got, -1) == jnp.argmax(ref, -1)).mean()
    logp_ref = jax.nn.log_softmax(ref, -1)
    logp_got = jax.nn.log_softmax(got, -1)
    kl = (jnp.exp(logp_ref) * (logp_ref - logp_got)).sum(-1).mean()
    print(json.dumps({
        "metric": f"int8_quality_{label}",
        "value": round(float(top1), 4),
        "unit": "top1_agreement",
        "mean_abs_dlogit": round(float(d.mean()), 4),
        "max_abs_dlogit": round(float(d.max()), 3),
        "mean_abs_logit_bf16": round(float(ref_scale), 3),
        "kl_bf16_to_int8": round(float(kl), 5),
        "weights_gb": [round(before / 1e9, 2), round(after / 1e9, 2)],
        "batch": args.batch, "seq": args.seq,
        "device": jax.devices()[0].device_kind,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
