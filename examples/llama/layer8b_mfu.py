"""Measured per-layer MFU at the 8B geometry on ONE real chip.

Full 8B training cannot fit a single v5e, but one transformer layer at the
exact 8B geometry (d4096 / F14336 / H32 / KV8 / Dh128) at the AOT fsdp=64
plan's per-chip shape (batch 1 × seq 8192) can. The fsdp=64 HBM plan
(examples/llama/aot_fsdp64.py) assumes 8B matches the 0.87B
bench proxy's efficiency — this measures that assumption directly: R
applications of the layer (fwd+bwd, flash remat, shared weights) inside one
jit, one scalar fetch (a per-call dispatch floor would swamp per-call timing).

    python examples/llama/layer8b_mfu.py [--reps 8] [--seq 8192] [--batch 1]
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time

import jax
import jax.numpy as jnp


def main() -> int:
    from tony_tpu.models import llama
    from tony_tpu.ops import attention as attn_ops
    from tony_tpu.ops import layers as L
    from tony_tpu.train.metrics import detect_peak_flops, transformer_flops_per_token

    p = argparse.ArgumentParser()
    p.add_argument("--reps", type=int, default=8)
    p.add_argument("--seq", type=int, default=8192)
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--steps", type=int, default=6)
    args = p.parse_args()

    cfg = dataclasses.replace(
        llama.LLAMA3_8B, n_layers=1, max_seq=args.seq,
        remat=True, remat_policy="flash", attn_impl="auto",
    )
    D = cfg.d_model
    key = jax.random.PRNGKey(0)
    lp = {k: v[0] for k, v in llama.init(key, cfg)["layers"].items()}
    x = jax.random.normal(jax.random.fold_in(key, 1), (args.batch, args.seq, D), jnp.bfloat16)
    cos, sin = L.rope_frequencies(cfg.head_dim, args.seq, cfg.rope_theta, cfg.rope_scaling)

    block = attn_ops.remat_block(
        functools.partial(llama._block, cos=cos, sin=sin, cfg=cfg, mesh=None),
        cfg.remat, cfg.remat_policy,
    )

    def loss(lp, x):
        def body(h, _):
            h, _ = block(h, lp)
            return h, None
        h, _ = jax.lax.scan(body, x, length=args.reps)
        return (h.astype(jnp.float32) ** 2).mean()

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))

    t0 = time.perf_counter()
    out = step(lp, x)
    float(out[0])
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(args.steps):
        out = step(lp, x)
        float(out[0])  # hard host sync per step (dispatch is async)
    dt = (time.perf_counter() - t0) / args.steps

    # per-layer training-FLOP basis: the shared 6N + causal-attention
    # formula, with N = this ONE layer's params (no embed/head)
    layer_params = sum(v.size for v in lp.values())
    fpt = transformer_flops_per_token(layer_params, 1, D, args.seq, training=True)
    tokens = args.batch * args.seq * args.reps
    mfu = fpt * tokens / dt / detect_peak_flops()
    print(json.dumps({
        "metric": "llama8b_layer_train_mfu_1chip",
        "value": round(mfu, 4),
        "unit": "mfu",
        "layer_params": layer_params,
        "batch": args.batch, "seq": args.seq, "reps": args.reps,
        "step_ms": round(dt * 1000, 2),
        "warmup_s": round(compile_s, 1),
        "device": getattr(jax.devices()[0], "device_kind", "unknown"),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
