"""Dense-equivalent control for the MoE bench: a Llama with the SAME
active FLOPs per token as the moe preset (top-2 of 8 experts at F=2048 ≡
dense F=4096), same d/L/heads/vocab/seq, benched with the same recipe.

The gap between this number and the moe preset's active-param MFU is the
structural cost of MoE on this chip (dispatch movements + grouped-GEMM
rate).

Run: python examples/mixtral/dense_equiv.py [--batch 44]
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import time


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=44)
    p.add_argument("--seq", type=int, default=2048)
    p.add_argument("--steps", type=int, default=8)
    args = p.parse_args()

    import jax

    from tony_tpu.models import llama
    from tony_tpu.parallel import MeshSpec
    from tony_tpu.train import (
        OptimizerConfig, Throughput, make_train_step, sharded_init,
    )
    from tony_tpu.train.metrics import detect_peak_flops, flops_per_token_for_batch

    cfg = llama.LlamaConfig(
        vocab_size=32_000, d_model=1024, n_layers=8, n_heads=8, n_kv_heads=4,
        d_ff=4096, max_seq=args.seq, remat=True, remat_policy="flash",
        ce_chunk=512,
    )
    mesh = MeshSpec.auto(len(jax.devices())).build()
    opt = OptimizerConfig(warmup_steps=10, total_steps=1000).build()
    state = sharded_init(
        lambda: llama.init(jax.random.PRNGKey(0), cfg), llama.sharding_rules(cfg),
        mesh, opt,
    )
    step_fn = make_train_step(functools.partial(llama.loss_fn, cfg=cfg, mesh=mesh), opt)
    batch = llama.synthetic_batch(jax.random.PRNGKey(1), args.batch, args.seq, cfg)

    for _ in range(2):
        state, m = step_fn(state, batch)
        float(m["loss"])

    meter = Throughput(
        tokens_per_step=args.batch * args.seq,
        flops_per_token=flops_per_token_for_batch(cfg, batch, args.seq),
        n_chips=1,
        peak_flops=detect_peak_flops(),
    )
    meter.start()
    for _ in range(args.steps):
        state, m = step_fn(state, batch)
        float(m["loss"])
        meter.step()
    r = meter.report()
    print(json.dumps({"dense_equiv_mfu": r["mfu"], **{k: round(v, 2) for k, v in r.items()}}))


if __name__ == "__main__":
    main()
