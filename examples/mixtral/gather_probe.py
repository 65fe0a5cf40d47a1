"""Probe: per-row DMA gather (Pallas) vs XLA row gather at MoE bench shape.

Decides whether the fused grouped-GEMM kernel (ops/moe_gemm.py) can gather
token rows in-kernel via scalar-prefetched indices + per-row async DMA —
killing the materialized [PN, D] dispatch gather and its remat replay —
without the per-descriptor DMA issue cost eating the win (the builders' r3
notes, older than this code: the queued "in-kernel gather/combine" lever).

Arms (loop-in-jit, ITERS serialized iterations per jit call, input scaled
by (1+1e-9) each iteration to defeat CSE; whole output reduced so nothing
dead-codes):
  xla      — xs = x[idx] (the current _dispatch_gather forward)
  pallas   — per-row DMA straight into the pipelined output block
  pallas2  — per-row DMA into a double-buffered VMEM scratch (tile m+1's
             rows issued while tile m copies out) — the shape the fused
             kernel would use, where compute hides the issue latency
  control  — the loop scaffolding alone (subtract from the arms)

Run on the chip: python examples/mixtral/gather_probe.py
"""

from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

TILE = 256
ITERS = 16


def pallas_gather_direct(x, idx, tile=TILE):
    """Rows are DMA'd one by one straight into the pipelined output block.

    HBM slices must align to the (8, 128) bf16 tiling, so a row is viewed
    as an [8, D//8] tile: x arrives [BT, 8, D//8] (free reshape in HBM)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    PN = idx.shape[0]
    BT, D = x.shape
    x3 = x.reshape(BT, 8, D // 8)

    def kern(idx_ref, x_hbm, o_ref, sem):
        m = pl.program_id(0)

        def start(r, _):
            pltpu.make_async_copy(
                x_hbm.at[idx_ref[m * tile + r]], o_ref.at[r], sem
            ).start()
            return 0

        jax.lax.fori_loop(0, tile, start, 0)

        def wait(r, _):
            pltpu.make_async_copy(
                x_hbm.at[idx_ref[m * tile + r]], o_ref.at[r], sem
            ).wait()
            return 0

        jax.lax.fori_loop(0, tile, wait, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(PN // tile,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tile, 8, D // 8), lambda m, idx: (m, 0, 0)),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((PN, 8, D // 8), x.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=0, bytes_accessed=2 * PN * D * x.dtype.itemsize, transcendentals=0
        ),
    )(idx, x3)
    return out.reshape(PN, D)


def pallas_gather_pipelined(x, idx, tile=TILE):
    """Double-buffered: tile m+1's row DMAs issue while tile m copies out.

    Also answers whether the (tile, 8, D//8) → (tile, D) in-VMEM reshape
    the fused kernel needs is cheap (the copy-out does exactly that)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    PN = idx.shape[0]
    BT, D = x.shape
    x3 = x.reshape(BT, 8, D // 8)

    def kern(idx_ref, x_hbm, o_ref, buf, sem):
        m = pl.program_id(0)
        nm = pl.num_programs(0)

        def start(t, slot):
            def row(r, _):
                pltpu.make_async_copy(
                    x_hbm.at[idx_ref[t * tile + r]], buf.at[slot, r], sem.at[slot]
                ).start()
                return 0

            jax.lax.fori_loop(0, tile, row, 0)

        @pl.when(m == 0)
        def _warm():
            start(0, 0)

        @pl.when(m + 1 < nm)
        def _next():
            start(m + 1, (m + 1) % 2)

        slot = m % 2

        def wait(r, _):
            pltpu.make_async_copy(
                x_hbm.at[idx_ref[m * tile + r]], buf.at[slot, r], sem.at[slot]
            ).wait()
            return 0

        jax.lax.fori_loop(0, tile, wait, 0)
        o_ref[...] = buf[slot].reshape(tile, D)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(PN // tile,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tile, D), lambda m, idx: (m, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, tile, 8, D // 8), jnp.bfloat16),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((PN, D), x.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=0, bytes_accessed=2 * PN * D * x.dtype.itemsize, transcendentals=0
        ),
    )(idx, x3)


def make_loop(arm):
    @jax.jit
    def loop(x, idx):
        def body(i, carry):
            x, acc = carry
            if arm == "xla":
                xs = x[idx]
            elif arm == "xla_tiled":
                # gather (8, D//8) slabs instead of flat rows — does XLA's
                # gather run faster on tile-aligned slices?
                xs = x.reshape(x.shape[0], 8, x.shape[1] // 8)[idx].reshape(
                    idx.shape[0], x.shape[1]
                )
            elif arm == "pallas":
                xs = pallas_gather_direct(x, idx)
            elif arm == "pallas2":
                xs = pallas_gather_pipelined(x, idx)
            else:
                xs = None
            if xs is not None:
                acc = acc + xs.astype(jnp.float32).sum()
            # true serialization: x depends on acc (isnan can't be folded,
            # and the select defeats CSE across iterations) — note a plain
            # x * (1+eps) folds away in bf16 and CSE collapses the loop
            x = jnp.where(jnp.isnan(acc), jnp.bfloat16(0), x)
            return (x, acc)

        # acc starts data-dependent so the control arm's chain can't fold
        x, acc = jax.lax.fori_loop(0, ITERS, body, (x, x[0, 0].astype(jnp.float32)))
        return acc

    return loop


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--bt", type=int, default=65536)
    p.add_argument("--d", type=int, default=1024)
    p.add_argument("--pn", type=int, default=133120)
    p.add_argument("--reps", type=int, default=3)
    args = p.parse_args()

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (args.bt, args.d), jnp.bfloat16)
    idx = jax.random.randint(jax.random.PRNGKey(1), (args.pn,), 0, args.bt, jnp.int32)

    # correctness first (tiny shapes would hide alignment bugs; use real ones)
    ref = np.asarray(x)[np.asarray(idx)]
    for name, fn in [("pallas", pallas_gather_direct), ("pallas2", pallas_gather_pipelined)]:
        got = np.asarray(jax.jit(fn)(x, idx))
        ok = np.array_equal(got, ref)
        print(f"{name} correctness: {'OK' if ok else 'MISMATCH'}")
        if not ok:
            bad = np.argwhere(~(got == ref).all(axis=1))[:5]
            print("  first bad rows:", bad.ravel())

    results = {}
    for arm in ["control", "xla", "xla_tiled", "pallas", "pallas2"]:
        loop = make_loop(arm)
        loop(x, idx).block_until_ready()  # compile
        best = float("inf")
        for _ in range(args.reps):
            t0 = time.perf_counter()
            loop(x, idx).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        per_iter = best / ITERS * 1e3
        results[arm] = per_iter
        print(f"{arm:8s}: {per_iter:7.3f} ms/iter (best of {args.reps})")

    ctl = results["control"]
    for arm in ["xla", "xla_tiled", "pallas", "pallas2"]:
        net = results[arm] - ctl
        gb = 2 * args.pn * args.d * 2 / 1e9
        print(f"{arm:8s}: net {net:7.3f} ms  ({gb / (net / 1e3):6.1f} GB/s effective)")


if __name__ == "__main__":
    main()
