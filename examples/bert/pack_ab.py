"""Packed-vs-padded BERT MLM throughput A/B (BASELINE config #2 follow-up).

Real MLM corpora have variable-length documents; the padded recipe gives
every document its own 512-token row and pays full attention+FFN cost on
the padding. Packing (data.pack_sequences) lays multiple documents per row
with segment-confined attention and per-segment positions, so the same
document stream needs fewer rows. Both arms run in ONE process on the same
synthetic length distribution; the metric is REAL (non-pad) content tokens
per second.

    python examples/bert/pack_ab.py [--steps 8] [--rows 384]
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
import numpy as np


def doc_stream(rng: np.random.Generator, n_docs: int, lo: int = 48, hi: int = 512):
    """Uniform[lo, hi] doc lengths — mean ~280 of a 512 row (a 1.8× pack)."""
    return [
        rng.integers(1, 30_000, size=rng.integers(lo, hi + 1)).astype(np.int32)
        for _ in range(n_docs)
    ]


def masked_positions(rng, seg: np.ndarray, m: int):
    """Sample m mask positions per row from REAL (non-pad) positions
    (with replacement — static shapes; fine for a throughput A/B)."""
    B, T = seg.shape
    pos = np.zeros((B, m), np.int32)
    for b in range(B):
        real = np.flatnonzero(seg[b] != 0)
        pos[b] = rng.choice(real, size=m, replace=True)
    return np.sort(pos, axis=1)


def run_arm(name, tokens, seg, cfg, steps, mask_frac=0.15):
    from tony_tpu.train import OptimizerConfig, make_train_step, sharded_init
    from tony_tpu.models import bert
    from tony_tpu.parallel import MeshSpec

    rng = np.random.default_rng(1)
    B, T = tokens.shape
    m = max(1, round(T * mask_frac))
    batch = {
        "tokens": jnp.asarray(tokens),
        "segment_ids": jnp.asarray(seg),
        "masked_pos": jnp.asarray(masked_positions(rng, seg, m)),
    }
    batch["masked_targets"] = jnp.take_along_axis(
        batch["tokens"], batch["masked_pos"], axis=1
    )
    mesh = MeshSpec.auto(len(jax.devices())).build()
    opt = OptimizerConfig(warmup_steps=10, total_steps=1000).build()
    state = sharded_init(
        lambda: bert.init(jax.random.PRNGKey(0), cfg), bert.sharding_rules(cfg), mesh, opt
    )
    step_fn = make_train_step(functools.partial(bert.loss_fn, cfg=cfg, mesh=mesh), opt)

    for _ in range(2):
        state, metrics = step_fn(state, batch)
        float(metrics["loss"])
    real_tokens = int((seg != 0).sum())
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step_fn(state, batch)
        float(metrics["loss"])  # hard host sync (dispatch is async)
    dt = (time.perf_counter() - t0) / steps
    out = {
        "arm": name, "rows": B, "seq": T, "real_tokens_per_batch": real_tokens,
        "step_ms": round(dt * 1000, 2),
        "content_tokens_per_sec": round(real_tokens / dt, 1),
    }
    print(json.dumps(out))
    return out


def main() -> int:
    from tony_tpu.data.dataset import pack_sequences
    from tony_tpu.models import bert

    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--rows", type=int, default=384, help="PADDED-arm row count")
    p.add_argument("--seq", type=int, default=512)
    args = p.parse_args()

    cfg = dataclasses.replace(bert.BERT_BASE, remat=True, attn_impl="auto")
    rng = np.random.default_rng(0)
    docs = doc_stream(rng, args.rows)

    # padded arm: one doc per row
    T = args.seq
    tok_pad = np.zeros((args.rows, T), np.int32)
    seg_pad = np.zeros((args.rows, T), np.int32)
    for i, d in enumerate(docs):
        tok_pad[i, : len(d)] = d[:T]
        seg_pad[i, : len(d)] = 1
    padded = run_arm("padded", tok_pad, seg_pad, cfg, args.steps)

    # packed arm: same docs, first-fit packed; pad row count to a multiple
    # of 8 for clean sharding
    tok_pk, seg_pk = pack_sequences(docs, T)
    keep = (len(tok_pk) // 8) * 8 or len(tok_pk)
    packed = run_arm("packed", tok_pk[:keep], seg_pk[:keep], cfg, args.steps)

    speedup = packed["content_tokens_per_sec"] / max(padded["content_tokens_per_sec"], 1)
    print(json.dumps({
        "metric": "bert_pack_speedup", "value": round(speedup, 3), "unit": "x",
        "padded_tok_s": padded["content_tokens_per_sec"],
        "packed_tok_s": packed["content_tokens_per_sec"],
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
