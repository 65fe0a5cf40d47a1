"""Benchmark harness: measures this framework's training throughput + MFU.

The reference published no throughput numbers (BASELINE.json: "published": {});
the north star is ≥45% MFU on Llama pretraining. This harness runs the
flagship Llama train step on the available chip(s) and prints ONE JSON line:

    {"metric": ..., "value": <MFU>, "unit": "mfu", "vs_baseline": <mfu/0.45>}

Presets scale the model to the hardware (a single v5e chip benches a ~0.9B
Llama; the 8B config needs a slice). Run `python bench.py --help` for knobs.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

NORTH_STAR_MFU = 0.45


def _build_presets():
    from tony_tpu.models import llama, mixtral

    # ~0.9B params: fits one 16G v5e chip with Adam + remat at seq 2048.
    # Best measured single-chip recipe: batch 12, remat_policy="flash" (rung 1
    # of ops/attention.REMAT_LADDER: pin only the flash-kernel outputs; replay
    # the cheap matmuls), CE fused per 1024-token chunk (the builders'
    # rounds-1-5 ladder, older than this code and than the rungs above 1; the
    # train loop's "auto" would choose from the device's memory, bench.py
    # builds its own step and so names its rung).
    bench_1chip = dataclasses.replace(
        llama.LLAMA_1B, max_seq=2048, remat=True, remat_policy="flash",
        attn_impl="auto", ce_chunk=1024,
    )
    tiny = dataclasses.replace(llama.LLAMA_TINY, max_seq=128)
    # ~0.5B-total / ~0.17B-active MoE that fits one chip (all 8 experts
    # local; EP shards them over the `expert` axis on a slice). MFU is
    # computed on ACTIVE params — the honest MoE basis. head_dim is 128
    # (like real Mixtral): Dh=64 measured 4.8pt slower (lane underfill).
    # ce_chunk 512 (not 1024): the smaller CE logits buffer is what lets
    # batch 44 fit — b44+ce512 measured 35.3% vs b32+ce1024 33.6% (r3);
    # b48 OOMs on a ~334M overshoot no knob moves
    moe_1chip = mixtral.MixtralConfig(
        vocab_size=32_000, d_model=1024, n_layers=8, n_heads=8, n_kv_heads=4,
        d_ff=2048, max_seq=2048, num_experts=8, top_k=2,
        remat=True, remat_policy="flash", ce_chunk=512,
    )
    from tony_tpu.models import bert

    bert_base = dataclasses.replace(bert.BERT_BASE, remat=True, attn_impl="auto")
    return {
        "tiny": (llama, tiny, 8, 128),          # (module, config, batch, seq)
        "1chip": (llama, bench_1chip, 12, 2048),  # single v5e
        "8b": (llama, llama.LLAMA3_8B, 8, 4096),  # needs a slice (FSDP over ICI)
        "moe": (mixtral, moe_1chip, 44, 2048),    # Mixtral-style MoE, single v5e
        "bert": (bert, bert_base, 384, 512),      # BASELINE config #2, single v5e
    }


def run_bench(
    preset: str,
    steps: int,
    warmup: int,
    batch: int | None,
    seq: int | None,
    remat_policy: str | None = None,
    ce_chunk: int | None = None,
    mu_dtype: str = "",
    moe_dispatch: str | None = None,
    sync_every_step: bool = False,
    profile_dir: str | None = None,
) -> dict:
    import jax

    from tony_tpu.parallel import MeshSpec
    from tony_tpu.train import OptimizerConfig, Throughput, make_train_step, sharded_init
    from tony_tpu.train.metrics import detect_peak_flops

    model, cfg, B, T = _build_presets()[preset]
    B = batch or B
    T = seq or T
    cfg = dataclasses.replace(cfg, max_seq=T)
    fields = {f.name for f in dataclasses.fields(cfg)}
    if remat_policy is not None:
        override = {"remat": remat_policy != "none"}
        if "remat_policy" in fields:
            override["remat_policy"] = remat_policy
        elif remat_policy not in ("none", "full"):
            print(f"[bench] {type(cfg).__name__} has no remat_policy field: "
                  f"--remat-policy {remat_policy} falls back to full remat", file=sys.stderr)
        cfg = dataclasses.replace(cfg, **override)
    if ce_chunk is not None:
        if "ce_chunk" in fields:
            cfg = dataclasses.replace(cfg, ce_chunk=ce_chunk)
        else:
            print(f"[bench] ignoring --ce-chunk: {type(cfg).__name__} has no such field",
                  file=sys.stderr)
    if moe_dispatch is not None:
        if "moe_dispatch" in fields:
            cfg = dataclasses.replace(cfg, moe_dispatch=moe_dispatch)
        else:
            print(f"[bench] ignoring --moe-dispatch: {type(cfg).__name__} has no such field",
                  file=sys.stderr)

    n_dev = len(jax.devices())
    spec = MeshSpec.auto(n_dev)  # fsdp over all chips
    mesh = spec.build()
    opt = OptimizerConfig(warmup_steps=10, total_steps=1000, mu_dtype=mu_dtype).build()
    state = sharded_init(
        lambda: model.init(jax.random.PRNGKey(0), cfg), model.sharding_rules(cfg), mesh, opt
    )
    step_fn = make_train_step(functools.partial(model.loss_fn, cfg=cfg, mesh=mesh), opt)

    key = jax.random.PRNGKey(1)
    batch_data = model.synthetic_batch(key, B, T, cfg)

    t_compile = time.perf_counter()
    for _ in range(max(warmup, 2)):  # step 2 hits the donated-buffer recompile
        state, metrics = step_fn(state, batch_data)
        float(metrics["loss"])
    compile_s = time.perf_counter() - t_compile

    from tony_tpu.train.metrics import flops_per_token_for_batch

    meter = Throughput(
        tokens_per_step=B * T,
        flops_per_token=flops_per_token_for_batch(cfg, batch_data, T),
        n_chips=n_dev,
        peak_flops=None if jax.default_backend() == "cpu" else detect_peak_flops(),
    )
    meter.start()
    if sync_every_step:
        # the r1–r5 measurement loop, kept as the BEFORE control: a hard
        # host sync every step fetches the loss scalar and stalls dispatch
        # until the device drains.
        for _ in range(steps):
            state, metrics = step_fn(state, batch_data)
            loss_val = float(metrics["loss"])  # lint: disable=host-sync — this IS the control being measured
            meter.step()
    else:
        # pipelined dispatch: steps are enqueued back to back (device-side
        # execution is already serialized by the donated-state dependency),
        # and ONE final block_until_ready proves every enqueued step
        # physically finished before the meter reads the clock. Same total
        # device work, no per-step host round trip — the aggregate time is
        # the honest steady-state measure; the per-step control run above
        # is what async dispatch would misreport WITHOUT the final sync.
        for _ in range(steps):
            state, metrics = step_fn(state, batch_data)
            meter.step()
        jax.block_until_ready(metrics["loss"])
        loss_val = float(metrics["loss"])
    r = meter.report()
    out = {
        "preset": preset,
        "model": model.__name__.rsplit(".", 1)[-1],
        "model_params": cfg.num_params(),
        "batch": B,
        "seq": T,
        "n_chips": n_dev,
        "device_kind": jax.devices()[0].device_kind,
        "warmup_s": round(compile_s, 2),
        "loss": loss_val,
        **{k: round(v, 4) for k, v in r.items()},
    }
    if profile_dir:
        # provenance capture (AFTER measurement, so the trace overhead never
        # skews the numbers): a short jax.profiler window of this exact
        # step/sync regime, referenced from the BENCH_* payload
        mode = "sync_per_step" if sync_every_step else "pipelined"
        out_dir = os.path.join(profile_dir, mode)
        os.makedirs(out_dir, exist_ok=True)
        jax.profiler.start_trace(out_dir)
        try:
            for _ in range(3):
                state, metrics = step_fn(state, batch_data)
                if sync_every_step:
                    float(metrics["loss"])  # lint: disable=host-sync — profiled control regime
            jax.block_until_ready(metrics["loss"])
        finally:
            # a failed capture must not leave the profiler armed — it
            # would skew every later measurement run in this process
            jax.profiler.stop_trace()
        out["profile_dir"] = out_dir
    return out


# ---------------------------------------------------------------------------
# On-chip kernel smoke: numerics of every hot Pallas path ON THIS BACKEND.
#
# Exists because interpret-mode tests are a numerics check, not a lowering
# check: a kernel that fails TPU lowering (or lowers to wrong math) while the
# CPU suite stays green shows up here as a hard failure, not as a silent MFU
# regression. Runs before every throughput bench (quick set) so the driver
# exercises it each round; `bench.py --smoke` runs the full set standalone.
# ---------------------------------------------------------------------------

def _smoke_checks(full: bool):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tony_tpu.ops import attention as A
    from tony_tpu.ops import layers as L
    from tony_tpu.ops import quant as Q

    def qkv(B, H, Hkv, T, D, seed=7):
        ks = [jax.random.fold_in(jax.random.PRNGKey(seed), i) for i in range(3)]
        q = jax.random.normal(ks[0], (B, H, T, D), jnp.float32) * 0.5
        k = jax.random.normal(ks[1], (B, Hkv, T, D), jnp.float32) * 0.5
        v = jax.random.normal(ks[2], (B, Hkv, T, D), jnp.float32) * 0.5
        return q, k, v

    def rel_err(a, b):
        scale = float(jnp.max(jnp.abs(b))) + 1e-9
        return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))) / scale

    def flash_fwd():
        q, k, v = qkv(1, 4, 4, 1024, 128)
        out = A._flash_fwd_impl(q, k, v, True, 256, 256)[0]
        want = A.attention_reference(q, k, v, causal=True)
        return rel_err(out, want)

    def flash_fwd_gqa():
        q, k, v = qkv(1, 4, 2, 512, 128, seed=11)
        out = A._flash_fwd_impl(q, k, v, True, 256, 256)[0]
        want = A.attention_reference(q, A.repeat_kv(k, 2), A.repeat_kv(v, 2), causal=True)
        return rel_err(out, want)

    def _bwd_err(B, H, Hkv, T, D, seed):
        q, k, v = qkv(B, H, Hkv, T, D, seed=seed)
        n_rep = H // Hkv
        w = jnp.arange(D, dtype=jnp.float32)

        def loss_flash(q, k, v):
            return (A._flash_trainable(q, k, v, True) * w).sum()

        def loss_ref(q, k, v):
            return (
                A.attention_reference(q, A.repeat_kv(k, n_rep), A.repeat_kv(v, n_rep), causal=True) * w
            ).sum()

        gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
        return max(rel_err(a, b) for a, b in zip(gf, gr))

    def flash_bwd():
        return _bwd_err(1, 4, 2, 1024, 128, seed=13)

    def flash_bwd_long():
        # the training cells' length: a kv head's k, v, dk and dv resident over 8192
        return _bwd_err(1, 2, 1, 8192, 64, seed=17)

    def flash_packed():
        # packed sequences: segment-confined attention fwd+bwd on chip
        q, k, v = qkv(1, 2, 2, 512, 128, seed=29)
        seg = jnp.where(jnp.arange(512) < 200, 1, 2)[None, :].astype(jnp.int32)
        w = jnp.arange(q.shape[-1], dtype=jnp.float32)

        def loss_flash(q, k, v):
            return (A._flash_trainable_seg(q, k, v, seg, True) * w).sum()

        def loss_ref(q, k, v):
            return (A.attention_reference(q, k, v, causal=True, segment_ids=seg) * w).sum()

        gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
        return max(rel_err(a, b) for a, b in zip(gf, gr))

    def flash_swa():
        # sliding-window attention fwd+bwd on chip (Mixtral parity)
        q, k, v = qkv(1, 2, 2, 1024, 128, seed=37)
        w = jnp.arange(q.shape[-1], dtype=jnp.float32)
        window = 300

        def loss_flash(q, k, v):
            return (A._flash_trainable(q, k, v, True, window) * w).sum()

        def loss_ref(q, k, v):
            return (A.attention_reference(q, k, v, causal=True, window=window) * w).sum()

        gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
        return max(rel_err(a, b) for a, b in zip(gf, gr))

    def chunked_ce():
        key = jax.random.PRNGKey(3)
        B, T, D, V = 2, 512, 256, 2048
        x = jax.random.normal(key, (B, T, D), jnp.float32) * 0.1
        head = jax.random.normal(jax.random.fold_in(key, 1), (D, V), jnp.float32) * 0.05
        tgt = jax.random.randint(jax.random.fold_in(key, 2), (B, T), 0, V)

        def chunked(x, h):
            return L.chunked_cross_entropy_loss(x, h, tgt, chunk=128)[0]

        def plain(x, h):
            return L.cross_entropy_loss(x @ h, tgt)[0]

        lc, gc = jax.value_and_grad(chunked, argnums=(0, 1))(x, head)
        lp, gp = jax.value_and_grad(plain, argnums=(0, 1))(x, head)
        return max(rel_err(jnp.asarray(lc), jnp.asarray(lp)), *(rel_err(a, b) for a, b in zip(gc, gp)))

    def int8_mm():
        key = jax.random.PRNGKey(5)
        x = jax.random.normal(key, (512, 1024), jnp.bfloat16)
        w = jax.random.normal(jax.random.fold_in(key, 1), (1024, 1024), jnp.float32)
        qt = Q.quantize_int8(w)
        out = Q.int8_matmul(x, qt)           # tile-aligned → Pallas kernel
        want = Q.int8_matmul_ref(x, qt)      # XLA reference of the SAME quantized math
        return rel_err(out, want)

    def moe_grouped_gemm():
        import dataclasses as dc

        from tony_tpu.parallel.expert import MoEConfig, moe_ffn

        E, D, F = 8, 256, 512
        ks = jax.random.split(jax.random.PRNGKey(7), 5)
        x = (jax.random.normal(ks[0], (4, 128, D)) * 0.5).astype(jnp.bfloat16)
        router = jax.random.normal(ks[1], (D, E))
        wg = (jax.random.normal(ks[2], (E, D, F)) / D**0.5).astype(jnp.bfloat16)
        wu = (jax.random.normal(ks[3], (E, D, F)) / D**0.5).astype(jnp.bfloat16)
        wd = (jax.random.normal(ks[4], (E, F, D)) / F**0.5).astype(jnp.bfloat16)
        kcfg = MoEConfig(num_experts=E, top_k=2, dispatch="ragged")
        xcfg = dc.replace(kcfg, dispatch="ragged_xla")

        def loss(cfg):
            def f(x, wg, wu, wd):
                y, _ = moe_ffn(x, router, wg, wu, wd, cfg)
                return (y.astype(jnp.float32) ** 2).sum()
            return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3)))

        lk, gk = loss(kcfg)(x, wg, wu, wd)
        lx, gx = loss(xcfg)(x, wg, wu, wd)
        return max(rel_err(jnp.asarray(lk), jnp.asarray(lx)),
                   *(rel_err(a, b) for a, b in zip(gk, gx)))

    def remat_parity():
        import dataclasses as dc
        import functools as ft

        from tony_tpu.models import llama

        cfg = dc.replace(llama.LLAMA_TINY, max_seq=256)
        params = llama.init(jax.random.PRNGKey(0), cfg)
        batch = llama.synthetic_batch(jax.random.PRNGKey(1), 2, 256, cfg)
        results = []
        for pol in ("none", "full", "dots", "flash"):
            c = dc.replace(cfg, remat=pol != "none", remat_policy=pol if pol != "none" else "full")
            loss, grads = jax.jit(
                jax.value_and_grad(lambda p: llama.loss_fn(p, batch, c, None)[0])
            )(params)
            gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in jax.tree.leaves(grads)))
            results.append((float(loss), float(gnorm)))
        l0, g0 = results[0]
        return max(
            max(abs(l - l0) / (abs(l0) + 1e-9), abs(g - g0) / (abs(g0) + 1e-9))
            for l, g in results[1:]
        )

    checks = [
        ("flash_fwd", flash_fwd, 2e-2),
        ("flash_fwd_gqa", flash_fwd_gqa, 2e-2),
        ("flash_bwd", flash_bwd, 2e-2),
        ("flash_bwd_long", flash_bwd_long, 2e-2),
        ("flash_packed", flash_packed, 2e-2),
        ("flash_swa", flash_swa, 2e-2),
        ("chunked_ce", chunked_ce, 2e-2),
        ("moe_grouped_gemm", moe_grouped_gemm, 3e-2),
    ]
    if full:
        checks += [
            ("int8_matmul", int8_mm, 2e-2),
            ("remat_parity", remat_parity, 2e-2),
        ]
    return checks


def run_smoke(full: bool = False) -> dict:
    """Run the kernel smoke set; returns {"passed": n, "total": n, "failures": [...]}."""
    results, failures = [], []
    for name, fn, tol in _smoke_checks(full):
        t0 = time.perf_counter()
        try:
            err = fn()
            ok = err < tol
            detail = f"max_rel_err={err:.2e} tol={tol:.0e}"
        except Exception as e:  # noqa: BLE001 — a lowering failure IS the signal
            ok, detail = False, f"{type(e).__name__}: {e}"
        dt = time.perf_counter() - t0
        print(f"[smoke] {name:22s} {'PASS' if ok else 'FAIL'}  {detail}  ({dt:.1f}s)",
              file=sys.stderr)
        results.append(ok)
        if not ok:
            failures.append(f"{name}: {detail}")
    return {"passed": sum(results), "total": len(results), "failures": failures}


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--preset", default=None, choices=["tiny", "1chip", "8b", "moe", "bert"])
    p.add_argument("--smoke", action="store_true",
                   help="run ONLY the on-chip kernel smoke (full set) and exit")
    p.add_argument("--no-smoke", action="store_true",
                   help="skip the quick kernel smoke that precedes the bench")
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--repeats", type=int, default=None,
                   help="measurement runs; the MEDIAN is reported (a single "
                        "run makes round-over-round deltas uninterpretable). "
                        "Default: 3 on the chip, 1 in a CPU rehearsal")
    p.add_argument("--batch", type=int, default=None)
    p.add_argument("--seq", type=int, default=None)
    p.add_argument("--remat-policy", default=None, choices=["none", "full", "dots", "flash"])
    p.add_argument("--ce-chunk", type=int, default=None, help="0 = materialize logits")
    p.add_argument("--mu-dtype", default="", choices=["", "bfloat16", "float32"],
                   help="Adam first-moment dtype (default: param dtype)")
    p.add_argument("--moe-dispatch", default=None,
                   choices=["ragged", "ragged_xla", "gather", "dense"],
                   help="override the MoE dispatch scheme (moe preset only)")
    p.add_argument("--profile-dir", default="profiles/bench",
                   help="where the before/after provenance traces land "
                        "(referenced from the output payload)")
    p.add_argument("--no-profile", action="store_true",
                   help="skip the profile captures and the per-step-sync "
                        "control run (faster; payload loses provenance)")
    args = p.parse_args()

    import jax

    from tony_tpu.runtime import enable_compile_cache

    backend = jax.default_backend()
    if backend == "cpu" and args.preset is None:
        # a measurement path that finds no chip fails: no CPU default, no
        # smaller preset in its place (a CPU rehearsal names its preset)
        print("[bench] no accelerator: the jax backend is 'cpu' and bench.py "
              "measures on the chip. For a CPU rehearsal pass --preset tiny "
              "explicitly (and TONY_PALLAS_INTERPRET=1 for the smoke).",
              file=sys.stderr)
        return 2
    preset = args.preset or "1chip"
    enable_compile_cache()

    if args.smoke:
        smoke = run_smoke(full=True)
        print(json.dumps({
            "metric": "kernel_smoke_pass_fraction",
            "value": round(smoke["passed"] / max(smoke["total"], 1), 4),
            "unit": "fraction",
            "vs_baseline": 1.0 if not smoke["failures"] else 0.0,
            **smoke,
        }))
        return 0 if not smoke["failures"] else 1

    smoke = None
    if not args.no_smoke and backend != "cpu":
        # every round, before trusting MFU: the hot kernels must be RIGHT on
        # this chip, not just fast (r1 lost 6 MFU points to a silent lowering
        # fallback the CPU suite could not see)
        smoke = run_smoke(full=False)
        if smoke["failures"]:
            print(json.dumps({"metric": "kernel_smoke_pass_fraction", **smoke}))
            return 1

    repeats = max(args.repeats if args.repeats is not None else (1 if backend == "cpu" else 3), 1)
    prof = None if args.no_profile else os.path.join(args.profile_dir, preset)
    # BEFORE control: the legacy per-step-sync measurement loop, one run —
    # the same binary/config measured the r1–r5 way, so the payload itself
    # proves how much the pipelined loop moved
    control = None
    if not args.no_profile:
        control = run_bench(
            preset, args.steps, args.warmup, args.batch, args.seq,
            args.remat_policy, args.ce_chunk, args.mu_dtype,
            args.moe_dispatch, sync_every_step=True, profile_dir=prof,
        )
    # median-of-N: the compile is cached after run 1, so extra runs cost
    # only measurement steps
    runs = [
        run_bench(
            preset, args.steps, args.warmup, args.batch, args.seq,
            args.remat_policy, args.ce_chunk, args.mu_dtype,
            args.moe_dispatch,
            profile_dir=prof if i == repeats - 1 else None,
        )
        for i in range(repeats)
    ]
    after_profile = next((x["profile_dir"] for x in runs if "profile_dir" in x), None)
    runs.sort(key=lambda r: r["tokens_per_sec"])
    r = runs[len(runs) // 2]
    mfu = r.get("mfu")  # None in a CPU rehearsal: MFU is a device metric
    out = {
        "metric": f"{r['model']}_train_mfu_{r['n_chips']}chip_{preset}",
        "value": mfu,
        "unit": "mfu",
        "vs_baseline": None if mfu is None else round(mfu / NORTH_STAR_MFU, 4),
        "platform": backend,
        "runs_mfu": [x.get("mfu") for x in runs],
        **{k: v for k, v in r.items() if k not in ("mfu", "profile_dir")},
    }
    if control is not None:
        out["control_sync_per_step"] = {
            "mfu": control.get("mfu"), "step_time_ms": control["step_time_ms"],
        }
    if control is not None or after_profile is not None:
        out["profile"] = {
            **({"before": control["profile_dir"]}
               if control and "profile_dir" in control else {}),
            **({"after": after_profile} if after_profile else {}),
        }
    if smoke is not None:
        out["kernel_smoke"] = f"{smoke['passed']}/{smoke['total']}"
    print(json.dumps(out))
    return 0

if __name__ == "__main__":
    sys.exit(main())
