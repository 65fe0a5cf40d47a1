"""``tony tune`` — sweep Pallas kernel block sizes on the real backend and
persist the winners to the autotuner cache (ops/tune.py).

The kernels ship with block sizes measured once on one device generation;
``tony tune`` re-fits them per (device kind, shape, dtype) so every later
run — bench, training, serving — picks the measured optimum up from the
cache automatically. See docs/performance.md for the playbook.

    tony tune --preset 1chip                 # the bench preset's geometries
    tony tune --flash 12,16,8,2048,128       # explicit B,H,Hkv,T,D
    tony tune --moe 8,1024,2048,90112        # explicit E,D,F,N-rows
    tony tune --int8 512,1024,1024           # explicit M,K,N
    tony tune --preset 1chip --dry-run       # print the ladder, write nothing

Exit codes: 0 tuned (or dry-run), 1 nothing measurable (no candidates /
every candidate failed), 2 bad usage.
"""

from __future__ import annotations

import argparse
import json
import sys


def _dims(spec: str, n: int, flag: str) -> list[int]:
    parts = [p for p in spec.replace("x", ",").split(",") if p]
    if len(parts) != n:
        raise ValueError(f"--{flag} wants {n} comma-separated ints, got {spec!r}")
    return [int(p) for p in parts]


def preset_jobs(preset: str) -> list[tuple[str, tuple]]:
    """(op, dims) sweep jobs for a bench preset's kernel geometries."""
    from tony_tpu.models import llama, mixtral

    if preset == "1chip":
        c = llama.LLAMA_1B
        return [("flash", (12, c.n_heads, c.n_kv_heads, 2048, c.head_dim))]
    if preset == "moe":
        # mirror bench.py's moe_1chip geometry (batch 44 × seq 2048, top-2)
        c = mixtral.MixtralConfig(
            vocab_size=32_000, d_model=1024, n_layers=8, n_heads=8, n_kv_heads=4,
            d_ff=2048, max_seq=2048, num_experts=8, top_k=2,
        )
        rows = 44 * 2048 * c.top_k
        return [
            ("flash", (44, c.n_heads, c.n_kv_heads, 2048, c.head_dim)),
            ("moe", (c.num_experts, c.d_model, c.d_ff, rows)),
        ]
    if preset == "tiny":
        return [("flash", (2, 4, 2, 512, 128))]
    raise ValueError(f"unknown --preset {preset!r} (want 1chip|moe|tiny)")


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="tony tune",
        description="autotune Pallas kernel block sizes for this backend "
                    "(docs/performance.md)")
    p.add_argument("--preset", default=None, choices=["1chip", "moe", "tiny"],
                   help="sweep the kernel geometries of a bench preset")
    p.add_argument("--flash", action="append", default=[], metavar="B,H,Hkv,T,D",
                   help="sweep flash attention fwd+bwd for this geometry "
                        "(repeatable)")
    p.add_argument("--moe", action="append", default=[], metavar="E,D,F,N",
                   help="sweep the fused MoE grouped GEMM (N = routed rows)")
    p.add_argument("--int8", action="append", default=[], metavar="M,K,N",
                   help="sweep the int8 weight matmul")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--steps", type=int, default=3,
                   help="timed runs per candidate (median wins)")
    p.add_argument("--cache", default=None,
                   help="cache file to write (default: $TONY_TUNE_CACHE; "
                        "one of the two is required unless --dry-run)")
    p.add_argument("--dry-run", action="store_true",
                   help="sweep and print, but persist nothing")
    p.add_argument("--json", action="store_true", help="machine-readable output")
    try:
        args = p.parse_args(argv)
    except SystemExit as e:
        return int(e.code or 0)

    from tony_tpu.ops import tune

    if not (args.dry_run or args.cache or tune.default_cache_path()):
        print(f"tony tune: no cache file to write: pass --cache or set "
              f"{tune.ENV_CACHE} (or --dry-run)", file=sys.stderr)
        return 2
    jobs: list[tuple[str, tuple]] = []
    try:
        if args.preset:
            jobs += preset_jobs(args.preset)
        jobs += [("flash", tuple(_dims(s, 5, "flash"))) for s in args.flash]
        jobs += [("moe", tuple(_dims(s, 4, "moe"))) for s in args.moe]
        jobs += [("int8", tuple(_dims(s, 3, "int8"))) for s in args.int8]
    except ValueError as e:
        print(f"tony tune: {e}", file=sys.stderr)
        return 2
    if not jobs:
        print("tony tune: nothing to sweep (pass --preset or an explicit "
              "--flash/--moe/--int8 geometry)", file=sys.stderr)
        return 2

    kind = tune.device_kind()
    rows: list[dict] = []
    for kernel, dims in jobs:
        if not args.json:
            print(f"[tune] {kernel} {dims} on {kind} ...", file=sys.stderr)
        if kernel == "flash":
            rows += tune.sweep_flash(*dims, dtype=args.dtype, steps=args.steps)
        elif kernel == "moe":
            E, D, F, N = dims
            rows += tune.sweep_moe(E, D, F, N, dtype=args.dtype, steps=args.steps)
        else:
            M, K, N = dims
            rows += tune.sweep_int8(M, K, N, dtype=args.dtype, steps=args.steps)

    measured = [r for r in rows if r.get("ms") is not None]
    if args.json:
        print(json.dumps({"device_kind": kind, "rows": [
            {**r, "shape": list(r["shape"])} for r in rows
        ]}))
    else:
        for r in rows:
            ms = "-" if r.get("ms") is None else f"{r['ms']:9.3f} ms"
            extra = f"  {r['error']}" if r.get("error") else ""
            print(f"  {r['op']:<12s} {'x'.join(map(str, r['shape'])):<24s} "
                  f"{json.dumps(r['params']):<44s} {ms}{extra}")
    if not measured:
        print("tony tune: no candidate completed a measurement", file=sys.stderr)
        return 1
    if args.dry_run:
        return 0
    cache = tune.TuneCache(args.cache) if args.cache else tune.shared_cache()
    tune.persist_winners(rows, cache)
    best = {}
    for r in measured:
        k = (r["op"], tuple(r["shape"]))
        if k not in best or r["ms"] < best[k]["ms"]:
            best[k] = r
    if not args.json:
        for (op, shape), r in sorted(best.items()):
            print(f"[tune] winner {op} {'x'.join(map(str, shape))}: "
                  f"{json.dumps(r['params'])} ({r['ms']:.3f} ms)")
        print(f"[tune] wrote {len(best)} winner(s) to {cache.path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
