"""Fixed-occupancy serve-parity A/B (VERDICT r4 #7).

    python examples/serving/bench_endpoint.py --slots 16 --window 45

Measures the HTTP layer's overhead with occupancy and ambient drift
cancelled out, in ONE process/session:

  A.  bare engine, closed loop — a small pending backlog keeps all S
      slots fed; every retirement is refilled before the next step, so
      occupancy is pinned at S.
  B.  HTTP endpoint — EngineServer + ThreadingHTTPServer driven by S
      closed-loop blocking clients, each resubmitting the instant its
      response lands; occupancy pinned at S again.
  A'. bare engine repeated, so ambient drift across the session shows up
      as A vs A' disagreement instead of polluting the B/A ratio.

All three phases decode the same ~0.9B bench Llama with identical slot
count, prompt length, and token budget. Tokens are counted over a timed
steady-state window (after a warmup). The headline is
endpoint / mean(engine, engine2): at equal occupancy this ratio IS the
HTTP layer's overhead (queues + handler threads + JSON + socket writes).

The round-4 session could not produce this number (drifting ambient +
open-loop clients conflated occupancy with overhead) — this driver is the fixed-occupancy design the verdict asked for.
"""

from __future__ import annotations

import argparse
import dataclasses
import http.client
import json
import sys
import threading
import time

import jax
import numpy as np

from tony_tpu.models import llama
from tony_tpu.models.serving import ContinuousBatcher
from tony_tpu.models.serving_http import EngineServer, _Handler
from tony_tpu.cluster.executor import pick_free_port


def _build(cfg, args):
    params = llama.init(jax.random.PRNGKey(0), cfg)
    return ContinuousBatcher(
        params, cfg, num_slots=args.slots, max_len=args.max_len,
        decode_chunk=args.chunk, attn=args.attn, kv=args.kv,
    )


def _prompts(cfg, args, seed=0):
    rng = np.random.default_rng(seed)

    def make():
        return rng.integers(0, cfg.vocab_size, args.prompt_len).tolist()

    return make


def run_engine_phase(cfg, args) -> float:
    """Closed-loop direct drive; returns steady-state tokens/sec."""
    eng = _build(cfg, args)
    make = _prompts(cfg, args)
    backlog = 4  # refill margin: retirements are replaced before admission starves

    def top_up():
        in_flight = len(eng.pending) + len(eng._staged) + len(eng.running)
        for _ in range(max(args.slots + backlog - in_flight, 0)):
            eng.submit(make(), max_new_tokens=args.new_tokens)

    def produced():
        return sum(len(r.out) for r in eng.running.values()) + sum(
            len(v) for v in eng.done.values()
        )

    top_up()
    eng.step()  # prefill + decode-chunk compile warmup
    t_end_warm = time.perf_counter() + args.warmup
    while time.perf_counter() < t_end_warm:
        top_up()
        eng.step()
    # done{} only ever grows in this loop; snapshot-delta excludes warmup
    tok0, t0 = produced(), time.perf_counter()
    t_end = t0 + args.window
    while time.perf_counter() < t_end:
        top_up()
        eng.step()
    jax.block_until_ready(eng.tokens)
    dt = time.perf_counter() - t0
    return (produced() - tok0) / dt


def run_endpoint_phase(cfg, args) -> tuple[float, float]:
    """S closed-loop HTTP clients; returns (generated tok/s, delivered tok/s)."""
    from http.server import ThreadingHTTPServer

    eng = _build(cfg, args)
    srv = EngineServer(eng).start()
    handler = type("H", (_Handler,), {"server_ref": srv, "tokenizer": None})
    port = pick_free_port()
    httpd = ThreadingHTTPServer(("127.0.0.1", port), handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()

    stop = threading.Event()
    errors: list[str] = []

    def client(seed: int) -> None:
        make = _prompts(cfg, args, seed)
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
        body_tmpl = {"max_tokens": args.new_tokens, "stream": False}
        while not stop.is_set():
            body = json.dumps({**body_tmpl, "prompt_tokens": make()})
            try:
                conn.request("POST", "/v1/completions", body,
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                data = resp.read()
                if resp.status != 200:
                    errors.append(f"{resp.status}: {data[:120]!r}")
                    return
            except OSError as e:  # server going down at phase end
                if not stop.is_set():
                    errors.append(repr(e))
                return
        conn.close()

    n_clients = args.clients or args.slots
    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(n_clients)]
    for t in threads:
        t.start()
    time.sleep(args.warmup + 5.0)  # compile + ramp to full occupancy
    if errors:
        sys.exit(f"endpoint clients failed during warmup: {errors[:3]}")
    s0, t0 = srv.stats(), time.perf_counter()
    time.sleep(args.window)
    s1, t1 = srv.stats(), time.perf_counter()
    stop.set()
    for t in threads:
        t.join(timeout=30)
    srv.stop(timeout_s=60)
    httpd.shutdown()
    if errors:
        sys.exit(f"endpoint clients failed mid-window: {errors[:3]}")
    dt = t1 - t0
    gen = (s1["tokens_out"] - s0["tokens_out"]) / dt
    deliv = (s1["tokens_delivered"] - s0["tokens_delivered"]) / dt
    # occupancy sanity: the ratio is only meaningful if the window ran full
    if s1["slots_active"] < args.slots - 2:
        print(f"[bench] WARNING: only {s1['slots_active']}/{args.slots} slots "
              f"active at window end", file=sys.stderr)
    return gen, deliv


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--slots", type=int, default=16)
    p.add_argument("--prompt-len", type=int, default=128)
    p.add_argument("--new-tokens", type=int, default=64)
    p.add_argument("--max-len", type=int, default=512)
    p.add_argument("--chunk", type=int, default=8)
    p.add_argument("--attn", default="auto", choices=["auto", "ragged", "bucketed"])
    p.add_argument("--kv", default="dense", choices=["dense", "paged"])
    p.add_argument("--warmup", type=float, default=10.0)
    p.add_argument("--window", type=float, default=45.0)
    p.add_argument("--clients", type=int, default=0,
                   help="closed-loop client count (0 = --slots). slots+2 "
                        "probes whether the resubmission roundtrip gap "
                        "(the only occupancy difference vs phase A) matters")
    p.add_argument("--preset", default="bench-1b", choices=["bench-1b", "tiny"],
                   help="tiny: 4-layer toy model (mechanics smoke on CPU)")
    args = p.parse_args()

    if args.preset == "tiny":
        cfg = llama.LlamaConfig(
            vocab_size=256, d_model=128, n_layers=4, n_heads=4, n_kv_heads=2,
            d_ff=256, max_seq=args.max_len,
        )
    else:
        cfg = dataclasses.replace(llama.LLAMA_1B, max_seq=args.max_len)

    print("[bench] phase A: bare engine, closed loop", file=sys.stderr)
    eng1 = run_engine_phase(cfg, args)
    print(f"[bench]   engine: {eng1:.1f} tok/s", file=sys.stderr)
    print("[bench] phase B: HTTP endpoint, closed-loop clients", file=sys.stderr)
    ep_gen, ep_deliv = run_endpoint_phase(cfg, args)
    print(f"[bench]   endpoint: {ep_gen:.1f} generated, "
          f"{ep_deliv:.1f} delivered tok/s", file=sys.stderr)
    print("[bench] phase A': bare engine again (ambient check)", file=sys.stderr)
    eng2 = run_engine_phase(cfg, args)
    print(f"[bench]   engine: {eng2:.1f} tok/s", file=sys.stderr)

    mean_eng = (eng1 + eng2) / 2
    out = {
        "metric": "serve_endpoint_vs_engine_fixed_occupancy",
        "engine_tok_s": round(eng1, 1),
        "engine2_tok_s": round(eng2, 1),
        "endpoint_tok_s": round(ep_gen, 1),
        "endpoint_delivered_tok_s": round(ep_deliv, 1),
        "value": round(ep_gen / mean_eng, 4),
        "unit": "endpoint/engine throughput ratio at equal occupancy",
        "ambient_drift": round(abs(eng1 - eng2) / mean_eng, 4),
        "slots": args.slots,
        "clients": args.clients or args.slots,
        "window_s": args.window,
        "device": getattr(jax.devices()[0], "device_kind", "unknown"),
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
