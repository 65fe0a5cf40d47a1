"""Pallas kernel autotuner: measured block sizes per (device kind, shape, dtype).

The hot kernels (flash attention fwd/bwd in ops/attention.py, the fused MoE
grouped GEMM in ops/moe_gemm.py, the int8 matmul in ops/quant.py) ship with
block sizes measured ONCE on one device generation (the builders' r3 v5e
ladder, older than this code) and frozen as module constants. Those constants are the right
cold-cache default, but they are not the optimum for every (shape, dtype,
device) the framework meets — a different chip generation, head dim, or
sequence length can move the best block by 2+ MFU points, and until now the
only recourse was the ``TONY_FLASH_BQ``-style env overrides, global to the
whole process.

This module closes the loop:

- ``tony tune`` (cli/tune.py) sweeps each kernel's candidate block sizes on
  the REAL backend for the shapes a preset/model will run, wall-timing each
  candidate, and persists the winners to an on-disk JSON cache keyed by
  ``(op, device_kind, shape, dtype)``;
- the kernel entry points consult the cache at trace time via
  :func:`lookup` — a cache hit overrides the module-constant default, a miss
  (or ``TONY_TUNE_DISABLE=1``) keeps today's behavior byte-for-byte.

The cache file is the one ``TONY_TUNE_CACHE`` names (the executor exports it
from ``tony.tune.cache-file`` so tuned jobs see the same cache on every
worker). There is no default file: with the variable unset the kernels run
on the constants in the source, which git carries, and never on a file under
somebody's home directory. Lookups happen at trace time only — once per
compiled shape, never on the step path.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Iterable

from tony_tpu import constants

ENV_CACHE = constants.ENV_TUNE_CACHE      # cache file override (tony.tune.cache-file)
ENV_DISABLE = constants.ENV_TUNE_DISABLE  # "1" → kernels ignore the cache entirely


def default_cache_path() -> str:
    """``$TONY_TUNE_CACHE``, or "" — no cache file, source constants only."""
    return os.environ.get(ENV_CACHE, "")


def device_kind() -> str:
    """The backend's device kind (cache-key component); 'cpu' offline."""
    try:
        import jax

        return str(getattr(jax.devices()[0], "device_kind", jax.default_backend()))
    except Exception:  # noqa: BLE001 — no backend is a valid tuning-off state
        return "unknown"


def cache_key(op: str, kind: str, shape: Iterable[int], dtype: Any) -> str:
    return "|".join([op, kind, "x".join(str(int(d)) for d in shape), str(dtype)])


class TuneCache:
    """One JSON file of tuned winners: ``{key: {"params": {...}, "ms": f,
    "tuned_at": iso}}``. Reads are mtime-aware (a re-tune is picked up
    without a restart of THIS object); writes merge with the on-disk state
    so two concurrent tuners don't clobber each other's ops."""

    def __init__(self, path: str):
        self.path = path
        self._disk: dict[str, dict] = {}      # mirror of the file, mtime-tracked
        self._local: dict[str, dict] = {}     # puts not yet saved (win over disk)
        self._mtime: float | None = None

    def _refresh(self) -> None:
        try:
            mtime = os.stat(self.path).st_mtime_ns
        except OSError:
            self._disk, self._mtime = {}, None
            return
        if mtime == self._mtime:
            return
        try:
            with open(self.path, encoding="utf-8") as f:
                data = json.load(f)
            entries = data.get("entries", {})
            self._disk = entries if isinstance(entries, dict) else {}
            self._mtime = mtime
        except (OSError, ValueError):
            # a torn/corrupt cache must never break a kernel call: treat as
            # cold and let the next save rewrite it whole
            self._disk, self._mtime = {}, None

    def get(
        self, op: str, shape: Iterable[int], dtype: Any, kind: str | None = None
    ) -> dict[str, int] | None:
        """Tuned params for one kernel call site, or None (cold cache)."""
        self._refresh()
        key = cache_key(op, kind or device_kind(), shape, dtype)
        entry = self._local.get(key) or self._disk.get(key)
        params = entry.get("params") if isinstance(entry, dict) else None
        if not isinstance(params, dict):
            return None
        try:
            return {str(k): int(v) for k, v in params.items()}
        except (TypeError, ValueError):
            return None

    def put(
        self, op: str, shape: Iterable[int], dtype: Any, params: dict[str, int],
        ms: float | None = None, kind: str | None = None,
    ) -> None:
        self._local[cache_key(op, kind or device_kind(), shape, dtype)] = {
            "params": {str(k): int(v) for k, v in params.items()},
            **({"ms": round(float(ms), 3)} if ms is not None else {}),
            "tuned_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        }

    def save(self) -> str:
        """Atomic write (merged with any entries another process landed
        since our last refresh); returns the path written."""
        self._mtime = None
        self._refresh()
        merged = {**self._disk, **self._local}
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump({"version": 1, "entries": merged}, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, self.path)
        self._disk, self._local, self._mtime = merged, {}, None
        return self.path


_shared: TuneCache | None = None


def shared_cache() -> TuneCache:
    """Process-wide cache instance bound to the CURRENT env-resolved path
    (re-bound when TONY_TUNE_CACHE changes, so tests can redirect it)."""
    global _shared
    path = default_cache_path()
    if not path:
        raise ValueError(f"no tune cache file: set {ENV_CACHE} (or pass --cache)")
    if _shared is None or _shared.path != path:
        _shared = TuneCache(path)
    return _shared


def lookup(op: str, shape: Iterable[int], dtype: Any) -> dict[str, int] | None:
    """The kernel entry points' cache consult: tuned params or None.

    Trace-time only (static block sizes); disabled by ``TONY_TUNE_DISABLE=1``
    and inert (two env reads) when no cache file is named.
    """
    if os.environ.get(ENV_DISABLE) == "1" or not default_cache_path():
        return None
    return shared_cache().get(op, shape, dtype)


# ---------------------------------------------------------------------------
# Sweep machinery — `tony tune` drives these on a real backend.
# ---------------------------------------------------------------------------

def measure(thunk: Callable[[], Any], steps: int = 3, warmup: int = 1) -> float:
    """Median wall-time (ms) of ``thunk`` over ``steps`` timed runs, each
    synced via block_until_ready, after ``warmup`` compile runs."""
    import jax

    for _ in range(max(warmup, 1)):
        jax.block_until_ready(thunk())
    times = []
    for _ in range(max(steps, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(thunk())  # lint: disable=host-sync — per-run sync IS the measurement
        times.append((time.perf_counter() - t0) * 1000.0)
    times.sort()
    return times[len(times) // 2]


def flash_candidates(Tq: int, Tk: int) -> list[tuple[int, int]]:
    """(block_q, block_k) grid: alignment-legal blocks that divide the
    sequence lengths, the kernels' lowering preconditions (attention.py
    routes anything else to the XLA reference path)."""
    out = []
    for bq in (128, 256, 512):
        if bq > Tq or Tq % bq:
            continue
        for bk in (128, 256, 512, 1024):
            if bk > Tk or Tk % bk:
                continue
            out.append((bq, bk))
    return out


def sweep_flash(
    B: int, H: int, Hkv: int, T: int, D: int, dtype: str = "bfloat16",
    causal: bool = True, steps: int = 3,
) -> list[dict]:
    """Sweep flash fwd and bwd block sizes for one attention geometry;
    returns result rows (op/params/ms, best first per op) WITHOUT writing
    the cache — the CLI decides what to persist."""
    import jax
    import jax.numpy as jnp

    from tony_tpu.ops import attention as A

    dt = jnp.dtype(dtype)
    ks = [jax.random.fold_in(jax.random.PRNGKey(0), i) for i in range(4)]
    q = (jax.random.normal(ks[0], (B, H, T, D)) * 0.5).astype(dt)
    k = (jax.random.normal(ks[1], (B, Hkv, T, D)) * 0.5).astype(dt)
    v = (jax.random.normal(ks[2], (B, Hkv, T, D)) * 0.5).astype(dt)
    do = (jax.random.normal(ks[3], (B, H, T, D)) * 0.5).astype(dt)
    shape = (B, H, Hkv, T, T, D)

    rows: list[dict] = []
    fwd_rows: list[dict] = []
    for bq, bk in flash_candidates(T, T):
        fwd = jax.jit(
            lambda q, k, v, bq=bq, bk=bk: A._flash_fwd_lanes(q, k, v, causal, bq, bk)
        )
        try:
            ms = measure(lambda: fwd(q, k, v), steps=steps)
        except Exception as e:  # noqa: BLE001 — a non-lowering candidate just loses
            rows.append({"op": "flash_fwd", "shape": shape, "dtype": str(dt),
                         "params": {"block_q": bq, "block_k": bk},
                         "ms": None, "error": f"{type(e).__name__}: {e}"})
            continue
        fwd_rows.append({"op": "flash_fwd", "shape": shape, "dtype": str(dt),
                         "params": {"block_q": bq, "block_k": bk}, "ms": ms})
    o, lse = None, None
    if fwd_rows:
        best_fwd = min(fwd_rows, key=lambda r: r["ms"])
        p = best_fwd["params"]
        o, lse = A._flash_fwd_lanes(q, k, v, causal, p["block_q"], p["block_k"])

    bwd_rows: list[dict] = []
    if o is not None:
        for bq, bk in flash_candidates(T, T):
            bwd = jax.jit(
                lambda q, k, v, o, lse, do, bq=bq, bk=bk:
                A._flash_bwd_impl(q, k, v, o, lse, do, causal, bq, bk)
            )
            try:
                ms = measure(lambda: bwd(q, k, v, o, lse, do), steps=steps)
            except Exception as e:  # noqa: BLE001
                rows.append({"op": "flash_bwd", "shape": shape, "dtype": str(dt),
                             "params": {"block_q": bq, "block_k": bk},
                             "ms": None, "error": f"{type(e).__name__}: {e}"})
                continue
            bwd_rows.append({"op": "flash_bwd", "shape": shape, "dtype": str(dt),
                             "params": {"block_q": bq, "block_k": bk}, "ms": ms})
    return (sorted(fwd_rows, key=lambda r: r["ms"])
            + sorted(bwd_rows, key=lambda r: r["ms"]) + rows)


def moe_candidates(N: int) -> list[int]:
    return [t for t in (64, 128, 256, 512) if t <= max(N, 64)]


def sweep_moe(
    E: int, D: int, F: int, N: int, dtype: str = "bfloat16", steps: int = 3,
) -> list[dict]:
    """Sweep the fused MoE grouped-GEMM row tile for one expert geometry
    (fwd+bwd together — the tile is shared, TILE_M_BWD must divide it)."""
    import jax
    import jax.numpy as jnp

    from tony_tpu.ops import moe_gemm

    dt = jnp.dtype(dtype)
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    wg = (jax.random.normal(ks[0], (E, D, F)) / D ** 0.5).astype(dt)
    wu = (jax.random.normal(ks[1], (E, D, F)) / D ** 0.5).astype(dt)
    wd = (jax.random.normal(ks[2], (E, F, D)) / F ** 0.5).astype(dt)
    shape = (E, D, F)

    rows: list[dict] = []
    for tile in moe_candidates(N):
        per = -(-max(N // E, 1) // tile) * tile       # equal groups, tile-padded
        PN = per * E
        xs = (jax.random.normal(ks[3], (PN, D)) * 0.5).astype(dt)
        group_sizes = jnp.full((E,), per, jnp.int32)
        tg = moe_gemm.tile_group_map(group_sizes, PN // tile, tile)

        def loss(xs, wg, wu, wd, tg=tg, tile=tile):
            y = moe_gemm.moe_swiglu_grouped(xs, wg, wu, wd, tg, tile)
            return (y.astype(jnp.float32) ** 2).sum()

        step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)))
        try:
            ms = measure(lambda: step(xs, wg, wu, wd), steps=steps)
        except Exception as e:  # noqa: BLE001
            rows.append({"op": "moe_gemm", "shape": shape, "dtype": str(dt),
                         "params": {"tile": tile}, "ms": None,
                         "error": f"{type(e).__name__}: {e}"})
            continue
        rows.append({"op": "moe_gemm", "shape": shape, "dtype": str(dt),
                     "params": {"tile": tile}, "ms": ms})
    ok = [r for r in rows if r["ms"] is not None]
    bad = [r for r in rows if r["ms"] is None]
    return sorted(ok, key=lambda r: r["ms"]) + bad


def int8_candidates(M: int, K: int, N: int) -> list[tuple[int, int, int]]:
    out = []
    for bm in (128, 256, 512):
        for bn in (128, 256, 512):
            for bk in (256, 512, 1024):
                if bm <= M and bn <= N and bk <= K and not (M % bm or N % bn or K % bk):
                    out.append((bm, bn, bk))
    return out


def sweep_int8(
    M: int, K: int, N: int, dtype: str = "bfloat16", steps: int = 3,
) -> list[dict]:
    """Sweep the int8 weight-matmul block sizes for one GEMM geometry."""
    import jax
    import jax.numpy as jnp

    from tony_tpu.ops import quant

    dt = jnp.dtype(dtype)
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (M, K)).astype(dt)
    qt = quant.quantize_int8(jax.random.normal(jax.random.fold_in(key, 1), (K, N)))
    shape = (M, K, N)

    rows: list[dict] = []
    for bm, bn, bk in int8_candidates(M, K, N):
        try:
            ms = measure(
                lambda: quant.int8_matmul(x, qt, block_m=bm, block_n=bn, block_k=bk),
                steps=steps,
            )
        except Exception as e:  # noqa: BLE001
            rows.append({"op": "int8_matmul", "shape": shape, "dtype": str(dt),
                         "params": {"block_m": bm, "block_n": bn, "block_k": bk},
                         "ms": None, "error": f"{type(e).__name__}: {e}"})
            continue
        rows.append({"op": "int8_matmul", "shape": shape, "dtype": str(dt),
                     "params": {"block_m": bm, "block_n": bn, "block_k": bk},
                     "ms": ms})
    ok = [r for r in rows if r["ms"] is not None]
    bad = [r for r in rows if r["ms"] is None]
    return sorted(ok, key=lambda r: r["ms"]) + bad


def persist_winners(rows: list[dict], cache: TuneCache | None = None) -> TuneCache:
    """Store the best (lowest-ms) row per (op, shape, dtype) into the cache
    and save it. Rows without a measurement (lowering failures) never win."""
    cache = cache or shared_cache()
    best: dict[tuple, dict] = {}
    for r in rows:
        if r.get("ms") is None:
            continue
        k = (r["op"], tuple(r["shape"]), r["dtype"])
        if k not in best or r["ms"] < best[k]["ms"]:
            best[k] = r
    for (op, shape, dtype), r in best.items():
        cache.put(op, shape, dtype, r["params"], ms=r["ms"])
    cache.save()
    return cache
