"""Continuous-batching decode engine (any model module that supplies its
serving programs: `ServingPrograms`, below; the Llama and Mixtral families'
are built here, MiniCPM-SALA's in models/minicpm_sala.py, the exaone_moe
family's in models/exaone_moe.py).

The reference orchestrates training jobs only — serving is new capability
(SURVEY.md §2.5 "absent" rows); this is the slot-based engine layer above
models/generate.py. TPU shape discipline: one compiled decode step serves a
FIXED number of slots against a FIXED-length KV cache; requests of any
length flow through by admission into free slots (prefill, padded to
power-of-two buckets so the jit cache stays small) and per-slot position
masking — no dynamic shapes ever reach XLA.

The decode step is SLOT-NATIVE (r3 rewrite): one layer scan over a
[L, S, Hkv, maxT, Dh] cache runs every slot's token through batched
projections and FFN (so the Mixtral mixture runs once over all slots, not
vmapped per slot), with per-slot cache positions. Attention picks one of
two implementations:

- ``ragged`` (TPU): the Pallas per-slot-length kernel
  (ops/decode_attention.py) — each slot streams only ITS OWN cache length
  (and only the window for SWA models), so step cost follows Σ len_s and a
  single long-lived request no longer taxes every slot (r2 weak #3);
- ``bucketed`` (portable XLA): masked attention over the shortest
  power-of-two cache prefix covering every active slot — the r2 scheme,
  kept as the CPU/test path and fallback.

Host/device split: a pass does admission (staging, prefills, pages, inserts)
while the last pass's decode chunk is in flight, queued on the device behind
it, dispatches the next chunk behind that, and only then reads the last
chunk's tokens (``ContinuousBatcher.step``): queueing and EOS/termination
bookkeeping run on the host under the chunk after the one they read;
everything per-token is one jitted call over all slots. Weights may be an
int8-quantized tree (ops/quant.py) for the dense family — the same ``_mm``
dispatch as generate.py serves both.
"""

from __future__ import annotations

import functools
import itertools
import time
from dataclasses import dataclass, field
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from tony_tpu.models.generate import (
    KVCache,
    _embed_lookup,
    _ffn_with_cache,
    _forward_with_cache,
    _masked_slot_attention,
    _mm,
    _sample,
    init_cache,
    sample_logits,
)
from tony_tpu.models.llama import LlamaConfig
from tony_tpu.obs import metrics as obs_metrics
from tony_tpu.ops import layers as L

# The engine's own account of its time (docs/observability.md "Where a
# request's TTFT goes"): always on, like the server's instruments beside them.
_ENGINE_SECONDS = obs_metrics.counter(
    "tony_serve_engine_seconds_total",
    "engine-thread seconds by phase of a pass (the phases tile the thread's time)",
    labelnames=("phase",))
_ENGINE_OFFCPU = obs_metrics.counter(
    "tony_serve_engine_offcpu_seconds_total",
    "engine-thread seconds by phase in which the thread was on no CPU (a phase's wall time less its thread CPU "
    "time): the device in decode_wait and prefill_wait; in the other phases the interpreter held by another "
    "thread, a lock, or a blocking call into the runtime",
    labelnames=("phase",))
_CHUNKS = obs_metrics.counter(
    "tony_serve_engine_chunks_total", "decode chunks dispatched")
_CHUNKS_AHEAD = obs_metrics.counter(
    "tony_serve_chunks_ahead_total",
    "decode chunks dispatched while the tokens of the chunk before had not been read: over "
    "tony_serve_engine_chunks_total, the share of chunks the device could start without a turn of the host")
_DECODE_SLOTS = obs_metrics.counter(
    "tony_serve_decode_slots_total",
    "running slots summed over dispatched decode chunks (over chunks = mean batch)")
_PREFILL_TOKENS = obs_metrics.counter(
    "tony_serve_prefill_tokens_total",
    "prompt tokens dispatched to prefill, padded to their bucket (reused prefix tokens are not in it)")
_CONTEXT_TOKENS = obs_metrics.counter(
    "tony_serve_context_tokens_total",
    "positions in context, summed over running slots and decode steps dispatched")
_VISIBLE_TOKENS = obs_metrics.counter(
    "tony_serve_visible_tokens_total",
    "cache positions a decode step may read (a window, a chosen set of blocks), summed like the context")
_PREFILL_CHUNKS = obs_metrics.counter(
    "tony_serve_prefill_chunks_total",
    "prefill chunks dispatched, by how their attention reads its keys",
    labelnames=("path",))
_ADMIT_BLOCKED = obs_metrics.counter(
    "tony_serve_admit_blocked_total",
    "engine passes in which a waiting request could not be admitted, by what was short",
    labelnames=("reason",))
_ADMISSIONS = obs_metrics.counter(
    "tony_serve_admissions_total",
    "requests given a slot, by what the device was doing at their insert: a decode chunk in flight, or nothing",
    labelnames=("under",))
_ROUTED_FFN = obs_metrics.counter(
    "tony_serve_routed_ffn_programs_total",
    "decode chunks and prefill chunks dispatched by a model whose layers hold part of their experts, by where the "
    "routed FFN gathers its rows and sums its choices at that program's row count: in the grouped product's "
    "kernel, fetched by the kernels a row at a time from the tokens and the rows that exist, or staged through "
    "HBM at the static row bound by XLA (parallel/expert.held_form)",
    labelnames=("form",))
# a model whose layers hold part of their experts returns these four with a chunk's tokens, summed on the
# device over the chunk's steps and routed layers, from live slots' rows (ServingPrograms.decode_chunk)
_EXPERT_COUNTS = (
    obs_metrics.counter("tony_serve_expert_rows_total", "decode rows that landed on an expert this replica holds"),
    obs_metrics.counter("tony_serve_expert_rows_max_total",
                        "decode rows of the fullest held expert, a layer and step: the straggler a grouped product waits for"),
    obs_metrics.counter("tony_serve_expert_choices_total", "expert choices decode rows made (rows x experts a token)"),
    obs_metrics.counter("tony_serve_experts_touched_total",
                        "held experts that a live slot's decode row chose, a layer and step: the slabs the grouped product reads"),
)


# in the order a pass runs them (``ContinuousBatcher.step``)
PHASES = ("intake", "admit", "prefill_wait", "dispatch", "decode_wait", "emit", "idle")
_ANNOTATION = {phase: "tony.serve." + phase for phase in PHASES}


class _PhaseClock:
    """Which phase of a pass the engine thread is in. ``to(name)`` closes the
    open phase and opens the next, so consecutive phases tile the thread's
    time with nothing between them. Each phase lands on two clocks at once:
    its seconds in ``tony_serve_engine_seconds_total{phase}``, and a
    ``tony.serve.<phase>`` annotation in the profiler's trace (a flag check
    unless a capture runs), on the engine thread's line beside the device's
    operations. Beside the wall clock it reads the thread's CPU clock, and
    what a phase's wall time has over its CPU time goes to
    ``tony_serve_engine_offcpu_seconds_total{phase}``: the time the thread
    waited, whatever for. ``to(None)`` closes without opening."""

    def __init__(self, wall=time.perf_counter, cpu=time.thread_time):
        self._wall, self._cpu = wall, cpu
        self._name, self._t0, self._cpu0, self._ann = None, 0.0, 0.0, None

    def to(self, name: str | None) -> None:
        now, cpu = self._wall(), self._cpu()
        if self._name is not None:
            _ENGINE_SECONDS.inc(now - self._t0, phase=self._name)
            _ENGINE_OFFCPU.inc(max(0.0, now - self._t0 - (cpu - self._cpu0)), phase=self._name)
            self._ann.__exit__(None, None, None)
        self._name, self._t0, self._cpu0 = name, now, cpu
        if name is not None:
            self._ann = jax.profiler.TraceAnnotation(_ANNOTATION[name])
            self._ann.__enter__()


class SlotCache(NamedTuple):
    """Decode state for S slots. k/v: [L, S, Hkv, maxT, Dh]; lengths: [S]."""

    k: jax.Array
    v: jax.Array
    lengths: jax.Array  # int32 [S] — tokens already cached per slot


def init_slot_cache(cfg: LlamaConfig, num_slots: int, max_len: int) -> SlotCache:
    shape = (cfg.n_layers, num_slots, cfg.n_kv_heads, max_len, cfg.head_dim)
    return SlotCache(
        k=jnp.zeros(shape, cfg.jdtype),
        v=jnp.zeros(shape, cfg.jdtype),
        lengths=jnp.zeros((num_slots,), jnp.int32),
    )


# decode attention lives in generate.py (_masked_slot_attention) — ONE
# implementation shared with generate()'s decode steps, so the two paths
# cannot diverge in attention math


def _decode_one(
    params, cache, tokens: jax.Array, key: jax.Array,
    cfg: LlamaConfig, temperature: float = 0.0, top_k: int = 0, attn: str = "bucketed",
    samp=None, staged=None,
):
    """One token for every slot, slot-native: (next tokens [S], cache').

    Each slot runs at its own position (cache.lengths[s], clamped at
    maxT-1). Inactive slots decode garbage harmlessly; the host ignores
    them. Projections and the FFN (dense SwiGLU or the Mixtral mixture —
    generate._ffn_with_cache) run batched over the slot dim.

    ``cache`` is a SlotCache (dense per-slot slabs) or a PagedCache (page
    pool + per-slot page tables, models/paged_cache.py): the trace-time
    branch picks the attention read (per-slot slab DMA vs page-indirected
    DMA — same kernel body) and the write (per-slot column scatter vs
    in-place page windows, paged_cache.write_decode_chunk). Everything else — projections, RoPE, FFN,
    sampling — is identical, so the two cache layouts cannot drift.
    """
    from tony_tpu.models.paged_cache import PagedCache

    paged = isinstance(cache, PagedCache)
    S = tokens.shape[0]
    Dh, H, Hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    maxT = (cache.page_table.shape[1] * cache.k.shape[3]) if paged else cache.k.shape[3]
    cos, sin = L.rope_frequencies(Dh, maxT, cfg.rope_theta, cfg.rope_scaling)
    # KERNEL PRECONDITION: active slots have lengths < maxT (enforced by
    # submit()'s prompt+budget <= max_len check). A slot clamped AT maxT
    # would attend both the stale cached entry at maxT-1 and the current
    # token (double-counting one position) in the read-only-cache split —
    # only retired-not-yet-flushed slots decoding discarded overshoot
    # tokens can reach that state, and their output is never read.
    pos = jnp.minimum(cache.lengths, maxT - 1)                      # write position
    x = _embed_lookup(params["embed"], tokens[:, None], cfg.jdtype)  # [S, 1, D]

    # The cache is READ-ONLY inside the layer scan: attention sees the OLD
    # cache plus the current token's K/V explicitly, and the scan emits only
    # the tiny [S, Hkv, Dh] new K/V per layer. Carrying the updated cache
    # through the scan instead (the first r3 design) stacked a full cache
    # copy as scan ys EVERY token — measured −32% decode tok/s at 64 slots.
    # DENSE: the cache tensors are scan xs, one [S, Hkv, maxT, Dh] slab a
    # layer. PAGED: the scan's xs is the layer INDEX and the kernel takes the
    # whole pool with it — a layer's pool as xs is an operand of a Mosaic
    # call, which XLA gives a buffer of its own: a copy of the layer's pool,
    # K and V, every layer of every step (38-41% of the serving chip: PR 27).
    def layer(x, inputs):
        # rest — dense: this layer's (ck, cv); paged: (layer index[, its staged k, v])
        lp, *rest = inputs
        h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = _mm(h, lp["wq"]).reshape(S, 1, H, Dh).transpose(0, 2, 1, 3)
        k = _mm(h, lp["wk"]).reshape(S, 1, Hkv, Dh).transpose(0, 2, 1, 3)
        v = _mm(h, lp["wv"]).reshape(S, 1, Hkv, Dh).transpose(0, 2, 1, 3)
        q = L.apply_rope(q, cos, sin, positions=pos[:, None])
        k = L.apply_rope(k, cos, sin, positions=pos[:, None])
        k1 = k[:, :, 0].astype(cache.k.dtype)                        # [S, Hkv, Dh]
        v1 = v[:, :, 0].astype(cache.v.dtype)
        if paged:
            from tony_tpu.ops.decode_attention import paged_decode_attention

            extra = {}
            if staged is not None:
                extra = dict(
                    staged_k=rest[1], staged_v=rest[2],
                    staged_count=jnp.broadcast_to(staged[2], (S,)),
                )
            o = paged_decode_attention(
                q[:, :, 0], cache.k, cache.v, pos, cache.page_table, rest[0],
                cur_k=k1, cur_v=v1, window=cfg.sliding_window, **extra,
            )
        elif attn == "ragged":
            from tony_tpu.ops.decode_attention import ragged_decode_attention

            o = ragged_decode_attention(
                q[:, :, 0], *rest, pos, cur_k=k1, cur_v=v1,
                window=cfg.sliding_window,
            )
        else:
            o = _masked_slot_attention(
                q[:, :, 0], *rest, pos, H // Hkv, window=cfg.sliding_window,
                cur_k=k1, cur_v=v1,
            )
        x = x + _mm(o.reshape(S, 1, H * Dh), lp["wo"])
        h = L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        x = x + _ffn_with_cache(h, lp, cfg)
        return x, (k1, v1)

    if paged:
        xs = (params["layers"], jnp.arange(cache.k.shape[0], dtype=jnp.int32))
        if staged is not None:
            xs = xs + (staged[0], staged[1])  # per-layer staged windows
    else:
        xs = (params["layers"], cache.k, cache.v)
    x, (ks_new, vs_new) = jax.lax.scan(layer, x, xs)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _mm(x[:, 0], params["lm_head"]).astype(jnp.float32)     # [S, V]
    if samp is not None:
        nxt = sample_logits(logits, key, *samp)  # per-slot temp/top_k/top_p
    else:
        nxt = _sample(logits, key, temperature, top_k)

    # idle slots (length 0 — flushed retirements / never admitted) stay at 0
    # instead of regrowing +1 per step: their stale cache never re-enters
    # the ragged kernel's Σ len_s (active slots always have length ≥ 1)
    new_len = jnp.where(
        cache.lengths > 0, jnp.minimum(cache.lengths + 1, maxT), 0
    )
    if staged is not None:
        # deferred-write mode (decode_steps' paged chunk): this step's
        # columns go to the chunk staging, the POOL is untouched — the
        # per-token page write measured −24%/chunk as 2·S serial dus
        return nxt, cache._replace(lengths=new_len), ks_new, vs_new
    if paged:
        # a single step is a chunk of one: the same in-place write
        from tony_tpu.models.paged_cache import write_decode_chunk

        ks, vs = write_decode_chunk(
            cache.k, cache.v, ks_new[:, :, None], vs_new[:, :, None],
            cache.lengths, cache.page_table,
        )
        return nxt, cache._replace(k=ks, v=vs, lengths=new_len)

    # single write: scatter each slot's [L, Hkv, Dh] column at its position
    # (the donated cache updates in place — no full-cache copy per token)
    def write_slot(c, kv, p):
        # c [L, Hkv, maxT, Dh]; kv [L, Hkv, Dh]
        return jax.lax.dynamic_update_slice(c, kv[:, :, None], (0, 0, p, 0))

    ks = jax.vmap(write_slot, in_axes=(1, 1, 0), out_axes=1)(cache.k, ks_new, pos)
    vs = jax.vmap(write_slot, in_axes=(1, 1, 0), out_axes=1)(cache.v, vs_new, pos)
    return nxt, SlotCache(ks, vs, new_len)


decode_step = functools.partial(
    jax.jit, static_argnames=("cfg", "temperature", "top_k", "attn"), donate_argnums=(1,)
)(_decode_one)


@functools.partial(
    jax.jit, static_argnames=("cfg", "n", "temperature", "top_k", "attn"),
    donate_argnums=(1,),
)
def decode_steps(
    params, cache: SlotCache, tokens: jax.Array, key: jax.Array,
    cfg: LlamaConfig, n: int, temperature: float = 0.0, top_k: int = 0,
    attn: str = "ragged", samp=None,
):
    """``n`` decode steps in ONE compiled call (lax.scan): (tokens [S],
    all tokens [n, S], cache'). Amortizes per-dispatch host overhead,
    which dominates single-token steps of a small model.
    With ``attn='ragged'`` the Pallas kernel reads each slot's own cache
    length, so no bucketing is needed (or helpful). ``samp``: per-slot
    (temperature, top_k, top_p) device arrays — overrides the static
    sampling params when present.

    PAGED caches decode in DEFERRED-WRITE mode: each step's K/V columns
    land in a chunk staging buffer (one contiguous write per step), the
    kernel folds the staged window from VMEM, and the page pool is written
    ONCE per chunk — the per-token page scatter (2·S serial updates into
    dynamic (page, offset) targets) measured −24% on the whole chunk.
    Inside the chunk the pool is only ever touched BY PAGE: the kernel reads
    the whole pool through a layer index, and the one write a chunk lands in
    place on the donated pool (paged_cache.write_decode_chunk). Nothing in
    the program has the pool's shape or one layer's pool's shape but the
    pool itself (tests/test_chip_compile.py holds the compiled HLO to it)."""
    from tony_tpu.models.paged_cache import PagedCache, write_decode_chunk

    if not isinstance(cache, PagedCache):

        def body(carry, k_step):
            cache, toks = carry
            nxt, cache = _decode_one(
                params, cache, toks, k_step, cfg, temperature, top_k, attn, samp
            )
            return (cache, nxt), nxt

        (cache, toks), seq = jax.lax.scan(body, (cache, tokens), jax.random.split(key, n))
        return toks, seq, cache

    Lc, _, Hkv, _, Dh = cache.k.shape
    S = tokens.shape[0]
    len0 = cache.lengths
    stage_k = jnp.zeros((Lc, S, n, Hkv, Dh), cache.k.dtype)
    stage_v = jnp.zeros((Lc, S, n, Hkv, Dh), cache.v.dtype)

    def body(carry, k_step):
        cache, toks, sk, sv, i = carry
        nxt, cache, cols_k, cols_v = _decode_one(
            params, cache, toks, k_step, cfg, temperature, top_k, attn, samp,
            staged=(sk, sv, i),
        )
        # cols [L, S, Hkv, Dh] → staging[:, :, i] (one contiguous write)
        sk = jax.lax.dynamic_update_slice(sk, cols_k[:, :, None], (0, 0, i, 0, 0))
        sv = jax.lax.dynamic_update_slice(sv, cols_v[:, :, None], (0, 0, i, 0, 0))
        return (cache, nxt, sk, sv, i + 1), nxt

    (cache, toks, stage_k, stage_v, _), seq = jax.lax.scan(
        body, (cache, tokens, stage_k, stage_v, jnp.int32(0)),
        jax.random.split(key, n),
    )
    # ONE pool write for the whole chunk, in place: (slot s, step j) goes to
    # position len0[s]+j
    k, v = write_decode_chunk(cache.k, cache.v, stage_k, stage_v, len0, cache.page_table)
    return toks, seq, cache._replace(k=k, v=v)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "n", "bucket", "temperature", "top_k"),
    donate_argnums=(1,),
)
def decode_steps_bucketed(
    params, cache: SlotCache, tokens: jax.Array, key: jax.Array,
    cfg: LlamaConfig, n: int, bucket: int, temperature: float = 0.0, top_k: int = 0,
    samp=None,
):
    """``decode_steps`` over a LENGTH-BUCKETED cache view (XLA fallback):
    attention reads only the first ``bucket`` cache positions (a power of
    two ≥ the longest active slot + n, chosen by the host), then the grown
    view is written back into the full cache. Portable but global — one
    long slot drags every slot to its bucket; the ragged path doesn't.
    One jit variant per bucket (powers of two → log(max_len) variants)."""
    sub = SlotCache(cache.k[:, :, :, :bucket], cache.v[:, :, :, :bucket], cache.lengths)

    def body(carry, k_step):
        c, toks = carry
        nxt, c = _decode_one(
            params, c, toks, k_step, cfg, temperature, top_k, "bucketed", samp
        )
        return (c, nxt), nxt

    (sub, toks), seq = jax.lax.scan(body, (sub, tokens), jax.random.split(key, n))
    k = jax.lax.dynamic_update_slice(cache.k, sub.k, (0, 0, 0, 0, 0))
    v = jax.lax.dynamic_update_slice(cache.v, sub.v, (0, 0, 0, 0, 0))
    return toks, seq, SlotCache(k, v, sub.lengths)


# host-loop cache/token updates MUST be shape-stable jitted calls: an eager
# `.at[idx].set()` whose index list length (or constant-folded position)
# varies re-lowers and RE-COMPILES per distinct pattern — tens of ms per
# tiny executable. The builders' r5 probe (older than this code) caught
# retirement flushes + per-admission token writes dominating a serving pass
# this way; with the
# fixed-shape forms below each helper compiles exactly once per engine.
@functools.partial(jax.jit, donate_argnums=(0,))
def _set_slot_token(tokens, slot, val):
    return tokens.at[slot].set(val[0])  # val [1]: indexed inside the jit


@functools.partial(jax.jit, donate_argnums=(0,))
def _mask_zero(lengths, mask):
    return jnp.where(mask, 0, lengths)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _mask_zero_paged(lengths, page_table, mask):
    return jnp.where(mask, 0, lengths), jnp.where(mask[:, None], 0, page_table)


def _bucket(n: int, lo: int = 16) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


# one jit variant per (prompt bucket, cache length) — buckets are powers of
# two so the variant count stays logarithmic in max_len
_prefill_padded = jax.jit(_forward_with_cache, static_argnames=("cfg",))


@functools.partial(jax.jit, donate_argnums=(0,))
def _insert_prefill(cache: SlotCache, pre: KVCache, slot: jax.Array, true_len: jax.Array):
    """Copy a 1-request prefill cache [L, 1, Hkv, maxT, Dh] into ``slot``."""
    k = jax.lax.dynamic_update_slice(cache.k, pre.k, (0, slot, 0, 0, 0))
    v = jax.lax.dynamic_update_slice(cache.v, pre.v, (0, slot, 0, 0, 0))
    lengths = cache.lengths.at[slot].set(true_len)
    return SlotCache(k, v, lengths)


class ServingPrograms(NamedTuple):
    """What the engine asks a model module for (`<module>.serving_programs(cfg,
    kv)`, the module being the one that defines the config's class). The host
    loop below (admission, pages, slots, streaming, phases) is one piece of
    code for every model; everything that knows a layer is behind these."""

    init_cache: object        # (num_slots, max_len, page_len, num_pages) -> the slots' device state
    init_staging: object      # (max_len) -> one request's state while it prefills
    prefill_chunk: object     # (params, tokens [1, T], staging, take) -> (logits of row take-1 [1, V], staging')
    prefill_pad: object       # (take, prefill_chunk, room) -> padding rows of a prompt's last chunk
    insert: object            # paged: (cache, staging, fresh_pages, pt_row, slot, true_len, j0, n) -> cache'
    # (params, cache, tokens, key, n=, temperature=, top_k=, samp=) -> (tokens, all, cache'[, expert counts [4]:
    # held rows, the fullest held expert's rows, choices, held experts chosen; where layers hold part of their experts])
    decode_chunk: object
    release: object           # (cache, mask [S]) -> cache' with the masked slots idle
    visible_tokens: object    # (context lengths, numpy) -> cache positions a decode step may read at each
    prefill_path: object      # (pos, take) -> "dense" | "sparse": how that chunk's attention reads its keys
    insert_dense: object = None            # dense kv: (cache, staging, slot, true_len) -> cache'
    decode_chunk_bucketed: object = None   # dense kv: decode_chunk with bucket=
    # (staging, cache, pages, n) -> staging with an earlier request's full prompt pages in it; None where a
    # page is not all a prefix leaves behind (state beside it), so no page is shared
    gather_prefix: object = None
    # (the page allocator, the matched pages) -> how many of them a request may start from; None: all. Where state
    # lies beside a prefix's pages, a match ends at the deepest page whose edge has that state kept
    prefix_usable: object = None
    # (rows of a program: the slots of a decode chunk, a prefill chunk's padded length) -> "in_kernel" | "fetched" | "staged":
    # the form its routed FFN runs in (parallel/expert.held_ffn_form); None where no layer holds part of its experts
    routed_ffn_form: object = None


def programs_for(cfg, kv: str) -> ServingPrograms:
    """The serving programs of the module that defines `cfg`'s class."""
    from tony_tpu.models.registry import module_of

    return module_of(cfg).serving_programs(cfg, kv)


def llama_programs(cfg: LlamaConfig, kv: str) -> ServingPrograms:
    """The Llama and Mixtral families' programs: the jitted functions of this
    file and of paged_cache.py, as the engine called them before it had a seam."""
    from tony_tpu.models import paged_cache as pc

    def prefill(params, tokens, staging, take):
        logits, staging = _prefill_padded(params, tokens, staging, cfg)
        return logits[:, take - 1].astype(jnp.float32), staging

    def release_paged(cache, mask):
        lengths, page_table = _mask_zero_paged(cache.lengths, cache.page_table, mask)
        return pc.PagedCache(cache.k, cache.v, lengths, page_table)

    paged = kv == "paged"
    window = cfg.sliding_window
    return ServingPrograms(
        init_cache=(lambda S, max_len, page_len, num_pages: pc.init_paged_cache(cfg, S, max_len, page_len, num_pages))
        if paged else (lambda S, max_len, page_len, num_pages: init_slot_cache(cfg, S, max_len)),
        init_staging=lambda max_len: init_cache(cfg, 1, max_len),
        prefill_chunk=prefill,
        prefill_pad=lambda take, chunk, room: min(_bucket(take), room) - take,
        insert=lambda cache, pre, fresh, pt_row, slot, true_len, j0, n: pc.insert_paged_prefill(
            cache, pre.k, pre.v, fresh, pt_row, slot, true_len, j0, n=n),
        decode_chunk=lambda params, cache, tokens, key, n, temperature, top_k, samp: decode_steps(
            params, cache, tokens, key, cfg, n, temperature, top_k, "ragged", samp),
        release=release_paged if paged else (lambda cache, mask: SlotCache(cache.k, cache.v, _mask_zero(cache.lengths, mask))),
        visible_tokens=lambda n: np.minimum(n, window) if window > 0 else n,
        prefill_path=lambda pos, take: "dense",
        insert_dense=_insert_prefill,
        decode_chunk_bucketed=lambda params, cache, tokens, key, n, bucket, temperature, top_k, samp: decode_steps_bucketed(
            params, cache, tokens, key, cfg, n, bucket, temperature, top_k, samp),
        gather_prefix=lambda pre, cache, pages, n: pc.gather_prefix_into_staging(pre, cache.k, cache.v, pages, n=n),
    )


@dataclass
class _Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int
    out: list[int] = field(default_factory=list)
    slot: int = -1
    # per-request sampling overrides (None → the engine's defaults)
    temperature: float | None = None
    top_k: int | None = None
    top_p: float | None = None
    cancelled: bool = False           # client gone: retire at the next chunk
    # where its time to first token went, on the clock the server's TTFT uses
    # (time.time): left ``pending`` for ``_staged``, given a slot with its
    # first token on the host; the server closes the third stage at its fanout
    staged_s: float = 0.0
    slot_s: float = 0.0
    prefix_tokens: int = 0            # prompt tokens reused from the prefix cache
    prefill_chunks: int = 0           # prefill programs dispatched for it
    # its first token while only the device has it: a request admitted behind
    # a decode chunk takes the host copy with the tokens of its own first chunk
    first: object = None
    # decode steps dispatched for it: what ``ends_within`` counts with, since ``out`` trails it by the chunks in flight
    steps: int = 0
    finished: bool = False            # ``_finish`` has handed it over: a chunk dispatched with it since is all overshoot

    def is_done(self, eos_id: int) -> bool:
        """THE termination predicate — budget spent, EOS emitted, or the
        request cancelled. Both the chunk-drain loop and retirement consult
        this one method, so a cancelled slot frees within one decode chunk."""
        return self.cancelled or len(self.out) >= self.max_new_tokens or (
            eos_id >= 0 and bool(self.out) and self.out[-1] == eos_id
        )

    def ends_within(self, h: int) -> bool:
        """What the host knows BEFORE a chunk of ``h`` tokens has run, and
        before it has read the chunk before: the request is cancelled, or its
        budget (its first token and the steps dispatched so far) ends inside
        the chunk. (An EOS is known only once its chunk's tokens are on the
        host.)"""
        return self.cancelled or 1 + self.steps + h >= self.max_new_tokens


@dataclass
class _Staged:
    """A request mid-prefill, staged ahead of slot availability."""

    req: _Request
    pre: KVCache                      # per-request dense staging cache
    pos: int = 0                      # prompt tokens prefilled so far
    first: object = None              # sampled first output token (None → prefilling)
    matched: list[int] = field(default_factory=list)  # pinned shared-prefix pages
    keys: list[tuple] = field(default_factory=list)   # cumulative prefix keys (paged)


class _Chunk(NamedTuple):
    """A decode chunk between its dispatch and the pass that reads its tokens."""

    flying: dict                      # {slot: request} it was dispatched with
    seq: object                       # its tokens [h, S], on the device
    counts: tuple                     # what else the model's chunk returns (``_EXPERT_COUNTS``), on the device


class ContinuousBatcher:
    """Slot-based continuous batching: every pass admits in the shadow of the
    decode chunk in flight, dispatches the next chunk behind it, then emits
    and retires the one in flight (``step``).

    One engine instance owns S slots over a shared static KV cache. Requests
    are admitted into free slots as they arrive (prefill padded to a bucket
    so prompt-length jit variants stay bounded) and retire independently on
    EOS or their token budget — the running batch never drains to admit new
    work, which is the throughput property batch-of-one ``generate()`` lacks.

    ``attn``: "auto" (CPU: always bucketed; TPU: bucketed while every
    active slot fits a short bucket, the ragged Pallas kernel once the
    needed bucket crosses ``ragged_threshold`` — short regimes are
    XLA-batched-einsum-friendly, long/straggler regimes are where per-slot
    reads pay), or force "ragged"/"bucketed" (the dense cache's knob).

    ``cfg`` is the config object of any model module that has
    ``serving_programs(cfg, kv)`` (``ServingPrograms``): its cache, its
    prefill-chunk and decode-chunk programs, its admission commit. The host
    loop here knows slots, pages, lengths and tokens, and no layer: a Llama or
    Mixtral tree decodes through this file's programs (``llama_programs``), a
    MiniCPM-SALA tree through models/minicpm_sala.py's, with recurrent state a
    slot beside the page pool.
    """

    #: needed-bucket size above which "auto" switches to the ragged kernel
    RAGGED_THRESHOLD = 512

    def __init__(
        self, params, cfg, *, num_slots: int = 8, max_len: int = 512,
        eos_id: int = -1, temperature: float = 0.0, top_k: int = 0,
        key: jax.Array | None = None, decode_chunk: int = 8, attn: str = "auto",
        prefill_chunk: int = 0, kv: str = "dense", page_len: int = 256,
        num_pages: int | None = None, mesh=None,
    ):
        if num_slots < 1 or max_len < 1:
            raise ValueError(f"need num_slots>=1 and max_len>=1, got {num_slots}/{max_len}")
        if kv not in ("dense", "paged"):
            raise ValueError(f"kv must be dense|paged, got {kv!r}")
        self.kv = kv
        if kv == "paged":
            # paged mode always decodes through the paged Pallas kernel; the
            # attn policy knob only governs the dense engine
            if page_len < 8 or page_len % 8:
                raise ValueError(f"page_len must be a multiple of 8 >= 8, got {page_len}")
            if max_len % page_len:
                raise ValueError(f"max_len {max_len} must be a multiple of page_len {page_len}")
        # model-axis tensor parallelism (VERDICT r4 #3): the TRAINING
        # column/row rules (models/llama.py sharding_rules) shard the decode
        # projections unchanged, the KV cache shards over its head dim, and
        # the host loop stays identical — admission/retirement/sampling
        # bookkeeping never sees the mesh. GSPMD inserts the row-parallel
        # psums; attention is embarrassingly parallel over heads. TP=1 with
        # a mesh (or mesh=None) is byte-for-byte the single-device program.
        self.mesh = mesh
        self.tp = int(mesh.shape.get("model", 1)) if mesh is not None else 1
        if self.tp > 1:
            if kv == "paged":
                raise ValueError(
                    "model-axis TP serving currently requires kv='dense' "
                    "(the paged pool's page indirection is per-device)"
                )
            if cfg.n_kv_heads % self.tp or cfg.n_heads % self.tp:
                raise ValueError(
                    f"n_heads {cfg.n_heads} and n_kv_heads {cfg.n_kv_heads} "
                    f"must divide the model axis ({self.tp})"
                )
            # the Pallas ragged kernel is not GSPMD-partitionable; the
            # pure-XLA bucketed path shards cleanly over the head dim.
            # An EXPLICIT ragged ask under TP is an error (silently running
            # a different kernel would hide a perf cliff); "auto" coerces.
            if attn == "ragged":
                raise ValueError(
                    "attn='ragged' is incompatible with model-axis TP (the "
                    "Pallas kernel is not GSPMD-partitionable); use attn='auto'"
                )
            attn = "bucketed"
        if attn == "auto" and jax.default_backend() == "cpu":
            attn = "bucketed"
        if attn not in ("auto", "ragged", "bucketed"):
            raise ValueError(f"attn must be auto|ragged|bucketed, got {attn!r}")
        if attn == "auto" and max_len <= self.RAGGED_THRESHOLD:
            attn = "bucketed"  # ragged could never engage at this max_len
        if kv == "dense" and attn in ("auto", "ragged") and max_len % 128:
            raise ValueError(f"attn={attn!r} needs max_len % 128 == 0, got {max_len}")
        self.params, self.cfg = params, cfg
        self.programs = programs_for(cfg, kv)
        self.S, self.max_len, self.eos_id = num_slots, max_len, eos_id
        self.temperature, self.top_k = temperature, top_k
        self.attn = attn
        # per-slot sampling state (host mirrors, shipped per decode chunk):
        # engine defaults until a request overrides them. The first override
        # latches _per_slot and switches the decode step to the dynamic
        # sampler (one-time recompile; greedy/static engines never pay it)
        self._samp_temp = np.full((num_slots,), temperature, np.float32)
        self._samp_topk = np.full((num_slots,), top_k, np.int32)
        self._samp_topp = np.zeros((num_slots,), np.float32)
        self._per_slot = False
        self._samp_dev = None  # cached device copies; refreshed when dirty
        self._samp_dirty = True
        # decode this many tokens per compiled call; requests finishing
        # mid-chunk simply DISCARD their overshoot tokens (see step()). >1
        # amortizes host dispatch overhead at the cost of admission latency
        self.decode_chunk = max(1, decode_chunk)
        # >0: long prompts prefill in chunks of this many tokens, ONE chunk
        # per engine step, so a long admission can't stall running decodes
        # for more than ~one chunk's compute. Middle chunks are EXACT
        # length (cache positions must be true); only the final partial
        # chunk pads to a bucket (garbage K/V past the prompt is masked by
        # the slot length, as in the unchunked path).
        self.prefill_chunk = prefill_chunk
        if kv == "paged":
            from tony_tpu.models.paged_cache import PageAllocator

            self.page_len = page_len
            self.max_pages = max_len // page_len
            # default pool = dense-equivalent (every slot fully backed) + the
            # sacrificial page; the capacity win comes from running MORE
            # slots against the same pool (or a smaller pool) — HBM then
            # tracks reserved tokens, not slots × max_len
            self.num_pages = (
                num_pages if num_pages is not None else num_slots * self.max_pages + 1
            )
            self.allocator = PageAllocator(self.num_pages)
            self.cache = self.programs.init_cache(num_slots, max_len, page_len, self.num_pages)
            self._slot_pages: dict[int, list[int]] = {}  # slot → reserved pages
            #: cumulative count of prompt tokens whose prefill compute was
            #: skipped via prefix-cache hits (the sharing win, observable)
            self.prefix_hit_tokens = 0
        else:
            self.cache = self.programs.init_cache(num_slots, max_len, 0, 0)
        self.tokens = jnp.zeros((num_slots,), jnp.int32)  # last token per slot
        if self.tp > 1:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from tony_tpu.models import llama as _llama
            from tony_tpu.models import mixtral as _mixtral

            rules = (
                _mixtral.sharding_rules(cfg)
                if isinstance(cfg, _mixtral.MixtralConfig)
                else _llama.sharding_rules(cfg)
            )
            self.params = jax.device_put(params, rules.sharding_tree(params, mesh))
            repl = NamedSharding(mesh, P())
            heads = NamedSharding(mesh, P(None, None, "model"))  # [L,S,Hkv,T,Dh]
            self.cache = SlotCache(
                k=jax.device_put(self.cache.k, heads),
                v=jax.device_put(self.cache.v, heads),
                lengths=jax.device_put(self.cache.lengths, repl),
            )
            self.tokens = jax.device_put(self.tokens, repl)
        self.key = key if key is not None else jax.random.PRNGKey(0)
        self.pending: list[_Request] = []
        self.running: dict[int, _Request] = {}   # slot → request
        self.done: dict[int, list[int]] = {}
        # slots retired since the last flush: their device-side lengths are
        # zeroed in ONE batched update per step — a per-retirement
        # ``lengths.at[slot].set(0)`` dispatch costs this backend's ~10 ms
        # dispatch floor EACH, which measured as a −25% tok/s engine tax
        # when a whole batch retires together (r3-cont)
        self._retired_slots: list[int] = []
        self._next_rid = 0
        # streaming cursor per request: drain_stream() hands out tokens
        # appended since the last drain (serving_http's SSE path)
        self._stream_pos: dict[int, int] = {}
        self._stream_done: set[int] = set()
        # prefill state machine, dispatched ahead of slot availability
        # (overlap with the in-flight decode chunk)
        self._staged: list[_Staged] = []
        self._slot_len = [0] * num_slots  # host mirror of cache.lengths
        # decode chunks dispatched whose tokens the host has not read, oldest first: one between passes, two
        # while a pass waits for the older (``step``)
        self._chunks: list[_Chunk] = []
        self._ending = 0  # slots handed back at the newest chunk's dispatch, until a pass may refill them (``slots_active``)
        #: the engine thread's phase clock; the server around the engine
        #: switches it for its own parts of a pass (intake, fan-out, idle)
        self.phase = _PhaseClock()

    def submit(
        self, prompt, max_new_tokens: int, *,
        temperature: float | None = None, top_k: int | None = None,
        top_p: float | None = None,
    ) -> int:
        """``temperature``/``top_k``/``top_p`` override the engine defaults
        for THIS request only (per-slot sampling); None keeps the default."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("prompt must be non-empty")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if temperature is not None and temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if top_k is not None and top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        if top_p is not None and not 0 < top_p <= 1:
            # 0.0 is the internal "nucleus cut disabled" sentinel — a client
            # sending top_p=0 expecting near-greedy would silently get the
            # FULL distribution, so reject it (use temperature=0 for greedy)
            raise ValueError(
                f"top_p must be in (0, 1], got {top_p} "
                "(for greedy decoding use temperature=0)"
            )
        if len(prompt) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt {len(prompt)} + max_new_tokens {max_new_tokens} "
                f"exceeds engine max_len {self.max_len}"
            )
        if self.kv == "paged":
            need = self._pages_needed(len(prompt), max_new_tokens)
            if need > self.num_pages - 1:
                raise ValueError(
                    f"request needs {need} pages but the pool holds "
                    f"{self.num_pages - 1}: raise num_pages or shrink the request"
                )
        rid = self._next_rid
        self._next_rid += 1
        if temperature is not None or top_k is not None or top_p is not None:
            self._per_slot = True
        self.pending.append(_Request(
            rid, prompt, max_new_tokens,
            temperature=temperature, top_k=top_k, top_p=top_p,
        ))
        return rid

    def request(self, rid: int) -> _Request | None:
        """The engine's record of a request it holds (waiting, staged or
        running), newest first; None once it is done or was never there."""
        held = itertools.chain(reversed(self.pending), (e.req for e in self._staged), self._decoding())
        return next((r for r in held if r.rid == rid), None)

    def _decoding(self) -> list[_Request]:
        """The requests with tokens still to come from a slot: running, or
        given up at the dispatch of a chunk that is still in flight (a budget
        that ends inside it), so in no slot's name and not yet in ``done``."""
        handed_back = (req for chunk in self._chunks for slot, req in chunk.flying.items()
                       if not req.finished and self.running.get(slot) is not req)
        return [*self.running.values(), *handed_back]

    @property
    def slots_active(self) -> int:
        """Slots a request decodes in: running, or handed back at the newest
        chunk's dispatch, which the device steps until that chunk ends and
        which the next pass's admission refills (from where they count as
        running again). Plain reads of two values: any thread may ask."""
        return len(self.running) + self._ending

    def cancel(self, rid: int) -> bool:
        """Drop a request wherever it is (same-thread as step(), like all
        engine calls). Pending → removed; staged → removed with its prefix
        pins released; running → retires at the next chunk boundary (the
        slot and its pages free through the normal retirement flush — a
        dropped client stops costing TPU once the chunk in flight and the
        one dispatched behind it have run); in its last chunk, still in
        flight → that chunk's tokens are discarded. Returns False for
        unknown/already-finished rids. A cancelled request never lands in
        ``done``; its partial tokens are discarded."""
        for i, req in enumerate(self.pending):
            if req.rid == rid:
                self.pending.pop(i)
                self._stream_pos.pop(rid, None)
                return True
        for i, entry in enumerate(self._staged):
            if entry.req.rid == rid:
                if self.kv == "paged":
                    for p in entry.matched:
                        self.allocator.release(p)
                self._staged.pop(i)
                self._stream_pos.pop(rid, None)
                return True
        for req in self._decoding():
            if req.rid == rid:
                req.cancelled = True  # is_done() now true → retires next chunk
                return True
        return False

    # -- engine internals ---------------------------------------------------

    def _free_slots(self) -> list[int]:
        return [s for s in range(self.S) if s not in self.running]

    def _pages_needed(self, Tp: int, max_new: int) -> int:
        """Worst-case page RESERVATION for a request: prompt + budget,
        rounded up to whole decode chunks — a request retiring mid-chunk
        keeps writing (discarded) tokens until the chunk ends, and those
        writes must land inside its own pages. Reserving up front means
        decode can never hit an empty pool mid-request: admission is the
        only wait point, exactly like waiting for a free slot."""
        h = self.decode_chunk
        hi = min(Tp + -(-max_new // h) * h, self.max_len)
        return -(-hi // self.page_len)

    def _stage_prefills(self, budget: int):
        """Stage pending requests until ``budget`` are staged, and run prefill
        work for every staged entry: one chunk each (the one-chunk-per-pass
        stall bound), or the whole prompt when unchunked. Called from
        ``_admit``, after the pass's decode chunk is dispatched, so prefill
        compute queues behind the chunk instead of delaying it; with nothing
        decoding it runs at once."""
        while self.pending and len(self._staged) < budget:
            req = self.pending.pop(0)
            req.staged_s = time.time()
            entry = _Staged(req, self.programs.init_staging(self.max_len))
            if self.kv == "paged" and self.programs.gather_prefix is not None:
                from tony_tpu.models.paged_cache import prefix_keys

                entry.keys = prefix_keys(req.prompt, self.page_len)
                self._match_prefix_into(entry)
            self._staged.append(entry)
        # burst dedup: a staged entry whose FIRST full page matches ANY
        # earlier still-staged entry defers its prefill — the earlier
        # one admits and registers its pages, and this one re-matches
        # them (_advance_prefill) instead of recomputing. The leader
        # keeps claiming its key even after ITS prefill completes:
        # while it is page-blocked at admission nothing is registered
        # yet, and letting a follower through would burn a full
        # redundant prefill per blocked round.
        seen_first: set[tuple] = set()
        for entry in self._staged:
            fk = entry.keys[0] if entry.keys else None
            defer = (
                fk is not None and fk in seen_first
                and entry.first is None and entry.pos == 0 and not entry.matched
                # once the leader REGISTERED the prefix, followers must
                # all proceed this round (they re-match, not recompute) —
                # deferring on the raw key would serialize the burst to
                # one follower per engine step
                and not self.allocator.has_key(fk)
            )
            if fk is not None:
                seen_first.add(fk)
            if not defer:
                self._advance_prefill(entry)

    def _match_prefix_into(self, entry: _Staged) -> bool:
        """Shared-prefix reuse (paged kv): pin the longest resident chain of
        FULL prompt pages, copy it into the entry's staging cache, and start
        prefill after it — N same-prefix requests run ~1 prefill. Capped at
        (Tp-1)//page_len: the LAST prompt token must always be prefilled
        (its logits sample the first output token). Only callable while the
        entry has no pins and no prefill progress."""
        cap = (len(entry.req.prompt) - 1) // self.page_len
        matched = self.allocator.match_prefix(entry.keys[:cap])
        if self.programs.prefix_usable is not None:
            keep = self.programs.prefix_usable(self.allocator, matched)
            for p in matched[keep:]:
                self.allocator.release(p)  # matched past what is usable: unpinned again
            matched = matched[:keep]
        if not matched:
            return False
        # a host array: `jnp.asarray` of a list compiles a conversion a distinct LENGTH, and where matches differ
        # in length (a session's turns: 16 to 76 pages) that is a compile a new length inside a serving window
        entry.pre = self.programs.gather_prefix(
            entry.pre, self.cache, np.asarray(matched, np.int32), len(matched))
        entry.pos = len(matched) * self.page_len
        entry.matched = matched
        entry.req.prefix_tokens = entry.pos
        self.prefix_hit_tokens += entry.pos
        return True

    def _advance_prefill(self, entry: _Staged) -> None:
        """Run one prefill chunk (or the whole prompt when unchunked).
        ``pos`` starts past any shared-prefix pages (paged kv)."""
        req, pre, pos, first = entry.req, entry.pre, entry.pos, entry.first
        if first is not None:
            return
        Tp = len(req.prompt)
        if self.kv == "paged" and pos == 0 and not entry.matched:
            # the prefix chain may have grown since this entry was staged
            # (an earlier same-prefix request admitted) — re-match before
            # spending any prefill compute
            if self._match_prefix_into(entry):
                pre, pos = entry.pre, entry.pos
        step = self.prefill_chunk if self.prefill_chunk > 0 else Tp
        while first is None:
            take = min(step, Tp - pos)
            last = pos + take >= Tp
            if last:
                # cap the pad so the padded write NEVER runs past max_len —
                # dynamic_update_slice would clamp the start and silently
                # shift real prompt K/V (caught by review repro: prompt 59,
                # chunk 8, max_len 64 corrupted positions 48..59)
                pad = self.programs.prefill_pad(take, self.prefill_chunk, self.max_len - pos)
            else:
                pad = 0  # middle chunks are exact: cache positions stay true
            toks = jnp.array(
                req.prompt[pos:pos + take] + [0] * pad, jnp.int32
            )[None, :]
            # padded positions write garbage K/V past Tp; decode masks them
            # out via lengths[slot] = Tp, and causality protects the prefix
            last_logits, pre = self.programs.prefill_chunk(self.params, toks, pre, take)
            _PREFILL_TOKENS.inc(take + pad)
            _PREFILL_CHUNKS.inc(path=self.programs.prefill_path(pos, take))
            if self.programs.routed_ffn_form is not None:
                _ROUTED_FFN.inc(form=self.programs.routed_ffn_form(take + pad))
            req.prefill_chunks += 1
            pos += take
            if last:
                if (
                    req.temperature is not None or req.top_k is not None
                    or req.top_p is not None
                ):
                    first = sample_logits(
                        last_logits, self._split(),
                        jnp.full((1,), req.temperature if req.temperature is not None
                                 else self.temperature, jnp.float32),
                        jnp.full((1,), req.top_k if req.top_k is not None
                                 else self.top_k, jnp.int32),
                        jnp.full((1,), req.top_p if req.top_p is not None
                                 else 0.0, jnp.float32),
                    )
                else:
                    first = _sample(
                        last_logits, self._split(), self.temperature, self.top_k
                    )
            entry.pre, entry.pos, entry.first = pre, pos, first
            if first is not None:
                # start the device→host copy NOW, while the prefill is still
                # in flight: admission's int(first[0]) then finds the value
                # already local instead of paying a blocking round trip per
                # request (pure admission serialization)
                try:
                    first.copy_to_host_async()
                except AttributeError:  # non-jax.Array stand-ins in tests
                    pass
            if self.prefill_chunk > 0:
                break  # one chunk per engine step — decode interleaves

    def _admit(self, under: str):
        """Everything a request needs between the queue and its first decode
        chunk: stage, prefill, pages, ``insert``, its token, its sampling row.
        ``under`` says what the device is doing meanwhile. "chunk": a decode
        chunk is in flight and all of this queues behind it; the request
        decodes from the next chunk, and its first token stays a device value
        until that chunk's tokens are read (the host never waits for a prefill
        with the next chunk undispatched). "idle": nothing is decoding (start-up,
        drain, a replica that only prefills), there is nothing to hide behind,
        and the first token is taken at once."""
        free = self._free_slots()
        # one speculative stage beyond the free slots: its prefill is done by
        # the time a slot frees that the host could not foresee (EOS)
        self._stage_prefills(max(len(free), 1))
        while self._staged and free and self._staged[0].first is not None:
            head = self._staged[0]
            req, pre = head.req, head.pre
            slot = free[0]
            Tp = len(req.prompt)
            if self.kv == "paged":
                if not self._admit_paged(req, pre, head.matched, head.keys, slot, Tp):
                    _ADMIT_BLOCKED.inc(reason="pages")
                    break  # pages short: admission waits for retirements
            else:
                self.cache = self.programs.insert_dense(
                    self.cache, pre, jnp.int32(slot), jnp.int32(Tp)
                )
            self._staged.pop(0)
            free.pop(0)
            self.tokens = _set_slot_token(self.tokens, jnp.int32(slot), head.first)
            self._samp_temp[slot] = (
                req.temperature if req.temperature is not None else self.temperature
            )
            self._samp_topk[slot] = req.top_k if req.top_k is not None else self.top_k
            self._samp_topp[slot] = req.top_p if req.top_p is not None else 0.0
            self._samp_dirty = True
            self._slot_len[slot] = Tp
            req.slot, req.first = slot, head.first
            self.running[slot] = req
            _ADMISSIONS.inc(under=under)
            if under == "idle":
                self.phase.to("prefill_wait")  # blocked until the prefill has run
                self._take_first(req)
                self.phase.to("admit")
                if req.is_done(self.eos_id):  # 1-token requests finish at admission
                    self._free_slot(slot)
                    self._finish(req)
        if not free and (self.pending or self._staged):
            _ADMIT_BLOCKED.inc(reason="slots")
        self._sampling_arrays()  # an admission's row is uploaded here, not before the next chunk

    def _take_first(self, req: _Request) -> None:
        """The host copy of the first token (async-warmed since its prefill)."""
        req.out.append(int(np.asarray(req.first)[0]))
        req.first = None
        req.slot_s = time.time()

    def _admit_paged(
        self, req, pre, matched: list[int], keys: list[tuple], slot: int, Tp: int
    ) -> bool:
        """Reserve pages, attach the shared prefix, copy the prefilled span,
        install the page-table row. False → pool short, caller waits."""
        n_covered = self._pages_needed(Tp, req.max_new_tokens)
        n_fresh = n_covered - len(matched)
        if n_fresh > self.allocator.available():
            # nothing running means nothing will retire to free pages — the
            # only reclaimable capacity is OTHER staged entries' prefix pins.
            # Demoting a pin is free: its content was already COPIED into
            # that entry's staging cache, so insert simply copies instead of
            # attaching. Demote and retry once; still short → a true wait.
            if not self.running:
                for entry in self._staged:
                    if entry.req is not req and entry.matched:
                        for p in entry.matched:
                            self.allocator.release(p)
                        entry.matched = []
                if n_fresh > self.allocator.available():
                    return False
            else:
                return False
        fresh = self.allocator.alloc(n_fresh)
        row = list(matched) + fresh                      # logical page order
        n_prefill = -(-Tp // self.page_len)              # pages holding prompt K/V
        nc = n_prefill - len(matched)                    # pages to COPY from staging
        pt_row = np.zeros(self.max_pages, np.int32)
        pt_row[:n_covered] = row
        # fresh-page list padded to a FIXED [max_pages] width + traced copy
        # count: one compiled insert variant covers every page-count class
        # (a [nc]-shaped arg would re-compile per distinct nc)
        fp = np.zeros(self.max_pages, np.int32)
        fp[:nc] = fresh[:nc]
        self.cache = self.programs.insert(
            self.cache, pre, fp, pt_row,
            jnp.int32(slot), jnp.int32(Tp), jnp.int32(len(matched)), jnp.int32(nc),
        )
        # content-address the request's FULL prompt pages so later
        # same-prefix requests reuse them (first writer wins)
        # (no keys where the model shares no pages: nothing is registered)
        for j in range(len(matched), min(Tp // self.page_len, len(keys))):
            self.allocator.register(row[j], keys[j])
        self._slot_pages[slot] = row
        return True

    def _split(self):
        if self.temperature == 0.0 and not self._per_slot:
            return self.key  # greedy sampling never consumes the key
        self.key, sub = jax.random.split(self.key)
        return sub

    def _free_slot(self, slot: int) -> None:
        """The slot's request ends with the chunk in flight (or has ended):
        from here on the slot may be given away. Its device-side reset waits
        for ``_flush_retired``, which every pass runs before it admits."""
        del self.running[slot]
        self._retired_slots.append(slot)
        self._slot_len[slot] = 0

    def _finish(self, req: _Request) -> None:
        """Its last token is in ``out``: hand the answer over."""
        req.finished = True
        if req.cancelled:
            self._stream_pos.pop(req.rid, None)  # nobody drains it again
        else:
            self.done[req.rid] = req.out

    def _flush_retired(self):
        """Zero freed slots' device-side lengths in ONE update (idle slots
        would otherwise keep advancing, clamped at maxT, and the ragged
        kernel would stream their stale cache every step). Run at the head of
        a pass, with one chunk in flight at most, and queued behind it. That
        chunk is the old owners' last use of their slots and pages: a budget's
        end freed the slot at that chunk's dispatch, an EOS at the read of the
        chunk before, by when this one had been dispatched with the request
        still in it. The chunk this pass dispatches comes after the update and
        after this pass's admissions, which may be handed the same slots and
        the same pages, and the device runs them in that order."""
        idle, self._retired_slots = self._retired_slots, []
        if idle:
            mask = np.zeros(self.S, bool)
            mask[idle] = True
            mask = jnp.asarray(mask)  # [S] always — one compiled variant
            if self.kv == "paged":
                # release the reservation (registered full-prompt pages park
                # in the allocator's reuse pool for future prefix hits) and
                # reset the page-table rows: an idle slot's garbage write
                # lands in the sacrificial page 0, never a live page
                for s in idle:
                    for p in self._slot_pages.pop(s, []):
                        self.allocator.release(p)
            self.cache = self.programs.release(self.cache, mask)

    def _sampling_arrays(self):
        """Per-slot sampling parameters on the device; None while no request
        has overridden the engine's. Uploaded only when an admission changed a
        slot's row — not per chunk forever after the first override."""
        if not self._per_slot:
            return None
        if self._samp_dirty or self._samp_dev is None:
            self._samp_dev = (
                jnp.asarray(self._samp_temp),
                jnp.asarray(self._samp_topk),
                jnp.asarray(self._samp_topp),
            )
            self._samp_dirty = False
        return self._samp_dev

    def _dispatch_chunk(self) -> _Chunk:
        """Dispatch one decode chunk over the running slots. A request the
        host already knows to end inside the chunk (``ends_within``) gives its
        slot up HERE, so that the next pass's admission can refill it behind
        the chunk and no chunk is lost to the hand-over. Everything here is
        decided from what has been dispatched (``_Request.steps``,
        ``_slot_len``): the tokens of the chunk before may still be unread."""
        self.phase.to("dispatch")
        # constant chunk height = ONE compiled decode variant; slots whose
        # request finishes mid-chunk simply discard the overshoot tokens
        # (their cache writes clamp at the view's end and the slot is fully
        # overwritten at its next admission)
        h = self.decode_chunk
        flying = dict(self.running)
        samp = self._sampling_arrays()
        if self.kv == "paged":
            # paged decode has exactly one path: the page-indirected ragged
            # kernel ("ragged" below is ignored by _decode_one's paged branch)
            use_ragged, bucket = True, 0
        else:
            needed = max(self._slot_len[s] for s in flying) + h
            bucket = min(_bucket(max(needed, 1)), self.max_len)
            use_ragged = self.attn == "ragged" or (
                self.attn == "auto" and bucket > self.RAGGED_THRESHOLD
            )
        counts = ()
        if use_ragged:
            toks, seq, self.cache, *counts = self.programs.decode_chunk(
                self.params, self.cache, self.tokens, self._split(), n=h,
                temperature=self.temperature, top_k=self.top_k, samp=samp,
            )
        else:
            # length bucket: attention reads only the shortest power-of-two
            # cache prefix covering every active slot through this chunk
            toks, seq, self.cache = self.programs.decode_chunk_bucketed(
                self.params, self.cache, self.tokens, self._split(), n=h, bucket=bucket,
                temperature=self.temperature, top_k=self.top_k, samp=samp,
            )
        self.tokens = toks
        _CHUNKS.inc()
        if self.programs.routed_ffn_form is not None:
            _ROUTED_FFN.inc(form=self.programs.routed_ffn_form(self.S))
        _DECODE_SLOTS.inc(len(flying))
        # what the chunk's steps have in context and may read of it, from the
        # host's own lengths: step j of slot s sees _slot_len[s] + j + 1 positions
        context = np.array([self._slot_len[s] for s in flying])[:, None] + np.arange(1, h + 1)
        _CONTEXT_TOKENS.inc(int(context.sum()))
        _VISIBLE_TOKENS.inc(int(np.sum(self.programs.visible_tokens(context))))
        ending = 0
        for slot, req in flying.items():
            if req.ends_within(h):
                self._free_slot(slot)
                ending += 1
            else:
                req.steps += h
                self._slot_len[slot] = min(self._slot_len[slot] + h, self.max_len)
        self._ending = ending
        return _Chunk(flying, seq, tuple(counts))

    def _emit(self, chunk: _Chunk) -> None:
        """Block on a chunk's tokens, hand them to their requests, retire."""
        self.phase.to("decode_wait")
        seq_host = np.asarray(chunk.seq)  # [h, S]: ONE device→host transfer
        self.phase.to("emit")
        for counter, value in zip(_EXPERT_COUNTS, np.asarray(chunk.counts[0]) if chunk.counts else ()):
            counter.inc(int(value))
        # the slots the chunk was dispatched WITH: by now ``running`` may
        # name a slot's next owner, whose tokens these are not
        for slot, req in chunk.flying.items():
            if req.finished:
                continue  # an EOS or a cancel met in the chunk before, this one already dispatched with it: all overshoot
            if req.first is not None:
                self._take_first(req)  # no wait: its prefill ran before this chunk did
            for i in range(self.decode_chunk):
                if req.is_done(self.eos_id):
                    break  # post-budget/post-EOS chunk tokens are discarded
                req.out.append(int(seq_host[i, slot]))
            if not req.is_done(self.eos_id):
                continue
            if self.running.get(slot) is req:
                self._free_slot(slot)  # an EOS: not known at dispatch, refilled by the next pass's admission
            self._finish(req)

    def step(self) -> bool:
        """One pass. Returns True while work remains.

        Two decode chunks deep. A pass finds the chunk the pass before
        dispatched still in flight, its tokens unread. (1) While it runs, do
        everything the NEXT chunk needs: flush retirements, stage, advance
        prefills, reserve pages, ``insert``, set the slot's token. (2) Dispatch
        the next chunk over the slots that are running. All of it queues on the
        device behind the chunk in flight, and the device executes in dispatch
        order: that order is the only synchronisation, and the device goes from
        one chunk through admission's programs into the next with no turn of
        the host between. (3) Block on the older chunk's tokens, emit, retire:
        the read-back, the walk and whatever the caller does before its next
        pass run under the chunk just dispatched. Nothing (1) and (2) use comes
        from the host's copy of tokens: the next chunk's input tokens and the
        cache stay on the device, and a budget's end is counted from what was
        dispatched. Only an EOS is learnt from tokens, one chunk late: its
        request is in the chunk behind too, whose tokens of it are discarded.
        With no chunk in flight (start-up, after a drain) there is no (3) and
        admission runs with the device idle; with nothing running there is no
        (2), and ``more`` stays True until the last chunk in flight is read."""
        waiting = bool(self._chunks)
        self._ending = 0
        self.phase.to("admit")
        self._flush_retired()
        self._admit("chunk" if waiting else "idle")
        if self.running:
            self._chunks.append(self._dispatch_chunk())
            if waiting:
                _CHUNKS_AHEAD.inc()
        if waiting:
            self._emit(self._chunks.pop(0))
        more = bool(self._chunks or self.running or self.pending or self._staged)
        if not more:
            # drained: zero the final chunk's retirees now — cache.lengths is
            # externally observable and must agree with _slot_len between runs
            self._flush_retired()
        self.phase.to(None)
        return more

    def drain_stream(self) -> dict[int, tuple[list[int], bool]]:
        """Tokens appended per request since the last drain:
        {rid: (new_tokens, finished)}. Pure host-side bookkeeping (reads
        ``req.out`` cursors) — call between ``step()``s to stream
        incrementally; a finished request is reported exactly once with its
        final tokens and then forgotten."""
        out: dict[int, tuple[list[int], bool]] = {}
        # prune: once a finished request is popped from ``done`` by the
        # caller, its dedup entry has no further use — without this the set
        # grows with every request a long-lived server ever finishes
        self._stream_done &= self.done.keys()
        for rid, toks in self.done.items():
            if rid not in self._stream_done:
                pos = self._stream_pos.pop(rid, 0)
                out[rid] = (list(toks[pos:]), True)
                self._stream_done.add(rid)
        live = [e.req for e in self._staged] + list(self.pending) + self._decoding()
        for req in live:
            if req.rid in self._stream_done or req.rid in out:
                continue
            pos = self._stream_pos.get(req.rid, 0)
            if len(req.out) > pos:
                out[req.rid] = (list(req.out[pos:]), False)
                self._stream_pos[req.rid] = len(req.out)
        return out

    def run(self) -> dict[int, list[int]]:
        """Drain all submitted requests; returns {request_id: tokens}."""
        while self.step():
            pass
        return dict(self.done)
