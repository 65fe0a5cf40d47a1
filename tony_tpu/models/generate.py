"""Autoregressive generation with a KV cache for the Llama family.

The reference orchestrates training jobs only — serving/eval is new
capability (SURVEY.md §2.5 "absent" rows). TPU-first shape discipline:
the cache is a static [L, B, Hkv, max_len, Dh] ring of bf16 K/V, decode
steps are one jitted token step with `lax.scan` over positions (no Python
loop, no dynamic shapes), and attention against the cache is masked
full-length so XLA compiles one kernel for every step.

Numerical parity with training: reuses the same rms_norm/rope/swiglu ops
and the params pytree from models/llama.py — `tests/test_generate.py`
asserts greedy decode reproduces teacher-forced forward argmaxes.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from tony_tpu.models.llama import LlamaConfig
from tony_tpu.ops import layers as L
from tony_tpu.ops import quant as Q


def _mm(x, w):
    """x @ w where w may be an int8 QTensor (weight-only quantized serving:
    quant.quantize_tree(params) then pass the tree here unchanged)."""
    if isinstance(w, Q.QTensor):
        return Q.int8_matmul(x, w).astype(x.dtype)
    return jnp.einsum("...d,dh->...h", x, w)


def _embed_lookup(embed, tokens, dtype):
    if isinstance(embed, Q.QTensor):
        rows = jnp.take(embed.q, tokens, axis=0).astype(jnp.float32)
        return (rows * embed.scale).astype(dtype)
    return jnp.take(embed, tokens, axis=0)


class KVCache(NamedTuple):
    """Static-shape decode state. k/v: [L, B, Hkv, max_len, Dh]."""

    k: jax.Array
    v: jax.Array
    length: jax.Array  # [] int32 — tokens already in the cache


def init_cache(cfg: LlamaConfig, batch: int, max_len: int) -> KVCache:
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, max_len, cfg.head_dim)
    return KVCache(
        k=jnp.zeros(shape, cfg.jdtype),
        v=jnp.zeros(shape, cfg.jdtype),
        length=jnp.zeros((), jnp.int32),
    )


def _cached_attention(q, ck, cv, length, n_rep, window: int = 0):
    """q: [B, H, Tq, Dh]; ck/cv: [B, Hkv, maxT, Dh]; positions < length+Tq.

    Masked full-length attention: rows attend to cache slots [0, length+row]
    (causal within the new tokens, everything before them unconditionally);
    with ``window`` > 0 the band narrows to the last ``window`` positions —
    decode then matches the training-side sliding-window semantics instead
    of silently widening beyond it.
    """
    from tony_tpu.ops.attention import repeat_kv

    B, H, Tq, Dh = q.shape
    maxT = ck.shape[2]
    ck = repeat_kv(ck, n_rep)
    cv = repeat_kv(cv, n_rep)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, ck, preferred_element_type=jnp.float32)
    s = s * (Dh ** -0.5)
    slot = jax.lax.broadcasted_iota(jnp.int32, (Tq, maxT), 1)
    row_end = length + jax.lax.broadcasted_iota(jnp.int32, (Tq, maxT), 0)
    ok = slot <= row_end
    if window > 0:
        ok = jnp.logical_and(ok, slot > row_end - window)
    s = jnp.where(ok, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(cv.dtype), cv)


def _masked_slot_attention(q1, ck, cv, lengths, n_rep, window: int = 0,
                           *, cur_k, cur_v):
    """Single-token decode attention over read-only caches (shared by the
    serving engine's bucketed path and ``generate()``'s decode steps — ONE
    implementation, so the two paths cannot diverge in attention MATH;
    note bf16 projections can still differ by 1 ulp between batch sizes
    from XLA tiling, which is why MoE greedy-parity tests run f32).

    q1 [S, H, Dh] vs per-slot caches [S, Hkv, maxT, Dh]. ``lengths`` counts
    CACHE positions only; the current token's K/V arrive separately
    (``cur_k``/``cur_v`` [S, Hkv, Dh]) and its score is appended before the
    softmax — the big cache is READ-ONLY here, so callers write it once per
    step with a tiny scatter instead of carrying a full cache copy through
    their layer scans (the r3-cont serving fix: the copy cost −32% decode
    tok/s at 64 slots). Slot s attends cache positions
    [max(0, len_s + 1 - window), len_s) plus itself (always in-window)."""
    from tony_tpu.ops.attention import repeat_kv

    S, H, Dh = q1.shape
    maxT = ck.shape[2]
    ckr = repeat_kv(ck, n_rep)
    cvr = repeat_kv(cv, n_rep)
    s = jnp.einsum("shd,shkd->shk", q1, ckr, preferred_element_type=jnp.float32)
    s = s * (Dh ** -0.5)
    idx = jax.lax.broadcasted_iota(jnp.int32, (S, 1, maxT), 2)
    hi = lengths[:, None, None]
    ok = idx < hi
    if window > 0:
        ok = jnp.logical_and(ok, idx >= hi + 1 - window)
    s = jnp.where(ok, s, -1e30)
    ckr1 = repeat_kv(cur_k[:, :, None], n_rep)[:, :, 0]          # [S, H, Dh]
    cvr1 = repeat_kv(cur_v[:, :, None], n_rep)[:, :, 0]
    s_self = jnp.einsum(
        "shd,shd->sh", q1, ckr1, preferred_element_type=jnp.float32
    )[..., None] * (Dh ** -0.5)
    p = jax.nn.softmax(jnp.concatenate([s, s_self], axis=-1), axis=-1)
    o = jnp.einsum("shk,shkd->shd", p[..., :maxT].astype(cvr.dtype), cvr)
    return o + p[..., maxT:].astype(cvr1.dtype) * cvr1


def _ffn_with_cache(h, lp, cfg: LlamaConfig):
    """Decode-side FFN: dense SwiGLU, or the MoE mixture when the layer
    params carry a router (Mixtral family).

    The MoE DECODE path (short Tq) computes ALL experts for every row and
    combines with the top-k one-hot gates: E / top_k times the products a
    routed dispatch makes, in exchange for no sort and no gather. At Mixtral's
    8 experts and a handful of rows that is cheap beside the weight read,
    which touches most experts anyway. It does not scale: at 128 experts and
    256 rows it is sixteen times the products, as long again as the layer's
    whole weight read, which is why models/exaone_moe.py decodes through
    parallel/expert.held_expert_ffn (sorted rows, the chosen experts only).
    Gates renormalize over top-k exactly like training
    (parallel/expert._gating). PREFILL (long Tq) routes through the training
    dispatch instead — all-expert compute over a whole prompt would pay
    E/top_k× the FFN FLOPs and materialize [B, T, E, F] banks."""
    if "router" not in lp:
        g = jax.nn.silu(_mm(h, lp["w_gate"]))
        u = _mm(h, lp["w_up"])
        return _mm(g * u, lp["w_down"])
    if h.shape[1] > 16:  # prefill: routed dispatch, same math, top-k FLOPs
        from tony_tpu.parallel.expert import moe_ffn

        y, _ = moe_ffn(
            h, lp["router"], lp["we_gate"], lp["we_up"], lp["we_down"], cfg.moe, None
        )
        return y
    from tony_tpu.parallel.expert import _gating

    E = lp["router"].shape[-1]
    # ONE copy of the gating convention: the training-side _gating
    # (softmax → top-k → renormalize) drives decode too
    gate_vals, gate_idx, _, _ = _gating(h, lp["router"], cfg.moe, None)
    w = jnp.sum(jax.nn.one_hot(gate_idx, E) * gate_vals[..., None], axis=-2)  # [B,T,E]
    ge = jnp.einsum("btd,edf->btef", h, lp["we_gate"])
    ue = jnp.einsum("btd,edf->btef", h, lp["we_up"])
    ye = jnp.einsum("btef,efd->bted", jax.nn.silu(ge) * ue, lp["we_down"])
    return jnp.einsum("bted,bte->btd", ye, w.astype(ye.dtype))


def _block_with_cache(x, lp, ck, cv, length, cos, sin, cfg: LlamaConfig):
    """One decoder block over Tq new tokens at positions [length, length+Tq).

    Returns (x, new_k, new_v) where new_k/v are this step's K/V slabs
    [B, Hkv, Tq, Dh] for the caller to write into the cache.
    """
    B, Tq = x.shape[0], x.shape[1]
    Dh, H, Hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    positions = length + jnp.arange(Tq)

    h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q = _mm(h, lp["wq"]).reshape(B, Tq, H, Dh).transpose(0, 2, 1, 3)
    k = _mm(h, lp["wk"]).reshape(B, Tq, Hkv, Dh).transpose(0, 2, 1, 3)
    v = _mm(h, lp["wv"]).reshape(B, Tq, Hkv, Dh).transpose(0, 2, 1, 3)
    q = L.apply_rope(q, cos, sin, positions=positions)
    k = L.apply_rope(k, cos, sin, positions=positions)

    if Tq == 1:
        # decode: the cache stays read-only (same split attention math as
        # the serving engine — shared _masked_slot_attention) and the
        # caller's post-scan dynamic_update_slice is the only cache write
        o = _masked_slot_attention(
            q[:, :, 0], ck, cv, jnp.broadcast_to(length, (B,)), H // Hkv,
            window=cfg.sliding_window,
            cur_k=k[:, :, 0].astype(ck.dtype), cur_v=v[:, :, 0].astype(cv.dtype),
        )[:, :, None]
    else:
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, 0, length, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, 0, length, 0))
        o = _cached_attention(q, ck, cv, length, H // Hkv, window=cfg.sliding_window)
    o = o.transpose(0, 2, 1, 3).reshape(B, Tq, H * Dh)
    x = x + _mm(o, lp["wo"])
    h = L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    x = x + _ffn_with_cache(h, lp, cfg)
    return x, k, v


def _forward_with_cache(params, tokens, cache: KVCache, cfg: LlamaConfig):
    """tokens [B, Tq] (new tokens only) → (logits [B, Tq, V], cache')."""
    maxT = cache.k.shape[3]
    cos, sin = L.rope_frequencies(cfg.head_dim, maxT, cfg.rope_theta, cfg.rope_scaling)
    x = _embed_lookup(params["embed"], tokens, cfg.jdtype)

    def layer(x, inputs):
        lp, ck, cv = inputs
        x, new_k, new_v = _block_with_cache(x, lp, ck, cv, cache.length, cos, sin, cfg)
        return x, (new_k, new_v)

    x, (new_ks, new_vs) = jax.lax.scan(layer, x, (params["layers"], cache.k, cache.v))
    Tq = tokens.shape[1]
    k = jax.lax.dynamic_update_slice(cache.k, new_ks, (0, 0, 0, cache.length, 0))
    v = jax.lax.dynamic_update_slice(cache.v, new_vs, (0, 0, 0, cache.length, 0))
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _mm(x, params["lm_head"]).astype(jnp.float32)
    return logits, KVCache(k, v, cache.length + Tq)


def prefill(params, tokens, cache: KVCache, cfg: LlamaConfig):
    """Run the prompt through the model, filling the cache.

    Returns (last-position logits [B, V], cache')."""
    logits, cache = _forward_with_cache(params, tokens, cache, cfg)
    return logits[:, -1], cache


# module-level jits: generate() is called per serving request, so the traced
# functions must be cached across calls (keys/prompt/cache are arguments,
# never closure constants — a closure would retrace every request)
_prefill_jit = jax.jit(prefill, static_argnames=("cfg",))


@functools.partial(jax.jit, static_argnames=("cfg", "temperature", "top_k"))
def _decode_all(params, cache, first, keys, cfg, temperature, top_k):
    def step(carry, k_step):
        cache, tok = carry
        logits, cache = _forward_with_cache(params, tok[:, None], cache, cfg)
        nxt = _sample(logits[:, -1], k_step, temperature, top_k)
        return (cache, nxt), nxt

    (_, _), rest = jax.lax.scan(step, (cache, first), keys)
    return rest


def _sample(logits, key, temperature: float, top_k: int):
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def sample_logits(logits, key, temperature, top_k, top_p):
    """PER-ROW sampling with temperature / top-k / top-p (nucleus), all
    DEVICE arrays [B] — one compiled variant serves every mixture of
    per-request params (the serving engine's per-slot path; the static
    ``_sample`` stays the cheap batch path when every row shares params).

    Row semantics: temperature 0 → greedy (argmax; the key is unused for
    that row); top_k 0 → no k-cut; top_p outside (0, 1) → no nucleus cut.
    One descending sort powers both cuts.
    """
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    desc = jnp.sort(scaled, axis=-1)[:, ::-1]                     # [B, V]
    # top-k: threshold at the k-th largest (k<=0 → keep all)
    k_idx = jnp.clip(top_k - 1, 0, V - 1)
    kth = jnp.take_along_axis(desc, k_idx[:, None], axis=1)       # [B, 1]
    keep_k = (top_k[:, None] <= 0) | (scaled >= kth)
    # top-p: smallest prefix of the sorted probs with mass >= p; the
    # threshold is the logit of the LAST kept rank
    probs = jax.nn.softmax(desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    p = top_p[:, None]
    nucleus = (cum - probs) < p                                    # keep-while mask
    last_rank = jnp.maximum(nucleus.sum(axis=-1) - 1, 0)           # [B]
    pth = jnp.take_along_axis(desc, last_rank[:, None], axis=1)    # [B, 1]
    keep_p = (p <= 0) | (p >= 1) | (scaled >= pth)
    masked = jnp.where(keep_k & keep_p, scaled, -1e30)
    sampled = jax.random.categorical(key, masked, axis=-1).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


def generate(
    params,
    prompt: jax.Array,
    cfg: LlamaConfig,
    *,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: int = 0,
    key: jax.Array | None = None,
    max_len: int | None = None,
) -> jax.Array:
    """prompt [B, Tp] int32 → generated tokens [B, max_new_tokens].

    Greedy when temperature == 0, else top-k/temperature sampling. One jit
    for prefill, one for the scanned decode loop.
    """
    B, Tp = prompt.shape
    max_len = max_len or (Tp + max_new_tokens)
    assert max_len >= Tp + max_new_tokens, "cache too small for requested tokens"
    key = key if key is not None else jax.random.PRNGKey(0)
    keys = jax.random.split(key, max_new_tokens)

    cache = init_cache(cfg, B, max_len)
    logits, cache = _prefill_jit(params, prompt, cache, cfg)
    first = _sample(logits, keys[0], temperature, top_k)

    if max_new_tokens == 1:
        return first[:, None]
    rest = _decode_all(params, cache, first, keys[1:], cfg, temperature, top_k)  # [N-1, B]
    return jnp.concatenate([first[:, None], rest.T], axis=1)
