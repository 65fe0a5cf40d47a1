"""The exaone_moe family (K-EXAONE): a decoder with a leading dense layer, then
layers whose FFN is routed, and attention that is windowed on three layers in
four and full on the fourth.

- Attention, every layer: pre-norm; q, k, v without biases; RMSNorm on q and k
  per head; GQA. A ``sliding_attention`` layer applies the rotary embedding and
  lets a query see itself and the ``window - 1`` positions before it; a
  ``full_attention`` layer applies none and sees everything before it.
- FFN: the first ``dense_layers`` layers a SwiGLU of width ``d_ff``; every
  other layer a router over ``num_experts`` experts (float32 sigmoid scores;
  the ``top_k`` largest of score + bias are chosen, the bias choosing and not
  weighing; the chosen scores renormalised and scaled by ``routed_scale``) and
  a shared expert, each a SwiGLU of width ``d_expert``.
- The share: the layer holds experts ``held = (first, count)`` of the
  ``num_experts`` it routes over (parallel/expert.held_expert_ffn): it adds its
  own experts' part of the result and the shared expert, and what the absent
  experts would add is left out. No code stands in for them.
- ``mtp_layers`` multi-token-prediction modules (``mtp_logits``): not served.

``params["dense"]`` is a list (a dict of leaves a dense layer); ``params["layers"]``
holds the routed layers' leaves STACKED, and every program here scans over
them, choosing the layer's kind of attention with ``lax.cond``: both kinds have
one parameter shape, so the compiled program holds one routed layer whatever
the depth (a scan over periods would not fit 47 routed layers, which are eleven
periods and three layers; a scan a kind cannot be, the kinds alternate). The
experts' banks are not among the scan's slices: the grouped kernel takes every
layer's bank and a layer index (a slice handed to a Mosaic call is a copy).

Serving (``serving_programs``, models/serving.py): a cache for each kind. Full
layers keep pages in a pool over the full layers only, read through the page
table (ops/decode_attention.paged_decode_attention); a window layer keeps a
ring of ``window + RING_SLACK`` positions a slot (models/paged_cache.py), so a
slot's window layers cost the same at any ``max_len``. A decode step reads a
ring whole, once, and masks a row by the position it holds
(``ring_decode_attention``: no page table); ``ring_table`` serves the chunk's
one write only (``write_decode_chunk``). No train step and no sharding rules:
served only.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from tony_tpu.ops import layers as L
from tony_tpu.parallel.expert import MoEConfig, held_expert_ffn, held_ffn_form, held_step_counts

WINDOW, FULL = "sliding_attention", "full_attention"


@dataclass(frozen=True)
class ExaoneMoeConfig:
    vocab_size: int = 153_600
    d_model: int = 6144
    n_heads: int = 64
    n_kv_heads: int = 8
    head_dim: int = 128               # free of d_model // n_heads: 64 x 128 = 8192 over 6144
    windows: tuple = (128, 128, 128, 0) * 12   # a layer's window, 0 for a full layer
    dense_layers: int = 1             # leading layers whose FFN is dense
    d_ff: int = 18_432
    d_expert: int = 2048
    num_experts: int = 128
    held: tuple = (0, 128)            # (first, count) of the experts this replica holds
    top_k: int = 8
    routed_scale: float = 2.5
    shared_experts: int = 1
    mtp_layers: int = 0               # multi-token-prediction modules (full attention, routed)
    max_seq: int = 8192
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"

    def __post_init__(self):
        if len({w for w in self.windows if w}) > 1:
            raise ValueError(f"window layers of one size only (a static size of the kernels), got {set(self.windows)}")
        if not 0 <= self.dense_layers <= len(self.windows):
            raise ValueError(f"dense_layers {self.dense_layers} of {len(self.windows)} layers")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"held {self.held} is not a range of the {self.num_experts} experts")

    @property
    def n_layers(self) -> int:
        return len(self.windows)

    @property
    def layer_types(self) -> tuple:
        return tuple(WINDOW if w else FULL for w in self.windows)

    @property
    def window(self) -> int:
        return max(self.windows)

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def moe(self) -> MoEConfig:
        return MoEConfig(num_experts=self.num_experts, top_k=self.top_k, scoring="sigmoid",
                         routed_scale=self.routed_scale, held=self.held)

    def count(self, kind: str) -> int:
        return sum(1 for t in self.layer_types if t == kind)

    def kind_index(self) -> tuple:
        """A layer's place among the layers of its kind: its layer of the cache of that kind."""
        seen, out = {WINDOW: 0, FULL: 0}, []
        for t in self.layer_types:
            out.append(seen[t])
            seen[t] += 1
        return tuple(out)


EXAONE_MOE_TINY = ExaoneMoeConfig(
    vocab_size=256, d_model=64, n_heads=8, n_kv_heads=2, head_dim=16, windows=(8, 8, 8, 0, 8), dense_layers=1,
    d_ff=128, d_expert=32, num_experts=8, held=(0, 4), top_k=2, max_seq=128, dtype="float32",
)

PRESETS = {"exaone-moe-tiny": EXAONE_MOE_TINY}


def init(key: jax.Array, cfg: ExaoneMoeConfig) -> dict:
    """The parameter tree (truncated normal, fan-in scaled; norms at one; the
    router float32, its bias small and not zero). Banks are drawn a layer at a
    time: one draw of every layer's is a float32 temporary of their whole size."""
    D, V, dh, dt = cfg.d_model, cfg.vocab_size, cfg.head_dim, cfg.jdtype
    q, kv, Fe, held = cfg.n_heads * dh, cfg.n_kv_heads * dh, cfg.d_expert, cfg.held[1]
    n_routed = cfg.n_layers - cfg.dense_layers
    ks = iter(jax.random.split(key, 8 + 8 * cfg.dense_layers + 16 * (1 + cfg.mtp_layers)))

    def dense(*shape, fan_in, dtype=dt, scale=1.0):
        return (jax.random.truncated_normal(next(ks), -2, 2, shape, jnp.float32) * (scale * fan_in ** -0.5)).astype(dtype)

    def stack(n, *shape, fan_in, dtype=dt, scale=1.0):
        draw = lambda k: (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32) * (scale * fan_in ** -0.5)).astype(dtype)
        return jax.lax.map(draw, jax.random.split(next(ks), n))

    def attention(n=None):
        mk = dense if n is None else functools.partial(stack, n)
        lead = () if n is None else (n,)
        return {"attn_norm": jnp.ones(lead + (D,), dt), "mlp_norm": jnp.ones(lead + (D,), dt),
                "wq": mk(D, q, fan_in=D), "wk": mk(D, kv, fan_in=D), "wv": mk(D, kv, fan_in=D), "wo": mk(q, D, fan_in=q),
                "q_norm": jnp.ones(lead + (dh,), dt), "k_norm": jnp.ones(lead + (dh,), dt)}

    def routed(n):
        Fs = Fe * cfg.shared_experts
        return {**attention(n),
                "router": stack(n, D, cfg.num_experts, fan_in=D, dtype=jnp.float32),
                "router_bias": stack(n, cfg.num_experts, fan_in=1.0, dtype=jnp.float32, scale=0.1),
                "ws_gate": stack(n, D, Fs, fan_in=D), "ws_up": stack(n, D, Fs, fan_in=D), "ws_down": stack(n, Fs, D, fan_in=Fs),
                "we_gate": stack(n, held, D, Fe, fan_in=D), "we_up": stack(n, held, D, Fe, fan_in=D),
                "we_down": stack(n, held, Fe, D, fan_in=Fe)}

    params = {"embed": dense(V, D, fan_in=1.0),
              "dense": [{**attention(), "w_gate": dense(D, cfg.d_ff, fan_in=D), "w_up": dense(D, cfg.d_ff, fan_in=D),
                         "w_down": dense(cfg.d_ff, D, fan_in=cfg.d_ff)} for _ in range(cfg.dense_layers)],
              "layers": routed(n_routed), "final_norm": jnp.ones((D,), dt), "lm_head": dense(D, V, fan_in=D)}
    if cfg.mtp_layers:
        params["mtp"] = {"proj": stack(cfg.mtp_layers, 2 * D, D, fan_in=2 * D),
                         "hidden_norm": jnp.ones((cfg.mtp_layers, D), dt), "embed_norm": jnp.ones((cfg.mtp_layers, D), dt),
                         "layers": routed(cfg.mtp_layers)}
    return params


# -- a layer over [T, D] rows (a sequence's positions, or the slots' tokens) ------------------------

BANKS = ("we_gate", "we_up", "we_down")


def _mm(x, w):
    return jnp.einsum("...d,dh->...h", x, w)


def _swiglu(h, wg, wu, wd):
    return _mm(jax.nn.silu(_mm(h, wg)) * _mm(h, wu), wd)


def _qkv(h, lp, cfg):
    """h [T, D] -> q [T, H, dh], k, v [T, Hkv, dh]; q and k normed per head (before any rotation)."""
    t, dh = h.shape[0], cfg.head_dim
    q = L.rms_norm(_mm(h, lp["wq"]).reshape(t, cfg.n_heads, dh), lp["q_norm"], cfg.norm_eps)
    k = L.rms_norm(_mm(h, lp["wk"]).reshape(t, cfg.n_kv_heads, dh), lp["k_norm"], cfg.norm_eps)
    return q, k, _mm(h, lp["wv"]).reshape(t, cfg.n_kv_heads, dh)


def _rope(a, cos, sin, positions):
    """a [T, heads, dh] at `positions` [T] (apply_rope reads [B, H, T, D])."""
    return L.apply_rope(a.transpose(1, 0, 2)[None], cos, sin, positions=positions)[0].transpose(1, 0, 2)


def _layer(x, lp, banks, li, attend, cfg, live=None, name="moe_swiglu_prefill"):
    """One layer over rows x [T, D]. `attend(q, k, v) -> (o [T, H, dh], what the
    caller keeps of the layer's keys and values)` is the caller's (it knows the
    cache). A routed layer's banks are `banks` (every routed layer's, stacked)
    at index `li`, and `name` its grouped product's in a trace. Returns (x',
    what `attend` kept, rows [count]: each held expert's rows from the tokens
    `live` marks, or None for a dense layer)."""
    t = x.shape[0]
    h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    o, kept = attend(*_qkv(h, lp, cfg))
    x = x + _mm(o.reshape(t, cfg.n_heads * cfg.head_dim), lp["wo"])
    h = L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    if "router" not in lp:
        return x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]), kept, None
    y, rows = held_expert_ffn(h, lp["router"], lp["router_bias"], *banks, li, cfg.moe, count_mask=live, name=name)
    return x + y + _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"]), kept, rows


def _by_kind(cfg, is_window, window_layer, full_layer, *args):
    """The layer's kind of attention, chosen where the program runs: both kinds
    have one parameter shape, so a scan's body holds both and `lax.cond` takes
    one. A model of one kind only has no cache of the other to index, and its
    branch is never built."""
    if not cfg.count(FULL) or not cfg.count(WINDOW):
        return (window_layer if cfg.count(WINDOW) else full_layer)(*args)
    return jax.lax.cond(is_window == 1, window_layer, full_layer, *args)


def _routed_xs(params, cfg):
    """(the routed layers' leaves but the banks, a layer's kind (1: window), its index among its kind,
    its index among the routed layers), stacked for a scan; and the banks."""
    layers = params["layers"]
    kinds = cfg.layer_types[cfg.dense_layers:]
    n = len(kinds)
    xs = ({k: v for k, v in layers.items() if k not in BANKS},
          jnp.asarray([t == WINDOW for t in kinds], jnp.int32),
          jnp.asarray(cfg.kind_index()[cfg.dense_layers:], jnp.int32), jnp.arange(n, dtype=jnp.int32))
    return xs, tuple(layers[k] for k in BANKS)


def _finish(x, params, cfg):
    """Rows of the trunk -> float32 logits."""
    return _mm(L.rms_norm(x, params["final_norm"], cfg.norm_eps), params["lm_head"]).astype(jnp.float32)


# -- a chunk of one sequence: prefill, and the whole-sequence forward --------------------------------

class Staging(NamedTuple):
    """A request mid-prefill: its full layers' keys and values at their true
    positions, and its window layers' last `window` positions."""

    fk: jax.Array      # [Lf, Hkv, max_len, dh]
    fv: jax.Array
    wk: jax.Array      # [Lw, Hkv, window, dh]: positions length - window .. length - 1 (after the rotation)
    wv: jax.Array
    length: jax.Array  # [] int32


def _init_staging(cfg: ExaoneMoeConfig, max_len: int) -> Staging:
    full = (cfg.count(FULL), cfg.n_kv_heads, max_len, cfg.head_dim)
    tail = (cfg.count(WINDOW), cfg.n_kv_heads, cfg.window, cfg.head_dim)
    return Staging(jnp.zeros(full, cfg.jdtype), jnp.zeros(full, cfg.jdtype), jnp.zeros(tail, cfg.jdtype),
                   jnp.zeros(tail, cfg.jdtype), jnp.zeros((), jnp.int32))


def _chunk(params, tokens, st: Staging, take, cfg: ExaoneMoeConfig):
    """tokens [T] at positions st.length .. + T, the first `take` of them real.
    Returns (the trunk's rows [T, D], the staging with the chunk in it).

    A window layer's queries run the band in flash over [the last `window`
    positions before the chunk ; the chunk], the rows before the chunk being
    padding (flash wants as many queries as keys) and the positions below 0 a
    segment of their own. A full layer's run over the staged keys under a
    causal mask (the masked flash kernel skips tiles past the chunk's end)."""
    from tony_tpu.ops.attention import flash_attention
    from tony_tpu.ops.sparse_attention import masked_prefill_attention

    t, max_len, W = tokens.shape[0], st.fk.shape[2], cfg.window
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pos0 = st.length
    positions = pos0 + jnp.arange(t, dtype=jnp.int32)
    cos, sin = L.rope_frequencies(dh, max(max_len, cfg.max_seq), cfg.rope_theta)

    def window_layer(q, k, v, i, st):
        q, k = _rope(q, cos, sin, positions), _rope(k, cos, sin, positions)
        ek = jnp.concatenate([st.wk[i], k.transpose(1, 0, 2).astype(st.wk.dtype)], axis=1)     # [Hkv, W + t, dh]
        ev = jnp.concatenate([st.wv[i], v.transpose(1, 0, 2).astype(st.wv.dtype)], axis=1)
        eq = jnp.concatenate([jnp.zeros((H, W, dh), q.dtype), q.transpose(1, 0, 2)], axis=1)
        seg = jnp.concatenate([pos0 - W + jnp.arange(W) >= 0, jnp.ones((t,), bool)]).astype(jnp.int32)[None]
        o = flash_attention(eq[None], ek[None], ev[None], causal=True, window=W, segment_ids=seg)[0, :, W:]
        wk = jax.lax.dynamic_update_slice(st.wk, jax.lax.dynamic_slice_in_dim(ek, take, W, axis=1)[None], (i, 0, 0, 0))
        wv = jax.lax.dynamic_update_slice(st.wv, jax.lax.dynamic_slice_in_dim(ev, take, W, axis=1)[None], (i, 0, 0, 0))
        return o.transpose(1, 0, 2), st._replace(wk=wk, wv=wv)

    def full_layer(q, k, v, i, st):
        fk = jax.lax.dynamic_update_slice(st.fk, k.transpose(1, 0, 2)[None].astype(st.fk.dtype), (i, 0, pos0, 0))
        fv = jax.lax.dynamic_update_slice(st.fv, v.transpose(1, 0, 2)[None].astype(st.fv.dtype), (i, 0, pos0, 0))
        mask = jnp.broadcast_to((jnp.arange(max_len)[None, :] <= positions[:, None])[None], (Hkv, t, max_len))
        qh = q.reshape(t, Hkv, H // Hkv, dh).transpose(1, 2, 0, 3)
        o = masked_prefill_attention(qh, fk[i], fv[i], mask.astype(jnp.int8), jnp.minimum(pos0 + t, max_len))
        return o.transpose(2, 0, 1, 3).reshape(t, H, dh), st._replace(fk=fk, fv=fv)

    x = jnp.take(params["embed"], tokens, axis=0)
    kind_index = cfg.kind_index()
    for l, lp in enumerate(params["dense"]):
        attend = functools.partial(window_layer if cfg.windows[l] else full_layer, i=kind_index[l], st=st)
        x, st, _ = _layer(x, lp, None, None, attend, cfg)
    xs, banks = _routed_xs(params, cfg)

    def body(carry, inputs):
        x, st = carry
        lp, is_window, i, li = inputs
        x, st, _ = _layer(x, lp, banks, li, lambda q, k, v: _by_kind(cfg, is_window, window_layer, full_layer, q, k, v, i, st), cfg)
        return (x, st), None

    (x, st), _ = jax.lax.scan(body, (x, st), xs)
    return x, st._replace(length=pos0 + take)


def hidden_states(params, tokens, cfg: ExaoneMoeConfig):
    """tokens [T] -> the trunk after the last layer [T, D] (before the final norm)."""
    t = tokens.shape[0]
    return _chunk(params, tokens, _init_staging(cfg, t), jnp.int32(t), cfg)[0]


def forward(params, tokens, cfg: ExaoneMoeConfig, mesh=None):
    """tokens [B, T] -> logits [B, T, V] float32 (one device)."""
    return jax.lax.map(lambda row: _finish(hidden_states(params, row, cfg), params, cfg), tokens)


def mtp_logits(params, hidden, tokens, cfg: ExaoneMoeConfig):
    """The prediction modules over one sequence: hidden [T, D] (the trunk's rows,
    `hidden_states`), tokens [T] -> logits [mtp_layers, T - 1, V] float32; row i
    of module m predicts token i + 2 + m. Module m takes the rows of the module
    before it (the trunk's for m = 0) with the embedding of the token one
    further on: u_i = W_p [norm(x_i) ; norm(embed(t_{i+1+m}))], one
    full-attention routed layer over u, then the trunk's final norm and head.
    Rows past the sequence's end read token 0 and are dropped by the caller
    (module m's last 1 + m rows have no token to read)."""
    from tony_tpu.ops.sparse_attention import masked_prefill_attention

    t, mtp = tokens.shape[0], params["mtp"]
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((t, t), jnp.int8))[None], (Hkv, t, t))
    banks = tuple(mtp["layers"][k] for k in BANKS)

    def attend(q, k, v):
        qh = q.reshape(t, Hkv, H // Hkv, dh).transpose(1, 2, 0, 3)
        o = masked_prefill_attention(qh, k.transpose(1, 0, 2), v.transpose(1, 0, 2), mask, jnp.int32(t))
        return o.transpose(2, 0, 1, 3).reshape(t, H, dh), None

    x, out = hidden, []
    for m in range(cfg.mtp_layers):
        nxt = jnp.take(params["embed"], jnp.roll(tokens, -(1 + m)), axis=0)
        u = _mm(jnp.concatenate([L.rms_norm(x, mtp["hidden_norm"][m], cfg.norm_eps),
                                 L.rms_norm(nxt, mtp["embed_norm"][m], cfg.norm_eps)], axis=-1), mtp["proj"][m])
        lp = {k: v[m] for k, v in mtp["layers"].items() if k not in BANKS}
        x, _, _ = _layer(u, lp, banks, jnp.int32(m), attend, cfg)
        out.append(_finish(x, params, cfg)[:t - 1])
    return jnp.stack(out)


# -- serving: what models/serving.ContinuousBatcher asks a model module for -------------------------

class ExaoneCache(NamedTuple):
    """The engine's device state for S slots: a page pool over the FULL layers
    only, and a ring a slot for each window layer (models/paged_cache.py)."""

    k: jax.Array            # [Lf, P, Hkv, page_len, dh]
    v: jax.Array
    lengths: jax.Array      # [S]
    page_table: jax.Array   # [S, max_pages]
    wk: jax.Array           # [Lw, S, Hkv, window + RING_SLACK, dh]
    wv: jax.Array
    ring_table: jax.Array   # [S, ring pages]: every logical page of slot s is page s of wk / wv (the chunk's write)


def _init_cache(cfg: ExaoneMoeConfig, num_slots: int, max_len: int, page_len: int, num_pages: int) -> ExaoneCache:
    from tony_tpu.models import paged_cache as pc

    if max_len % page_len:
        raise ValueError(f"max_len {max_len} must be a multiple of page_len {page_len}")
    pool = (cfg.count(FULL), num_pages, cfg.n_kv_heads, page_len, cfg.head_dim)
    wk, wv = pc.init_window_rings(cfg.count(WINDOW), num_slots, cfg.n_kv_heads, cfg.window, cfg.head_dim, cfg.jdtype)
    return ExaoneCache(jnp.zeros(pool, cfg.jdtype), jnp.zeros(pool, cfg.jdtype), jnp.zeros((num_slots,), jnp.int32),
                       jnp.zeros((num_slots, max_len // page_len), jnp.int32), wk, wv,
                       pc.ring_table(num_slots, max_len, wk.shape[3]))


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(2,))
def prefill_chunk(params, tokens, staging: Staging, take, cfg: ExaoneMoeConfig):
    """tokens [1, T] at positions staging.length .. + T, of which the first
    `take` are the prompt's. Returns (logits of row take-1 [1, V], staging')."""
    x, staging = _chunk(params, tokens[0], staging, take, cfg)
    return _finish(jax.lax.dynamic_slice_in_dim(x, take - 1, 1, axis=0), params, cfg), staging


@functools.partial(jax.jit, donate_argnums=(0,))
def insert_prefill(cache: ExaoneCache, staging: Staging, fresh_pages, pt_row, slot, true_len, j0, n):
    """Admission: the full layers' staged keys and values into the slot's fresh
    pages (the dense family's insert), the window layers' last positions into
    the slot's rings."""
    from tony_tpu.models import paged_cache as pc

    paged = pc.insert_paged_prefill(pc.PagedCache(cache.k, cache.v, cache.lengths, cache.page_table),
                                    staging.fk[:, None], staging.fv[:, None], fresh_pages, pt_row, slot, true_len, j0, n=n)
    wk, wv = pc.insert_window_rings(cache.wk, cache.wv, staging.wk, staging.wv, slot, true_len)
    return ExaoneCache(paged.k, paged.v, paged.lengths, paged.page_table, wk, wv, cache.ring_table)


def _decode_one(params, cache: ExaoneCache, tokens, cfg: ExaoneMoeConfig, staged):
    """One token a slot, pool and rings read-only: (logits [S, V], lengths',
    this step's keys and values [L, S, Hkv, dh] x 2, the routed layers' held
    rows [Lr, count])."""
    from tony_tpu.ops.decode_attention import paged_decode_attention, ring_decode_attention

    sk, sv, step = staged                                     # [L, S, n, Hkv, dh] x 2: the chunk's earlier steps
    S = tokens.shape[0]
    max_len = cache.page_table.shape[1] * cache.k.shape[3]
    pos = jnp.minimum(cache.lengths, max_len - 1)
    cos, sin = L.rope_frequencies(cfg.head_dim, max(max_len, cfg.max_seq), cfg.rope_theta)
    count = jnp.broadcast_to(step, (S,))
    live = cache.lengths > 0

    def window_layer(q, k, v, i, sk_l, sv_l):
        q, k = _rope(q, cos, sin, pos), _rope(k, cos, sin, pos)
        k1, v1 = k.astype(cache.wk.dtype), v.astype(cache.wv.dtype)
        o = ring_decode_attention(q, cache.wk, cache.wv, pos, i, cur_k=k1, cur_v=v1, window=cfg.window,
                                  staged_k=sk_l, staged_v=sv_l, staged_count=count)
        return o, (k1, v1)

    def full_layer(q, k, v, i, sk_l, sv_l):
        k1, v1 = k.astype(cache.k.dtype), v.astype(cache.v.dtype)
        o = paged_decode_attention(q, cache.k, cache.v, pos, cache.page_table, i, cur_k=k1, cur_v=v1,
                                   staged_k=sk_l, staged_v=sv_l, staged_count=count)
        return o, (k1, v1)

    x = jnp.take(params["embed"], tokens, axis=0)
    kind_index, nd = cfg.kind_index(), cfg.dense_layers
    first = []
    for l, lp in enumerate(params["dense"]):
        attend = functools.partial(window_layer if cfg.windows[l] else full_layer, i=jnp.int32(kind_index[l]), sk_l=sk[l], sv_l=sv[l])
        x, kv, _ = _layer(x, lp, None, None, attend, cfg)
        first.append(kv)
    xs, banks = _routed_xs(params, cfg)

    def body(x, inputs):
        (lp, is_window, i, li), sk_l, sv_l = inputs
        attend = lambda q, k, v: _by_kind(cfg, is_window, window_layer, full_layer, q, k, v, i, sk_l, sv_l)
        x, kv, rows = _layer(x, lp, banks, li, attend, cfg, live=live, name="moe_swiglu_decode")
        return x, (kv, rows)

    x, ((ks, vs), rows) = jax.lax.scan(body, x, (xs, sk[nd:], sv[nd:]))
    ks = jnp.concatenate([jnp.stack([k for k, _ in first]), ks]) if nd else ks
    vs = jnp.concatenate([jnp.stack([v for _, v in first]), vs]) if nd else vs
    # idle slots (length 0) stay at 0, as in the dense family's step
    lengths = jnp.where(live, jnp.minimum(cache.lengths + 1, max_len), 0)
    return _finish(x, params, cfg), lengths, ks, vs, rows, live


@functools.partial(jax.jit, static_argnames=("cfg", "n", "temperature", "top_k"), donate_argnums=(1,))
def decode_steps(params, cache: ExaoneCache, tokens, key, cfg: ExaoneMoeConfig, n: int, temperature: float = 0.0,
                 top_k: int = 0, samp=None):
    """`n` decode steps in one compiled call: (tokens [S], all tokens [n, S],
    cache', counts [4] int32). The pool and the rings are written once, when
    the chunk is over (the dense family's deferred write). `counts`, summed
    over the chunk's steps and the routed layers, from live slots' tokens
    (parallel/expert.held_step_counts): the rows that landed on a held expert,
    the rows of the fullest held expert, the choices made (rows x top_k), and
    the held experts a row chose."""
    from tony_tpu.models.generate import _sample, sample_logits
    from tony_tpu.models.paged_cache import RING_SLACK, write_decode_chunk

    if n > RING_SLACK:
        raise ValueError(f"a decode chunk of {n} steps: a window layer's ring has room for {RING_SLACK}")
    S = tokens.shape[0]
    stage = jnp.zeros((cfg.n_layers, S, n, cfg.n_kv_heads, cfg.head_dim), cache.k.dtype)

    def body(carry, k_step):
        lengths, toks, sk, sv, i, counts = carry
        logits, lengths, cols_k, cols_v, rows, live = _decode_one(params, cache._replace(lengths=lengths), toks, cfg, (sk, sv, i))
        nxt = sample_logits(logits, k_step, *samp) if samp is not None else _sample(logits, k_step, temperature, top_k)
        sk = jax.lax.dynamic_update_slice(sk, cols_k[:, :, None], (0, 0, i, 0, 0))
        sv = jax.lax.dynamic_update_slice(sv, cols_v[:, :, None], (0, 0, i, 0, 0))
        counts = counts + held_step_counts(rows, live, cfg.top_k)
        return (lengths, nxt, sk, sv, i + 1, counts), nxt

    (lengths, toks, sk, sv, _, counts), seq = jax.lax.scan(
        body, (cache.lengths, tokens, stage, stage, jnp.int32(0), jnp.zeros((4,), jnp.int32)), jax.random.split(key, n))
    is_w = np.array([w > 0 for w in cfg.windows])
    k, v = write_decode_chunk(cache.k, cache.v, sk[~is_w], sv[~is_w], cache.lengths, cache.page_table)
    wk, wv = write_decode_chunk(cache.wk, cache.wv, sk[is_w], sv[is_w], cache.lengths, cache.ring_table)
    return toks, seq, ExaoneCache(k, v, lengths, cache.page_table, wk, wv, cache.ring_table), counts


@functools.partial(jax.jit, donate_argnums=(0,))
def _release(cache: ExaoneCache, mask):
    """Retired slots: length and page-table row to zero. Their rings stay as
    they are: a step reads a ring by position, and a slot's next tenant writes
    every position its steps may read."""
    return cache._replace(lengths=jnp.where(mask, 0, cache.lengths), page_table=jnp.where(mask[:, None], 0, cache.page_table))


def serving_programs(cfg: ExaoneMoeConfig, kv: str):
    from tony_tpu.models.serving import ServingPrograms, _bucket

    if kv != "paged":
        raise ValueError("this model is served from the page pool only (kv='paged'): a cache for each kind of layer")
    n_window, n_full = cfg.count(WINDOW), cfg.count(FULL)

    def prefill(params, tokens, staging, take):
        return prefill_chunk(params, tokens, staging, jnp.int32(take), cfg)

    return ServingPrograms(
        init_cache=functools.partial(_init_cache, cfg),
        init_staging=functools.partial(_init_staging, cfg),
        prefill_chunk=prefill,
        # a last chunk is padded to a power of two (a compiled program a bucket), never past the chunk or the room
        prefill_pad=lambda take, chunk, room: min(_bucket(take), chunk or room, room) - take,
        insert=insert_prefill,
        decode_chunk=functools.partial(decode_steps, cfg=cfg),
        release=_release,
        # no gather_prefix: a shared page is not all a prefix leaves behind (the window layers' last positions
        # at the page's edge are the rest, and nothing keeps them). No page is shared.
        visible_tokens=lambda n: (n_window * np.minimum(n, cfg.window) + n_full * n) / cfg.n_layers,
        prefill_path=lambda pos, take: "dense",
        routed_ffn_form=lambda rows: held_ffn_form(cfg.moe, rows, cfg.d_model, cfg.d_expert, cfg.jdtype),
    )
