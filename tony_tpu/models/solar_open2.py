"""The solar_open2 family (Solar Open 2): a decoder whose layers take turns between
two mixers, three recurrent ones to an attention one, with a routed FFN in EVERY
layer.

``layer_types`` gives each layer its mixer:

- ``kda`` — Kimi Delta Attention (ops/kda.py): a delta rule whose state forgets by a
  gate a CHANNEL. One projection to q, k and v (``kda_heads`` heads of
  ``kda_head_dim`` each); a causal depthwise convolution of ``conv_taps`` inputs over
  time on every channel of the three, then SiLU; q and k L2-normed a head, q times
  ``kda_head_dim ** -0.5``; a log-decay a head AND channel ``g = -exp(A_log[h])
  softplus(x W_fa W_fb + dt_bias)`` through a projection of rank ``gate_rank``; a
  write strength ``beta = 2 sigmoid(x W_b)`` in (0, 2) a head; the rule over a float32
  state ``[kda_head_dim, kda_head_dim]`` a head; RMSNorm on each head's output (one
  weight shared by the heads) times ``sigmoid(x W_ga W_gb)``, the output gate, low
  rank too; ``W_o``.
- ``attention`` — grouped-query softmax attention with NO rotary embedding (the
  recurrent layers carry position), no q/k norm, no bias, scores over
  ``sqrt(head_dim)``, and an output gate: ``(attn * sigmoid(x W_gate)) W_o``,
  elementwise from the layer's input.

Every layer is pre-norm: ``h = x + Mixer(Norm(x))``, ``y = h + Routed(n) + Shared(n)``,
``n = Norm(h)``. The routed FFN scores every expert by a sigmoid of a float32
router, chooses the ``top_k`` largest of score + bias (the bias chooses and does
not weigh), weighs the chosen by their scores over the scores' sum times
``routed_scale``, each expert a SwiGLU of ``d_expert``; the shared expert a SwiGLU of
``d_shared``. The replica holds ``held`` of the experts
(parallel/expert.held_expert_ffn): what the absent experts would add is left out,
and no code stands in for them. A final RMSNorm and an untied head. Each of these
choices is one function here and one in the benchmark's reference
(benchmark/families/solar_open2_reference.py; the configuration's ``assumed``).

``params["layers"]`` is a list with one dict of leaves a layer, in order, shaped by
the layer's mixer (two shapes: unrolled, not scanned); the held experts' banks are
every layer's, STACKED (``we_gate``, ``we_up``, ``we_down``: the grouped kernel takes
the stack and a layer index). ``forward`` is the whole-sequence program;
``serving_programs`` is what the serving engine asks for (models/serving.py): the
llama family's paged K/V pool over the ATTENTION layers only (one layer in four),
beside it the recurrent layers' state (a buffer a layer, updated in place) and
convolution tail a slot. No prefix reuse: a page is not all a prefix leaves
behind, and this family keeps no snapshot of its state (12.6 MB a slot at the
published sizes of one period). Served only: no train step (``kda_chunk`` has no
backward) and no sharding rules; one chip's share of the chips that share a layer.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from tony_tpu.ops import layers as L
from tony_tpu.ops.delta_rule import short_conv_chunk, short_conv_step
from tony_tpu.ops.kda import kda_chunk, kda_step
from tony_tpu.parallel.expert import MoEConfig, held_expert_ffn, held_ffn_form, held_step_counts

KDA, ATTENTION = "kda", "attention"
BANKS = ("we_gate", "we_up", "we_down")


@dataclass(frozen=True)
class SolarOpen2Config:
    vocab_size: int = 196_608
    d_model: int = 4096
    layer_types: tuple = (ATTENTION, KDA, KDA, KDA) * 12
    n_heads: int = 64                 # attention layers: query heads
    n_kv_heads: int = 8
    head_dim: int = 128
    kda_heads: int = 64               # recurrent layers: heads (queries, keys and values alike)
    kda_head_dim: int = 128
    conv_taps: int = 4
    gate_rank: int = 128              # the two gates' projections W_a W_b pass through this many channels
    d_expert: int = 1280
    num_experts: int = 320
    held: tuple = (0, 320)            # (first, count) of the experts this replica holds
    top_k: int = 8
    routed_scale: float = 1.0
    d_shared: int = 1280
    max_seq: int = 1_048_576
    norm_eps: float = 1e-5
    page_len: int = 256               # serving: a prompt's last chunk is padded to a page times a power of two
    dtype: str = "bfloat16"

    def __post_init__(self):
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"held {self.held} is not a range of the {self.num_experts} experts")
        if set(self.layer_types) - {KDA, ATTENTION}:
            raise ValueError(f"layer types {sorted(set(self.layer_types))}: each is {KDA!r} or {ATTENTION!r}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def kda_width(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def conv_channels(self) -> int:
        """What the convolution runs over: q, k and v of every head, side by side."""
        return 3 * self.kda_width

    @property
    def moe(self) -> MoEConfig:
        return MoEConfig(num_experts=self.num_experts, top_k=self.top_k, scoring="sigmoid", routed_scale=self.routed_scale,
                         held=self.held)

    def count(self, kind: str) -> int:
        return sum(1 for m in self.layer_types if m == kind)


SOLAR_OPEN2_TINY = SolarOpen2Config(
    vocab_size=256, d_model=64, layer_types=(ATTENTION, KDA, KDA, KDA) * 2, n_heads=4, n_kv_heads=2, head_dim=16,
    kda_heads=4, kda_head_dim=16, gate_rank=8, d_expert=32, num_experts=8, held=(0, 4), top_k=3, d_shared=32,
    max_seq=256, page_len=16, dtype="float32",
)

PRESETS = {"solar-open2-tiny": SOLAR_OPEN2_TINY}

#: of the seeded weights: the choosing bias's scale beside sigmoid scores (a trained bias evens the load; 0.1 beside a
#: unit-scale router made the fullest held expert 3-4 times the mean: ROADMAP R-B11)
ROUTER_BIAS_SCALE = 0.01


def init(key: jax.Array, cfg: SolarOpen2Config) -> dict:
    """The parameter tree (truncated normal, fan-in scaled; norms at one; the router
    float32, its bias ``ROUTER_BIAS_SCALE`` x a normal; the convolution's taps fan-in
    scaled; ``A_log = log U(1, 16)`` a head and ``dt_bias`` a head and channel the
    inverse softplus of ``exp U(log 0.001, log 1)``, float32: ``exp(g)`` spans 1e-7 to
    0.999 a position before the token's own term). ``layers`` is a list with one dict
    of leaves a layer, shaped by its mixer; the held experts' banks are stacked over
    the layers and drawn a layer at a time."""
    D, V, dt, n = cfg.d_model, cfg.vocab_size, cfg.jdtype, cfg.n_layers
    Fe, Fs, held, E = cfg.d_expert, cfg.d_shared, cfg.held[1], cfg.num_experts
    ks = iter(jax.random.split(key, 8 + 16 * n))

    def draw(k, shape, fan_in, dtype=dt, scale=1.0):
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32) * (scale * fan_in ** -0.5)).astype(dtype)

    def dense(*shape, fan_in, dtype=dt, scale=1.0):
        return draw(next(ks), shape, fan_in, dtype, scale)

    def stack(*shape, fan_in):
        return jax.lax.map(lambda k: draw(k, shape, fan_in), jax.random.split(next(ks), n))

    def layer(kind):
        lp = {"mixer_norm": jnp.ones((D,), dt), "ffn_norm": jnp.ones((D,), dt), "router": dense(D, E, fan_in=D, dtype=jnp.float32),
              "router_bias": dense(E, fan_in=1.0, dtype=jnp.float32, scale=ROUTER_BIAS_SCALE),
              "ws_gate": dense(D, Fs, fan_in=D), "ws_up": dense(D, Fs, fan_in=D), "ws_down": dense(Fs, D, fan_in=Fs)}
        if kind == ATTENTION:
            q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
            return {**lp, "w_qkv": dense(D, q + 2 * kv, fan_in=D), "w_gate": dense(D, q, fan_in=D), "wo": dense(q, D, fan_in=q)}
        H, W, C, r = cfg.kda_heads, cfg.kda_width, cfg.conv_channels, cfg.gate_rank
        step = jnp.exp(jax.random.uniform(next(ks), (W,), jnp.float32, np.log(0.001), 0.0))
        return {**lp, "w_qkv": dense(D, C, fan_in=D), "conv": dense(cfg.conv_taps, C, fan_in=cfg.conv_taps),
                "w_fa": dense(D, r, fan_in=D), "w_fb": dense(r, W, fan_in=r), "w_b": dense(D, H, fan_in=D),
                "w_ga": dense(D, r, fan_in=D), "w_gb": dense(r, W, fan_in=r),
                "A_log": jnp.log(jax.random.uniform(next(ks), (H,), jnp.float32, 1.0, 16.0)),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)), "o_norm": jnp.ones((cfg.kda_head_dim,), dt),
                "wo": dense(W, D, fan_in=W)}

    return {"embed": dense(V, D, fan_in=1.0), "layers": [layer(kind) for kind, _ in _layers(cfg)],
            "we_gate": stack(held, D, Fe, fan_in=D), "we_up": stack(held, D, Fe, fan_in=D), "we_down": stack(held, Fe, D, fan_in=Fe),
            "final_norm": jnp.ones((D,), dt), "lm_head": dense(D, V, fan_in=D)}


# -- the layers, over [T, D] rows (a sequence's positions, or the slots' tokens) --------------------

def _layers(cfg: SolarOpen2Config):
    """(kind, index among the layers of its kind) of every layer, in order: the
    index is the layer's place in the cache of its kind (pages, or state)."""
    seen = {KDA: 0, ATTENTION: 0}
    for kind in cfg.layer_types:
        yield kind, seen[kind]
        seen[kind] += 1


def _mm(x, w):
    return jnp.einsum("...d,dh->...h", x, w)


def _mm32(x, w):
    return jnp.einsum("...d,dh->...h", x, w, preferred_element_type=jnp.float32)


def _ffn(x, lp, banks, li, cfg, live, name):
    """x [T, D] -> (x + (Routed + Shared)(Norm(x)), rows [count]: each held
    expert's rows from the tokens `live` marks)."""
    n = L.rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    y, rows = held_expert_ffn(n, lp["router"], lp["router_bias"], *banks, li, cfg.moe, count_mask=live, name=name)
    return x + y + L.swiglu(n, lp["ws_gate"], lp["ws_up"], lp["ws_down"]), rows


def _qkv(u, lp, cfg):
    """u [T, D] -> q [T, H, dh], k, v [T, Hkv, dh]; no rotary embedding, no norm."""
    t, q_w, kv_w = u.shape[0], cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    qkv = _mm(u, lp["w_qkv"])
    return (qkv[:, :q_w].reshape(t, cfg.n_heads, cfg.head_dim), qkv[:, q_w:q_w + kv_w].reshape(t, cfg.n_kv_heads, cfg.head_dim),
            qkv[:, q_w + kv_w:].reshape(t, cfg.n_kv_heads, cfg.head_dim))


def _attention_out(o, u, lp):
    """o [T, H x dh] -> the output gate, elementwise from the layer's input, then W_o."""
    gate = jax.nn.sigmoid(_mm32(u, lp["w_gate"]))
    return _mm((o.astype(jnp.float32) * gate).astype(u.dtype), lp["wo"])


def _l2(a, eps):
    af = a.astype(jnp.float32)
    return af * jax.lax.rsqrt(jnp.sum(af * af, axis=-1, keepdims=True) + eps)


def _low_rank(u, wa, wb):
    """The gates' projection: u W_a in the activations' type, then W_b with a float32 result."""
    return _mm32(_mm(u, wa), wb)


def _rule_inputs(y, u, lp, cfg):
    """The convolution's output y [T, 3 W] and the layer's normed input u [T, D] -> q,
    k, v [T, H, dk] in the activations' type, g [T, H, dk] and beta [T, H] float32."""
    t, H, dk, W = y.shape[0], cfg.kda_heads, cfg.kda_head_dim, cfg.kda_width
    q = (_l2(y[:, :W].reshape(t, H, dk), cfg.norm_eps) * dk ** -0.5).astype(y.dtype)
    k = _l2(y[:, W:2 * W].reshape(t, H, dk), cfg.norm_eps).astype(y.dtype)
    rate = jax.nn.softplus(_low_rank(u, lp["w_fa"], lp["w_fb"]) + lp["dt_bias"].astype(jnp.float32)).reshape(t, H, dk)
    g = -jnp.exp(lp["A_log"].astype(jnp.float32))[None, :, None] * rate
    return q, k, y[:, 2 * W:].reshape(t, H, dk), g, 2.0 * jax.nn.sigmoid(_mm32(u, lp["w_b"]))


def _rule_out(o, u, lp, cfg):
    """o [T, H, dv] -> RMSNorm a head x sigmoid(u W_ga W_gb), then W_o."""
    gate = jax.nn.sigmoid(_low_rank(u, lp["w_ga"], lp["w_gb"])).reshape(o.shape)
    normed = L.rms_norm(o.astype(jnp.float32), lp["o_norm"].astype(jnp.float32), cfg.norm_eps)
    return _mm((normed * gate).astype(u.dtype).reshape(o.shape[0], -1), lp["wo"])


def _finish(x, params, cfg):
    """Rows of the trunk -> float32 logits over the held rows of the vocabulary."""
    return _mm32(L.rms_norm(x, params["final_norm"], cfg.norm_eps), params["lm_head"])


# -- a chunk of one sequence: prefill, and the whole-sequence forward --------------------------------

class Staging(NamedTuple):
    """A request mid-prefill: its attention layers' keys and values at their true
    positions, its recurrent layers' state and convolution tail after `length`
    positions."""

    k: jax.Array           # [La, 1, Hkv, max_len, dh]
    v: jax.Array
    state: jax.Array       # [Lk, H, dk, dv] float32
    tail: jax.Array        # [Lk, taps - 1, C]
    length: jax.Array      # [] int32


def _init_staging(cfg: SolarOpen2Config, max_len: int) -> Staging:
    kv = (cfg.count(ATTENTION), 1, cfg.n_kv_heads, max_len, cfg.head_dim)
    return Staging(jnp.zeros(kv, cfg.jdtype), jnp.zeros(kv, cfg.jdtype),
                   jnp.zeros((cfg.count(KDA), cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim), jnp.float32),
                   jnp.zeros((cfg.count(KDA), cfg.conv_taps - 1, cfg.conv_channels), cfg.jdtype), jnp.zeros((), jnp.int32))


def _chunk(params, tokens, st: Staging, take, cfg: SolarOpen2Config):
    """tokens [T] at positions st.length .. + T, the first `take` of them real.
    Returns (the trunk's rows [T, D], the staging with the chunk in it)."""
    from tony_tpu.ops.attention import chunk_prefill_attention

    t = tokens.shape[0]
    pos0 = st.length
    x = jnp.take(params["embed"], tokens, axis=0)
    banks = tuple(params[k] for k in BANKS)
    ks, vs, state, tail = st.k, st.v, st.state, st.tail
    for li, ((kind, i), lp) in enumerate(zip(_layers(cfg), params["layers"], strict=True)):
        u = L.rms_norm(x, lp["mixer_norm"], cfg.norm_eps)
        if kind == ATTENTION:
            q, k, v = _qkv(u, lp, cfg)
            ks = jax.lax.dynamic_update_slice(ks, k.transpose(1, 0, 2)[None, None].astype(ks.dtype), (i, 0, 0, pos0, 0))
            vs = jax.lax.dynamic_update_slice(vs, v.transpose(1, 0, 2)[None, None].astype(vs.dtype), (i, 0, 0, pos0, 0))
            o = chunk_prefill_attention(q.transpose(1, 0, 2), ks, vs, pos0, pos0 + t, jnp.int32(i))
            branch = _attention_out(o.transpose(1, 0, 2).reshape(t, -1), u, lp)
        else:
            y, new_tail = short_conv_chunk(_mm(u, lp["w_qkv"]), tail[i], lp["conv"], take)
            q, k, v, g, beta = _rule_inputs(y, u, lp, cfg)
            o, new = kda_chunk(q.transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2), g.transpose(1, 0, 2), beta.T,
                               state[i], take)
            state, tail = state.at[i].set(new), tail.at[i].set(new_tail.astype(tail.dtype))
            branch = _rule_out(o.transpose(1, 0, 2), u, lp, cfg)
        x, _ = _ffn(x + branch, lp, banks, jnp.int32(li), cfg, None, "moe_swiglu_prefill")
    return x, Staging(ks, vs, state, tail, pos0 + take)


def forward(params, tokens, cfg: SolarOpen2Config, mesh=None):
    """tokens [B, T] -> logits [B, T, V] float32 (one device; T in whole blocks of the rule's and the convolution's)."""
    t = tokens.shape[1]
    return jax.lax.map(lambda row: _finish(_chunk(params, row, _init_staging(cfg, t), jnp.int32(t), cfg)[0], params, cfg), tokens)


# -- serving: what models/serving.ContinuousBatcher asks a model module for -------------------------

class HybridCache(NamedTuple):
    """The engine's device state for S slots: a page pool over the ATTENTION
    layers only, the recurrent layers' state and convolution tail a slot."""

    k: jax.Array           # [La, P, Hkv, page_len, dh]
    v: jax.Array
    lengths: jax.Array     # [S]
    page_table: jax.Array  # [S, max_pages]
    state: tuple           # Lk arrays [S, H, dk, dv] float32: a layer's is a buffer of its own, updated in place
    tail: jax.Array        # [Lk, S, taps - 1, C]


def _init_cache(cfg: SolarOpen2Config, num_slots: int, max_len: int, page_len: int, num_pages: int) -> HybridCache:
    if max_len % page_len:
        raise ValueError(f"max_len {max_len} must be a multiple of page_len {page_len}")
    pool = (cfg.count(ATTENTION), num_pages, cfg.n_kv_heads, page_len, cfg.head_dim)
    return HybridCache(
        k=jnp.zeros(pool, cfg.jdtype), v=jnp.zeros(pool, cfg.jdtype),
        lengths=jnp.zeros((num_slots,), jnp.int32),
        page_table=jnp.zeros((num_slots, max_len // page_len), jnp.int32),
        state=tuple(jnp.zeros((num_slots, cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim), jnp.float32) for _ in range(cfg.count(KDA))),
        tail=jnp.zeros((cfg.count(KDA), num_slots, cfg.conv_taps - 1, cfg.conv_channels), cfg.jdtype),
    )


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(2,))
def prefill_chunk(params, tokens, staging: Staging, take, cfg: SolarOpen2Config):
    """tokens [1, T] at positions staging.length .. + T, of which the first
    `take` are the prompt's. Returns (logits of row take-1 [1, V], staging')."""
    x, staging = _chunk(params, tokens[0], staging, take, cfg)
    return _finish(jax.lax.dynamic_slice_in_dim(x, take - 1, 1, axis=0), params, cfg), staging


@functools.partial(jax.jit, donate_argnums=(0,))
def insert_prefill(cache: HybridCache, staging: Staging, fresh_pages, pt_row, slot, true_len, j0, n):
    """Admission: the staged keys and values into the slot's fresh pages (the
    llama family's insert), the slot's state and tail from the staging."""
    from tony_tpu.models.paged_cache import PagedCache, insert_paged_prefill

    paged = insert_paged_prefill(PagedCache(cache.k, cache.v, cache.lengths, cache.page_table),
                                 staging.k, staging.v, fresh_pages, pt_row, slot, true_len, j0, n=n)
    return HybridCache(
        paged.k, paged.v, paged.lengths, paged.page_table,
        tuple(jax.lax.dynamic_update_slice_in_dim(s, staging.state[i][None], slot, axis=0) for i, s in enumerate(cache.state)),
        jax.lax.dynamic_update_slice_in_dim(cache.tail, staging.tail[:, None], slot, axis=1),
    )


def _decode_one(params, cache: HybridCache, tokens, cfg: SolarOpen2Config, staged):
    """One token a slot, the pool read-only: (logits [S, V], lengths', state',
    tail', this step's keys and values [La, S, Hkv, dh] x 2, the layers' held rows [L, count])."""
    from tony_tpu.ops.decode_attention import paged_decode_attention

    sk, sv, step = staged
    S = tokens.shape[0]
    max_len = cache.page_table.shape[1] * cache.k.shape[3]
    pos = jnp.minimum(cache.lengths, max_len - 1)
    live = cache.lengths > 0
    x = jnp.take(params["embed"], tokens, axis=0)
    banks = tuple(params[k] for k in BANKS)
    state, tail = list(cache.state), cache.tail
    new_k, new_v, rows = [], [], []
    for li, ((kind, i), lp) in enumerate(zip(_layers(cfg), params["layers"], strict=True)):
        u = L.rms_norm(x, lp["mixer_norm"], cfg.norm_eps)
        if kind == ATTENTION:
            q, k, v = _qkv(u, lp, cfg)
            k1, v1 = k.astype(cache.k.dtype), v.astype(cache.v.dtype)
            o = paged_decode_attention(q, cache.k, cache.v, pos, cache.page_table, jnp.int32(i), cur_k=k1, cur_v=v1,
                                       staged_k=sk[i], staged_v=sv[i], staged_count=jnp.broadcast_to(step, (S,)))
            new_k.append(k1)
            new_v.append(v1)
            branch = _attention_out(o.reshape(S, -1), u, lp)
        else:
            y, new_tail = short_conv_step(_mm(u, lp["w_qkv"]), tail[i], lp["conv"])
            q, k, v, g, beta = _rule_inputs(y, u, lp, cfg)
            o, state[i] = kda_step(q, k, v, g, beta, state[i])
            tail = tail.at[i].set(new_tail)
            branch = _rule_out(o, u, lp, cfg)
        x, held_rows = _ffn(x + branch, lp, banks, jnp.int32(li), cfg, live, "moe_swiglu_decode")
        rows.append(held_rows)
    # idle slots (length 0) stay at 0, as in the dense family's step
    lengths = jnp.where(live, jnp.minimum(cache.lengths + 1, max_len), 0)
    return _finish(x, params, cfg), lengths, tuple(state), tail, jnp.stack(new_k), jnp.stack(new_v), jnp.stack(rows)


@functools.partial(jax.jit, static_argnames=("cfg", "n", "temperature", "top_k"), donate_argnums=(1,))
def decode_steps(params, cache: HybridCache, tokens, key, cfg: SolarOpen2Config, n: int, temperature: float = 0.0,
                 top_k: int = 0, samp=None):
    """`n` decode steps in one compiled call: (tokens [S], all tokens [n, S],
    cache', counts [4] int32). The page pool is written once, when the chunk is
    over (the dense family's deferred write); the recurrent layers' state and
    tail are carried from step to step, a layer's state updated in place.
    `counts` as models/exaone_moe.decode_steps: rows that landed on a held
    expert, the fullest held expert's rows, the choices made, the held experts a
    row chose, summed over the chunk's steps and the layers."""
    from tony_tpu.models.generate import _sample, sample_logits
    from tony_tpu.models.paged_cache import write_decode_chunk

    na, S = cache.k.shape[0], tokens.shape[0]
    stage = jnp.zeros((na, S, n, cfg.n_kv_heads, cfg.head_dim), cache.k.dtype)
    live = cache.lengths > 0

    def body(carry, k_step):
        lengths, toks, state, tail, sk, sv, i, counts = carry
        view = cache._replace(lengths=lengths, state=state, tail=tail)
        logits, lengths, state, tail, cols_k, cols_v, rows = _decode_one(params, view, toks, cfg, (sk, sv, i))
        nxt = sample_logits(logits, k_step, *samp) if samp is not None else _sample(logits, k_step, temperature, top_k)
        sk = jax.lax.dynamic_update_slice(sk, cols_k[:, :, None], (0, 0, i, 0, 0))
        sv = jax.lax.dynamic_update_slice(sv, cols_v[:, :, None], (0, 0, i, 0, 0))
        return (lengths, nxt, state, tail, sk, sv, i + 1, counts + held_step_counts(rows, live, cfg.top_k)), nxt

    (lengths, toks, state, tail, sk, sv, _, counts), seq = jax.lax.scan(
        body, (cache.lengths, tokens, cache.state, cache.tail, stage, stage, jnp.int32(0), jnp.zeros((4,), jnp.int32)),
        jax.random.split(key, n))
    k, v = write_decode_chunk(cache.k, cache.v, sk, sv, cache.lengths, cache.page_table)
    return toks, seq, cache._replace(k=k, v=v, lengths=lengths, state=state, tail=tail), counts


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def decode_logits(params, cache: HybridCache, tokens, cfg: SolarOpen2Config):
    """A chunk of one step that hands back what it computed: (logits [S, V],
    cache' with the step's keys and values in the pool)."""
    from tony_tpu.models.paged_cache import write_decode_chunk

    stage = jnp.zeros((cache.k.shape[0], tokens.shape[0], 1, cfg.n_kv_heads, cfg.head_dim), cache.k.dtype)
    logits, lengths, state, tail, cols_k, cols_v, _ = _decode_one(params, cache, tokens, cfg, (stage, stage, jnp.int32(0)))
    k, v = write_decode_chunk(cache.k, cache.v, cols_k[:, :, None], cols_v[:, :, None], cache.lengths, cache.page_table)
    return logits, cache._replace(k=k, v=v, lengths=lengths, state=state, tail=tail)


@functools.partial(jax.jit, donate_argnums=(0,))
def _release(cache: HybridCache, mask):
    """Retired slots: length and page-table row to zero. Their state and tail stay
    as they are: the next admission overwrites all of a slot's."""
    return cache._replace(lengths=jnp.where(mask, 0, cache.lengths), page_table=jnp.where(mask[:, None], 0, cache.page_table))


def serving_programs(cfg: SolarOpen2Config, kv: str):
    from tony_tpu.models.serving import ServingPrograms, _bucket

    if kv != "paged":
        raise ValueError("this model is served from the page pool only (kv='paged'): its decode reads by page")
    page = cfg.page_len

    def prefill(params, tokens, staging, take):
        return prefill_chunk(params, tokens, staging, jnp.int32(take), cfg)

    def pad(take, chunk, room):
        # a last chunk is padded to a page times a power of two (a compiled program a bucket, in whole blocks of
        # the rule's and tiles of the convolution's), never past the chunk or the room
        if chunk % page:
            raise ValueError(f"prefill_chunk {chunk}: this model's chunks are whole pages of {page}")
        return min(max(_bucket(take), page), chunk or room, room) - take

    return ServingPrograms(
        init_cache=functools.partial(_init_cache, cfg),
        init_staging=functools.partial(_init_staging, cfg),
        prefill_chunk=prefill,
        prefill_pad=pad,
        insert=insert_prefill,
        decode_chunk=functools.partial(decode_steps, cfg=cfg),
        release=_release,
        visible_tokens=lambda n: n,            # the attention layer reads the whole context
        prefill_path=lambda pos, take: "dense",
        routed_ffn_form=lambda rows: held_ffn_form(cfg.moe, rows, cfg.d_model, cfg.d_expert, cfg.jdtype),
    )
