"""The olmo_hybrid family (Olmo-Hybrid): a decoder whose layers take turns between
two mixers, three recurrent ones to a full one.

``layer_types`` gives each layer its kind:

- ``linear_attention`` — a GATED DELTA-RULE layer (ops/delta_rule.py). One
  projection to q, k (``lin_heads`` heads of ``lin_key_dim``) and v (heads of
  ``lin_value_dim``); a causal depthwise convolution of ``conv_taps`` inputs over
  time on every channel of the three, then SiLU; q and k L2-normed a head, q
  times ``lin_key_dim ** -0.5``; a write strength ``beta = 2 sigmoid(b)`` and a
  log-decay ``g = -exp(A_log) softplus(a + dt_bias)`` a head and token; the rule
  over a float32 state ``[lin_key_dim, lin_value_dim]`` a head; RMSNorm on each
  head's output (one weight shared by the heads) times ``silu(W_g x)``; ``W_o``.
- ``full_attention`` — multi-head softmax attention, RMSNorm over the WHOLE
  projection of q and of k before the heads are split, NO rotary embedding (the
  recurrent layers carry position), no bias.

Every layer: ``h = x + Norm_a(Mixer(x))``, ``y = h + Norm_f(FFN(h))``: the norm
sits on each sublayer's OUTPUT; a SwiGLU FFN; a final RMSNorm and an untied head.
Each of these choices is one function here and one in the benchmark's reference
(benchmark/families/olmo_hybrid_reference.py; the configuration's ``assumed``).

``params["layers"]`` is a list with one dict of leaves a layer, in order, shaped
by the layer's kind (two shapes: unrolled, not scanned). ``forward`` is the
whole-sequence program; ``serving_programs`` is what the serving engine asks for
(models/serving.py): the llama family's paged K/V pool over the FULL layers
only, beside it the linear layers' state and convolution tail a slot, and a
store of SNAPSHOTS of both at the edges of prompt pages, so that a later request
with the same prefix (a session's next turn) starts from its pages AND its
state (models/paged_cache.PageAllocator keeps which page's edge has one). Served
only: no train step (``delta_chunk`` has no backward) and no sharding rules.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from tony_tpu.obs import metrics as obs_metrics
from tony_tpu.ops import layers as L
from tony_tpu.ops.delta_rule import gated_delta_chunk, gated_delta_step, short_conv_chunk, short_conv_step

LINEAR, FULL = "linear_attention", "full_attention"

_PREFILL_PAIRS = obs_metrics.counter(
    "tony_serve_prefill_pairs_total",
    "query-key pairs the causal attention of dispatched prefill chunks sees, a layer: a chunk's rows x the positions "
    "before it, and its own causal half")


@dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100_352
    d_model: int = 3840
    layer_types: tuple = (LINEAR, LINEAR, LINEAR, FULL) * 8
    n_heads: int = 30                 # full layers: query heads
    n_kv_heads: int = 30
    head_dim: int = 128
    lin_heads: int = 30               # linear layers: heads (queries, keys and values alike)
    lin_key_dim: int = 96
    lin_value_dim: int = 192
    conv_taps: int = 4
    d_ff: int = 11_008
    max_seq: int = 65_536
    norm_eps: float = 1e-6
    page_len: int = 256               # serving: a prompt page; a snapshot lies at a page's edge
    snapshots: int = 0                # serving: snapshots the store holds (0: two a slot)
    dtype: str = "bfloat16"

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def conv_channels(self) -> int:
        """What the convolution runs over: q, k and v of every head, side by side."""
        return self.lin_heads * (2 * self.lin_key_dim + self.lin_value_dim)

    def count(self, kind: str) -> int:
        return sum(1 for m in self.layer_types if m == kind)


OLMO_HYBRID_TINY = OlmoHybridConfig(
    vocab_size=256, d_model=64, layer_types=(LINEAR, LINEAR, LINEAR, FULL) * 2, n_heads=4, n_kv_heads=4, head_dim=16,
    lin_heads=4, lin_key_dim=8, lin_value_dim=16, d_ff=128, max_seq=256, page_len=16, dtype="float32",
)

PRESETS = {"olmo-hybrid-tiny": OLMO_HYBRID_TINY}


def init(key: jax.Array, cfg: OlmoHybridConfig) -> dict:
    """The parameter tree (truncated normal, fan-in scaled; norms at one; the
    convolution's taps fan-in scaled; ``A_log = log U(1, 16)`` and ``dt_bias`` the
    inverse softplus of ``exp U(log 0.001, log 0.1)``, both float32): ``layers`` is
    a list with one dict of leaves a layer, in order, shaped by the layer's kind."""
    D, F, V, dt = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.jdtype
    ks = iter(jax.random.split(key, 3 + 12 * cfg.n_layers))

    def dense(*shape, fan_in):
        return (jax.random.truncated_normal(next(ks), -2, 2, shape, jnp.float32) * fan_in ** -0.5).astype(dt)

    def layer(kind):
        lp = {"attn_norm": jnp.ones((D,), dt), "mlp_norm": jnp.ones((D,), dt),
              "w_gate": dense(D, F, fan_in=D), "w_up": dense(D, F, fan_in=D), "w_down": dense(F, D, fan_in=F)}
        if kind == FULL:
            q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
            return {**lp, "w_qkv": dense(D, q + 2 * kv, fan_in=D), "q_norm": jnp.ones((q,), dt), "k_norm": jnp.ones((kv,), dt),
                    "wo": dense(q, D, fan_in=q)}
        H, out = cfg.lin_heads, cfg.lin_heads * cfg.lin_value_dim
        step = jnp.exp(jax.random.uniform(next(ks), (H,), jnp.float32, np.log(0.001), np.log(0.1)))
        return {**lp, "w_qkv": dense(D, cfg.conv_channels, fan_in=D), "conv": dense(cfg.conv_taps, cfg.conv_channels, fan_in=cfg.conv_taps),
                "w_g": dense(D, out, fan_in=D), "w_ab": dense(D, 2 * H, fan_in=D),
                "A_log": jnp.log(jax.random.uniform(next(ks), (H,), jnp.float32, 1.0, 16.0)),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)), "o_norm": jnp.ones((cfg.lin_value_dim,), dt),
                "wo": dense(out, D, fan_in=out)}

    return {"embed": dense(V, D, fan_in=1.0), "layers": [layer(kind) for kind, _ in _layers(cfg)],
            "final_norm": jnp.ones((D,), dt), "lm_head": dense(D, V, fan_in=D)}


# -- the layers, over [T, D] rows (a sequence's positions, or the slots' tokens) --------------------

def _layers(cfg: OlmoHybridConfig):
    """(kind, index among the layers of its kind) of every layer, in order: the
    index is the layer's place in the cache of its kind (pages, or state)."""
    seen = {LINEAR: 0, FULL: 0}
    for kind in cfg.layer_types:
        if kind not in seen:
            raise ValueError(f"layer type {kind!r} is neither {LINEAR!r} nor {FULL!r}")
        yield kind, seen[kind]
        seen[kind] += 1


def _mm(x, w):
    return jnp.einsum("...d,dh->...h", x, w)


def _after(x, branch, weight, cfg):
    """The family's reordered norm: the sublayer's OUTPUT is normed, then added."""
    return x + L.rms_norm(branch, weight, cfg.norm_eps)


def _ffn(h, lp):
    return _mm(jax.nn.silu(_mm(h, lp["w_gate"])) * _mm(h, lp["w_up"]), lp["w_down"])


def _full_qkv(x, lp, cfg):
    """x [T, D] -> q [T, H, dh], k, v [T, Hkv, dh]; q and k normed over their WHOLE width, then split."""
    t, q_w, kv_w = x.shape[0], cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    qkv = _mm(x, lp["w_qkv"])
    q = L.rms_norm(qkv[:, :q_w], lp["q_norm"], cfg.norm_eps).reshape(t, cfg.n_heads, cfg.head_dim)
    k = L.rms_norm(qkv[:, q_w:q_w + kv_w], lp["k_norm"], cfg.norm_eps).reshape(t, cfg.n_kv_heads, cfg.head_dim)
    return q, k, qkv[:, q_w + kv_w:].reshape(t, cfg.n_kv_heads, cfg.head_dim)


def _l2(a, eps):
    af = a.astype(jnp.float32)
    return af * jax.lax.rsqrt(jnp.sum(af * af, axis=-1, keepdims=True) + eps)


def _rule_inputs(y, x, lp, cfg):
    """The convolution's output y [T, C] and the layer's input x [T, D] -> q, k [T,
    H, dk], v [T, H, dv] in the activations' type, g, beta [T, H] float32."""
    t, H, dk, dv = y.shape[0], cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim
    q = (_l2(y[:, :H * dk].reshape(t, H, dk), cfg.norm_eps) * dk ** -0.5).astype(y.dtype)
    k = _l2(y[:, H * dk:2 * H * dk].reshape(t, H, dk), cfg.norm_eps).astype(y.dtype)
    ab = jnp.einsum("td,dh->th", x, lp["w_ab"], preferred_element_type=jnp.float32)
    g = -jnp.exp(lp["A_log"].astype(jnp.float32)) * jax.nn.softplus(ab[:, :H] + lp["dt_bias"].astype(jnp.float32))
    return q, k, y[:, 2 * H * dk:].reshape(t, H, dv), g, 2.0 * jax.nn.sigmoid(ab[:, H:])


def _rule_out(o, x, lp, cfg):
    """o [T, H, dv] -> RMSNorm a head x silu(W_g x), then W_o."""
    t = o.shape[0]
    gate = jax.nn.silu(_mm(x, lp["w_g"]).astype(jnp.float32)).reshape(o.shape)
    normed = L.rms_norm(o.astype(jnp.float32), lp["o_norm"].astype(jnp.float32), cfg.norm_eps)
    return _mm((normed * gate).astype(x.dtype).reshape(t, -1), lp["wo"])


def _finish(x, params, cfg):
    """Rows of the trunk -> float32 logits."""
    return _mm(L.rms_norm(x, params["final_norm"], cfg.norm_eps), params["lm_head"]).astype(jnp.float32)


# -- a chunk of one sequence: prefill, and the whole-sequence forward --------------------------------

class Staging(NamedTuple):
    """A request mid-prefill: its full layers' keys and values at their true
    positions, its linear layers' state and convolution tail after `length`
    positions, and both as they were BEFORE the last chunk program (the state at
    that program's first position: a page's edge, what a snapshot keeps)."""

    k: jax.Array           # [Lf, 1, Hkv, max_len, dh]
    v: jax.Array
    state: jax.Array       # [Ll, H, dk, dv] float32
    tail: jax.Array        # [Ll, taps - 1, C]
    edge_state: jax.Array  # as `state`
    edge_tail: jax.Array   # as `tail`
    length: jax.Array      # [] int32


def _init_staging(cfg: OlmoHybridConfig, max_len: int) -> Staging:
    kv = (cfg.count(FULL), 1, cfg.n_kv_heads, max_len, cfg.head_dim)
    state = jnp.zeros((cfg.count(LINEAR), cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim), jnp.float32)
    tail = jnp.zeros((cfg.count(LINEAR), cfg.conv_taps - 1, cfg.conv_channels), cfg.jdtype)
    return Staging(jnp.zeros(kv, cfg.jdtype), jnp.zeros(kv, cfg.jdtype), state, tail, state, tail, jnp.zeros((), jnp.int32))


def _chunk(params, tokens, st: Staging, take, cfg: OlmoHybridConfig):
    """tokens [T] at positions st.length .. + T, the first `take` of them real.
    Returns (the trunk's rows [T, D], the staging with the chunk in it)."""
    from tony_tpu.ops.attention import chunk_prefill_attention

    t = tokens.shape[0]
    pos0 = st.length
    x = jnp.take(params["embed"], tokens, axis=0)
    ks, vs, state, tail = st.k, st.v, st.state, st.tail
    for (kind, i), lp in zip(_layers(cfg), params["layers"], strict=True):
        if kind == FULL:
            q, k, v = _full_qkv(x, lp, cfg)
            ks = jax.lax.dynamic_update_slice(ks, k.transpose(1, 0, 2)[None, None].astype(ks.dtype), (i, 0, 0, pos0, 0))
            vs = jax.lax.dynamic_update_slice(vs, v.transpose(1, 0, 2)[None, None].astype(vs.dtype), (i, 0, 0, pos0, 0))
            o = chunk_prefill_attention(q.transpose(1, 0, 2), ks, vs, pos0, pos0 + t, jnp.int32(i))
            branch = _mm(o.transpose(1, 0, 2).reshape(t, -1), lp["wo"])
        else:
            y, new_tail = short_conv_chunk(_mm(x, lp["w_qkv"]), tail[i], lp["conv"], take)
            q, k, v, g, beta = _rule_inputs(y, x, lp, cfg)
            o, new = gated_delta_chunk(q.transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2), g.T, beta.T, state[i], take)
            state, tail = state.at[i].set(new), tail.at[i].set(new_tail.astype(tail.dtype))
            branch = _rule_out(o.transpose(1, 0, 2), x, lp, cfg)
        x = _after(x, branch, lp["attn_norm"], cfg)
        x = _after(x, _ffn(x, lp), lp["mlp_norm"], cfg)
    return x, Staging(ks, vs, state, tail, st.state, st.tail, pos0 + take)


def forward(params, tokens, cfg: OlmoHybridConfig, mesh=None):
    """tokens [B, T] -> logits [B, T, V] float32 (one device; T in whole blocks of the rule's and the convolution's)."""
    t = tokens.shape[1]
    return jax.lax.map(lambda row: _finish(_chunk(params, row, _init_staging(cfg, t), jnp.int32(t), cfg)[0], params, cfg), tokens)


# -- serving: what models/serving.ContinuousBatcher asks a model module for -------------------------

class HybridCache(NamedTuple):
    """The engine's device state for S slots. A page pool over the FULL layers
    only, the linear layers' state and convolution tail a slot, and the snapshot
    store: N states and tails, each taken at the edge of some request's last full
    prompt page (which page: the host's PageAllocator)."""

    k: jax.Array           # [Lf, P, Hkv, page_len, dh]
    v: jax.Array
    lengths: jax.Array     # [S]
    page_table: jax.Array  # [S, max_pages]
    state: tuple           # Ll arrays [S, H, dk, dv] float32: a layer's is a buffer of its own, updated in place
    tail: jax.Array        # [Ll, S, taps - 1, C]
    snap_state: jax.Array  # [N, Ll, H, dk, dv] float32
    snap_tail: jax.Array   # [N, Ll, taps - 1, C]


def _init_cache(cfg: OlmoHybridConfig, num_slots: int, max_len: int, page_len: int, num_pages: int) -> HybridCache:
    if page_len != cfg.page_len:
        raise ValueError(f"page_len {page_len}: this configuration's prefill leaves its state at the edges of pages of "
                         f"{cfg.page_len} positions (OlmoHybridConfig.page_len)")
    if max_len % page_len:
        raise ValueError(f"max_len {max_len} must be a multiple of page_len {page_len}")
    nf, nl, n = cfg.count(FULL), cfg.count(LINEAR), cfg.snapshots or 2 * num_slots
    pool = (nf, num_pages, cfg.n_kv_heads, page_len, cfg.head_dim)
    heads = (cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim)
    return HybridCache(
        k=jnp.zeros(pool, cfg.jdtype), v=jnp.zeros(pool, cfg.jdtype),
        lengths=jnp.zeros((num_slots,), jnp.int32),
        page_table=jnp.zeros((num_slots, max_len // page_len), jnp.int32),
        state=tuple(jnp.zeros((num_slots, *heads), jnp.float32) for _ in range(nl)),
        tail=jnp.zeros((nl, num_slots, cfg.conv_taps - 1, cfg.conv_channels), cfg.jdtype),
        snap_state=jnp.zeros((n, nl, *heads), jnp.float32),
        snap_tail=jnp.zeros((n, nl, cfg.conv_taps - 1, cfg.conv_channels), cfg.jdtype),
    )


def _prefill(params, tokens, staging: Staging, take, cfg: OlmoHybridConfig):
    x, staging = _chunk(params, tokens[0], staging, take, cfg)
    return _finish(jax.lax.dynamic_slice_in_dim(x, take - 1, 1, axis=0), params, cfg), staging


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(2,))
def prefill_chunk(params, tokens, staging: Staging, take, cfg: OlmoHybridConfig):
    """tokens [1, T] at positions staging.length .. + T, of which the first
    `take` are the prompt's. Returns (logits of row take-1 [1, V], staging')."""
    return _prefill(params, tokens, staging, take, cfg)


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(2,))
def prefill_page(params, tokens, staging: Staging, take, cfg: OlmoHybridConfig):
    """`prefill_chunk` under the name the rows after a chunk's last page edge run
    by: every prefill chunk of the engine's ends in exactly one of these (a trace
    counts the chunks by it), and a prefix hit up to that edge runs this program
    on the rows a cold request ran it on."""
    return _prefill(params, tokens, staging, take, cfg)


@functools.partial(jax.jit, static_argnames=("rows",))
def _rows_from(tokens, start, rows: int):
    """tokens [1, T] -> [1, rows] from `start` on (one program a T, whatever the start)."""
    return jax.lax.dynamic_slice_in_dim(tokens, start, rows, axis=1)


@functools.partial(jax.jit, donate_argnums=(0,))
def insert_prefill(cache: HybridCache, staging: Staging, fresh_pages, pt_row, slot, true_len, j0, n, where):
    """Admission: the staged keys and values into the slot's fresh pages (the
    llama family's insert), the slot's state and tail from the staging, and, where
    `where` >= 0, the state and tail at the prompt's last page edge into that
    place of the snapshot store."""
    from tony_tpu.models.paged_cache import PagedCache, insert_paged_prefill

    paged = insert_paged_prefill(PagedCache(cache.k, cache.v, cache.lengths, cache.page_table),
                                 staging.k, staging.v, fresh_pages, pt_row, slot, true_len, j0, n=n)
    at = jnp.maximum(where, 0)

    def kept(store, edge):
        old = jax.lax.dynamic_slice_in_dim(store, at, 1, axis=0)
        return jax.lax.dynamic_update_slice_in_dim(store, jnp.where(where >= 0, edge[None].astype(store.dtype), old), at, axis=0)

    return HybridCache(
        paged.k, paged.v, paged.lengths, paged.page_table,
        tuple(jax.lax.dynamic_update_slice_in_dim(s, staging.state[i][None], slot, axis=0) for i, s in enumerate(cache.state)),
        jax.lax.dynamic_update_slice_in_dim(cache.tail, staging.tail[:, None], slot, axis=1),
        kept(cache.snap_state, staging.edge_state), kept(cache.snap_tail, staging.edge_tail),
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def gather_prefix(staging: Staging, cache: HybridCache, pages, n, where):
    """A prefix hit: the matched pages' keys and values into the request's
    staging (`paged_cache.gather_prefix_into_staging` with the count traced: one
    compiled variant), the snapshot at the last matched page's edge into its
    state and tail, its length past them."""
    nf, _, hkv, page_len, dh = cache.k.shape

    def body(j, kv):
        k, v = kv
        at = (0, pages[j], 0, 0, 0)
        return (jax.lax.dynamic_update_slice(k, jax.lax.dynamic_slice(cache.k, at, (nf, 1, hkv, page_len, dh)), (0, 0, 0, j * page_len, 0)),
                jax.lax.dynamic_update_slice(v, jax.lax.dynamic_slice(cache.v, at, (nf, 1, hkv, page_len, dh)), (0, 0, 0, j * page_len, 0)))

    k, v = jax.lax.fori_loop(0, n, body, (staging.k, staging.v))
    state, tail = cache.snap_state[where], cache.snap_tail[where]
    return Staging(k, v, state, tail, state, tail, n * page_len)


def _decode_one(params, cache: HybridCache, tokens, cfg: OlmoHybridConfig, staged):
    """One token a slot, the pool read-only: (logits [S, V], lengths', state',
    tail', this step's keys and values [Lf, S, Hkv, dh] x 2)."""
    from tony_tpu.ops.decode_attention import paged_decode_attention

    sk, sv, step = staged
    S = tokens.shape[0]
    max_len = cache.page_table.shape[1] * cache.k.shape[3]
    pos = jnp.minimum(cache.lengths, max_len - 1)
    x = jnp.take(params["embed"], tokens, axis=0)
    state, tail = list(cache.state), cache.tail
    new_k, new_v = [], []
    for (kind, i), lp in zip(_layers(cfg), params["layers"], strict=True):
        if kind == FULL:
            q, k, v = _full_qkv(x, lp, cfg)
            k1, v1 = k.astype(cache.k.dtype), v.astype(cache.v.dtype)
            o = paged_decode_attention(q, cache.k, cache.v, pos, cache.page_table, jnp.int32(i), cur_k=k1, cur_v=v1,
                                       staged_k=sk[i], staged_v=sv[i], staged_count=jnp.broadcast_to(step, (S,)))
            new_k.append(k1)
            new_v.append(v1)
            branch = _mm(o.reshape(S, -1), lp["wo"])
        else:
            y, new_tail = short_conv_step(_mm(x, lp["w_qkv"]), tail[i], lp["conv"])
            q, k, v, g, beta = _rule_inputs(y, x, lp, cfg)
            o, state[i] = gated_delta_step(q, k, v, g, beta, state[i])
            tail = tail.at[i].set(new_tail)
            branch = _rule_out(o, x, lp, cfg)
        x = _after(x, branch, lp["attn_norm"], cfg)
        x = _after(x, _ffn(x, lp), lp["mlp_norm"], cfg)
    # idle slots (length 0) stay at 0, as in the dense family's step
    lengths = jnp.where(cache.lengths > 0, jnp.minimum(cache.lengths + 1, max_len), 0)
    return _finish(x, params, cfg), lengths, tuple(state), tail, jnp.stack(new_k), jnp.stack(new_v)


@functools.partial(jax.jit, static_argnames=("cfg", "n", "temperature", "top_k"), donate_argnums=(1,))
def decode_steps(params, cache: HybridCache, tokens, key, cfg: OlmoHybridConfig, n: int, temperature: float = 0.0,
                 top_k: int = 0, samp=None):
    """`n` decode steps in one compiled call: (tokens [S], all tokens [n, S],
    cache'). The page pool is written once, when the chunk is over (the dense
    family's deferred write); the linear layers' state and tail are carried from
    step to step, a layer's state updated in place."""
    from tony_tpu.models.generate import _sample, sample_logits
    from tony_tpu.models.paged_cache import write_decode_chunk

    nf, S = cache.k.shape[0], tokens.shape[0]
    stage = jnp.zeros((nf, S, n, cfg.n_kv_heads, cfg.head_dim), cache.k.dtype)

    def body(carry, k_step):
        lengths, toks, state, tail, sk, sv, i = carry
        view = cache._replace(lengths=lengths, state=state, tail=tail)
        logits, lengths, state, tail, cols_k, cols_v = _decode_one(params, view, toks, cfg, (sk, sv, i))
        nxt = sample_logits(logits, k_step, *samp) if samp is not None else _sample(logits, k_step, temperature, top_k)
        sk = jax.lax.dynamic_update_slice(sk, cols_k[:, :, None], (0, 0, i, 0, 0))
        sv = jax.lax.dynamic_update_slice(sv, cols_v[:, :, None], (0, 0, i, 0, 0))
        return (lengths, nxt, state, tail, sk, sv, i + 1), nxt

    (lengths, toks, state, tail, sk, sv, _), seq = jax.lax.scan(
        body, (cache.lengths, tokens, cache.state, cache.tail, stage, stage, jnp.int32(0)), jax.random.split(key, n))
    k, v = write_decode_chunk(cache.k, cache.v, sk, sv, cache.lengths, cache.page_table)
    return toks, seq, cache._replace(k=k, v=v, lengths=lengths, state=state, tail=tail)


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def decode_logits(params, cache: HybridCache, tokens, cfg: OlmoHybridConfig):
    """A chunk of one step that hands back what it computed: (logits [S, V],
    cache' with the step's keys and values in the pool)."""
    from tony_tpu.models.paged_cache import write_decode_chunk

    stage = jnp.zeros((cache.k.shape[0], tokens.shape[0], 1, cfg.n_kv_heads, cfg.head_dim), cache.k.dtype)
    logits, lengths, state, tail, cols_k, cols_v = _decode_one(params, cache, tokens, cfg, (stage, stage, jnp.int32(0)))
    k, v = write_decode_chunk(cache.k, cache.v, cols_k[:, :, None], cols_v[:, :, None], cache.lengths, cache.page_table)
    return logits, cache._replace(k=k, v=v, lengths=lengths, state=state, tail=tail)


@functools.partial(jax.jit, donate_argnums=(0,))
def _release(cache: HybridCache, mask):
    """Retired slots: length and page-table row to zero. Their state and tail stay
    as they are: the next admission overwrites all of a slot's."""
    return cache._replace(lengths=jnp.where(mask, 0, cache.lengths), page_table=jnp.where(mask[:, None], 0, cache.page_table))


def serving_programs(cfg: OlmoHybridConfig, kv: str):
    from tony_tpu.models.paged_cache import STATE_SNAPSHOTS
    from tony_tpu.models.serving import ServingPrograms, _bucket

    if kv != "paged":
        raise ValueError("this model is served from the page pool only (kv='paged'): its decode reads by page")
    page = cfg.page_len
    # the host's page allocator, which also keeps which page's edge has a snapshot and where in the store: the
    # engine hands it over where it asks how much of a match is usable, which it does as it stages a request,
    # before that request's first prefill
    host = {}

    def usable(allocator, matched):
        host["allocator"] = allocator
        return allocator.deepest_state(matched)

    def prefill(params, tokens, staging, take):
        # up to the edge of the chunk's last page first, then the rest as a program of its own: the state at that
        # edge is what a snapshot keeps, and a later request that matches up to it runs THAT program on these rows
        # from a copy of this state, so that a hit's arithmetic is the cold path's
        head = (take - 1) // page * page
        if head:
            _, staging = prefill_chunk(params, tokens, staging, jnp.int32(head), cfg)
            tokens, take = _rows_from(tokens, jnp.int32(head), page), take - head
        return prefill_page(params, tokens, staging, jnp.int32(take), cfg)

    def insert(cache, staging, fresh_pages, pt_row, slot, true_len, j0, n):
        edge = (int(true_len) - 1) // page              # whole pages before the prompt's last row: where its snapshot lies
        where = -1
        if edge and "allocator" in host:
            where = host["allocator"].keep_state(int(np.asarray(pt_row)[edge - 1]), cache.snap_state.shape[0])
        return insert_prefill(cache, staging, fresh_pages, pt_row, slot, true_len, j0, n, jnp.int32(where))

    def gather(staging, cache, pages, n):
        # the table's width whatever was matched: one compiled variant, the count traced
        matched = np.asarray(pages)
        padded = np.zeros(cache.page_table.shape[1], np.int32)
        padded[:n] = matched
        STATE_SNAPSHOTS.inc(event="restored")
        return gather_prefix(staging, cache, padded, jnp.int32(n), jnp.int32(host["allocator"].state_at(int(matched[n - 1]))))

    def pad(take, chunk, room):
        # a last chunk is padded to a page times a power of two (a compiled program a bucket), never past the
        # chunk or the room
        if chunk % page:
            raise ValueError(f"prefill_chunk {chunk}: this model's chunks start and end at edges of its pages of {page}")
        return min(max(_bucket(take), page), chunk or room, room) - take

    def prefill_path(pos, take):
        # the engine calls this once a prefill chunk, from the host's own lengths
        _PREFILL_PAIRS.inc(take * pos + take * (take + 1) // 2)
        return "dense"

    return ServingPrograms(
        init_cache=functools.partial(_init_cache, cfg),
        init_staging=functools.partial(_init_staging, cfg),
        prefill_chunk=prefill,
        prefill_pad=pad,
        insert=insert,
        decode_chunk=functools.partial(decode_steps, cfg=cfg),
        release=_release,
        visible_tokens=lambda n: n,            # the full layers read the whole context
        prefill_path=prefill_path,
        gather_prefix=gather,
        prefix_usable=usable,
    )
