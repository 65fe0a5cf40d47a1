"""The falcon_h1 family (Falcon-H1, arXiv:2507.22448): a decoder in which EVERY layer
runs two mixers side by side on one normed input, a Mamba-2 state-space mixer and
rotary grouped-query attention, adds both to the stream, and then runs a dense
SwiGLU; muP scalars stand on the embedding, on each branch's way in and out, on
the keys, on the segments of the state-space projection, on the FFN's gate and
output and on the logits.

A layer, pre-norm::

    u = Norm(x)
    h = x + attention_out_multiplier Attn(attention_in_multiplier u)
          + ssm_out_multiplier SSM(ssm_in_multiplier u)
    y = h + FFN(Norm(h))

- ``Attn`` — ``n_heads`` query heads over ``n_kv_heads`` key/value heads of
  ``head_dim``; the keys times ``key_multiplier``; rotary embedding over the whole
  head (halves rotated) on queries and keys; scores over ``sqrt(head_dim)``, causal
  softmax; no bias, no q/k norm.
- ``SSM`` — a Mamba-2 mixer (ops/ssd.py). One projection to ``[z | x | B | C |
  dt]`` (the gate, the convolution's channels, a step a head) times the VECTOR that
  holds ``ssm_multipliers[0..4]`` over those five segments; a causal depthwise
  convolution of ``conv_taps`` inputs with a bias, then SiLU, over ``x | B | C``;
  ``x`` as ``ssm_heads`` heads of ``ssm_head_dim``, ``B`` and ``C`` as ``ssm_groups``
  groups of ``ssm_state`` (head ``h`` reads group ``h // (ssm_heads / ssm_groups)``);
  ``dt = softplus(dt + dt_bias)`` and the log-decay ``g = -exp(A_log) dt`` a head, no
  clamp; the recurrence over a float32 state ``[ssm_head_dim, ssm_state]`` a head
  (kept as ``[ssm_state, heads x ssm_head_dim]``: ops/ssd.lanes), plus ``D x``; gate
  THEN norm: ``y silu(z)``, RMSNorm over EACH GROUP's channels times a weight;
  ``W_out``.
- ``FFN`` — ``down_multiplier ((silu(gate_multiplier (n W_gate)) * (n W_up)) W_down)``,
  ``mlp_multipliers = (gate_multiplier, down_multiplier)``.

The stream starts as ``embedding_multiplier embed[token]``; the logits are
``lm_head_multiplier Norm(x) W_head`` (untied). Every scalar is computed where it
is written, none folded into a weight. Each of these choices is one function here
and one in the benchmark's reference (benchmark/families/falcon_h1_reference.py;
the configuration's ``assumed``).

``params["layers"]`` is a list with one dict of leaves a layer (unrolled, not
scanned: a layer's state is a buffer of its own, updated in place). ``forward`` is
the whole-sequence program; ``serving_programs`` is what the serving engine asks
for (models/serving.py). Every layer has BOTH kinds of cache: the llama family's
paged K/V pool spans all layers, and beside it every layer keeps a state-space
state and a convolution tail a slot. No prefix reuse: a page is not all a prefix
leaves behind, and this family keeps no snapshot of its state (37.7 MB a slot at
nine published layers). Served only: no train step (``ssd_chunk`` has no backward)
and no sharding rules; whole layers of one pipeline stage.
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
from tony_tpu.ops.delta_rule import short_conv_chunk, short_conv_step
from tony_tpu.ops.ssd import ssd_chunk, ssd_step

_PREFILL_PAIRS = obs_metrics.counter(
    "tony_serve_prefill_pairs_total",
    "query-key pairs the causal attention of dispatched prefill chunks sees, a layer: a chunk's rows x the positions "
    "before it, and its own causal half")


@dataclass(frozen=True)
class FalconH1Config:
    vocab_size: int = 261_120
    d_model: int = 5120
    n_layers: int = 72
    n_heads: int = 20                 # attention: query heads
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e11
    ssm_heads: int = 32               # the state-space mixer
    ssm_head_dim: int = 128
    ssm_state: int = 256
    ssm_groups: int = 2
    conv_taps: int = 4
    d_ff: int = 21_504
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: tuple = (0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738)   # z, x, B, C, dt
    mlp_multipliers: tuple = (0.1767766952966369, 0.011160714285714284)                                 # gate, down
    max_seq: int = 262_144
    norm_eps: float = 1e-5
    page_len: int = 256               # serving: a prompt's last chunk is padded to a page times a power of two
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads or self.ssm_heads % self.ssm_groups:
            raise ValueError(f"{self.n_heads} query heads over {self.n_kv_heads} kv heads, {self.ssm_heads} state-space heads "
                             f"over {self.ssm_groups} groups: each must divide")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers has one scalar a segment of [z | x | B | C | dt], mlp_multipliers (gate, down)")

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self) -> int:
        """What the convolution runs over: x of every head, then every group's B, then every group's C."""
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state


FALCON_H1_TINY = FalconH1Config(
    vocab_size=256, d_model=64, n_layers=2, n_heads=10, n_kv_heads=2, head_dim=16, rope_theta=1e4, ssm_heads=4, ssm_head_dim=32,
    ssm_state=16, ssm_groups=2, d_ff=160, max_seq=256, page_len=16, dtype="float32",
)

PRESETS = {"falcon-h1-tiny": FALCON_H1_TINY}


def init(key: jax.Array, cfg: FalconH1Config) -> dict:
    """The parameter tree (truncated normal; norms at one; ``A_log = log U(1, 16)``,
    ``dt_bias`` the inverse softplus of ``exp U(log 0.001, log 0.1)`` and ``D = 1``, all
    float32). A matrix's fan-in is its input width times the SQUARE of the scalars
    that stand between it and the stream (a column segment at a time where
    segments have scalars of their own), so that each published scalar cancels
    in the draw and the embedding, each branch and the head move the stream and
    the logits as a trained model's do (the configuration's
    ``assumed.matrix_init``; the draw is benchmark/families/falcon_h1_reference.py's,
    key for key): at plain fan-ins the branches would be 0.04, 0.09 and 0.01 of
    an embedding of 5.66 and the logits the token's own embedding. ``w_in`` is the state-space projection's
    ``z | x | B | C``; its ``dt`` columns are a leaf of their own (``w_dt``) whose
    product is float32."""
    D, V, dt, F = cfg.d_model, cfg.vocab_size, cfg.jdtype, cfg.d_ff
    q, kv, H, I, C = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim, cfg.ssm_heads, cfg.d_inner, cfg.conv_channels
    ks = iter(jax.random.split(key, 4 + 24 * cfg.n_layers))
    gate_m, down_m = cfg.mlp_multipliers

    def draw(k, shape, fan_in):
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32) * fan_in ** -0.5).astype(dt)

    def dense(*shape, fan_in):
        return draw(next(ks), shape, fan_in)

    def columns(*parts):
        """One matrix [D, sum of widths] whose column segments (width, the scalars on their way) are drawn apart."""
        return jnp.concatenate([dense(D, width, fan_in=D * scalar ** 2) for width, scalar in parts], axis=1)

    def vocabulary(fan_in):
        """[V, D], a slice of the rows at a time: the float32 draw of one whole is 2.7 GB at the published sizes."""
        n = 8 if V % 8 == 0 else 1
        return jax.lax.map(lambda k: draw(k, (V // n, D), fan_in), jax.random.split(next(ks), n)).reshape(V, D)

    a_in, s_in, GN = cfg.attention_in_multiplier, cfg.ssm_in_multiplier, cfg.ssm_groups * cfg.ssm_state
    m_z, m_x, m_b, m_c, m_dt = cfg.ssm_multipliers

    def layer():
        step = jnp.exp(jax.random.uniform(next(ks), (H,), jnp.float32, np.log(0.001), np.log(0.1)))
        return {"norm": jnp.ones((D,), dt), "ffn_norm": jnp.ones((D,), dt),
                "w_qkv": columns((q, a_in), (kv, a_in * cfg.key_multiplier), (kv, a_in)),
                "wo": dense(q, D, fan_in=q * cfg.attention_out_multiplier ** 2),
                "w_in": columns((I, s_in * m_z), (I, s_in * m_x), (GN, s_in * m_b), (GN, s_in * m_c)),
                "w_dt": dense(D, H, fan_in=D * (s_in * m_dt) ** 2),
                "conv": dense(cfg.conv_taps, C, fan_in=cfg.conv_taps), "conv_bias": dense(C, fan_in=cfg.conv_taps),
                "A_log": jnp.log(jax.random.uniform(next(ks), (H,), jnp.float32, 1.0, 16.0)),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)), "D": jnp.ones((H,), jnp.float32),
                "y_norm": jnp.ones((I,), dt), "w_out": dense(I, D, fan_in=I * cfg.ssm_out_multiplier ** 2),
                "w_gate": dense(D, F, fan_in=D * gate_m ** 2), "w_up": dense(D, F, fan_in=D), "w_down": dense(F, D, fan_in=F * down_m ** 2)}

    return {"embed": vocabulary(cfg.embedding_multiplier ** 2), "layers": [layer() for _ in range(cfg.n_layers)],
            "final_norm": jnp.ones((D,), dt), "lm_head": vocabulary(D * cfg.lm_head_multiplier ** 2)}


# -- the layers, over [T, D] rows (a sequence's positions, or the slots' tokens) --------------------

def _mm(x, w):
    return jnp.einsum("...d,dh->...h", x, w)


def _times(x, scalar):
    """A muP scalar where it is written: the product in float32, the result in x's type."""
    return (x.astype(jnp.float32) * scalar).astype(x.dtype)


def _embed(params, tokens, cfg):
    return _times(jnp.take(params["embed"], tokens, axis=0), cfg.embedding_multiplier)


def _rope(positions, max_len: int, cfg):
    """What rotates the rows at `positions` [T] (under `max_len`): the tables and the rows' places in them."""
    return (*L.rope_frequencies(cfg.head_dim, max_len, cfg.rope_theta), jnp.minimum(positions, max_len - 1))


def _qkv(u, lp, rope, cfg):
    """u [T, D] -> q [T, H, dh], k (times ``key_multiplier``), v [T, Hkv, dh]; q and k rotated (`rope`: `_rope`)."""
    t, q_w, kv_w = u.shape[0], cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    qkv = _mm(_times(u, cfg.attention_in_multiplier), lp["w_qkv"])
    cos, sin, positions = rope

    def rot(a):  # apply_rope reads [B, H, T, D]
        return L.apply_rope(a.transpose(1, 0, 2)[None], cos, sin, positions=positions)[0].transpose(1, 0, 2)

    k = _times(qkv[:, q_w:q_w + kv_w], cfg.key_multiplier)
    return (rot(qkv[:, :q_w].reshape(t, cfg.n_heads, cfg.head_dim)), rot(k.reshape(t, cfg.n_kv_heads, cfg.head_dim)),
            qkv[:, q_w + kv_w:].reshape(t, cfg.n_kv_heads, cfg.head_dim))


def _ssm_inputs(u, lp, cfg):
    """u [T, D] -> (z [T, I] the gate, xBC [T, C] before the convolution, dt, g [T, H]
    float32: the step softplus(dt + dt_bias) and the log-decay -exp(A_log) dt), each
    segment of the projection times its ``ssm_multipliers`` entry."""
    I, GN = cfg.d_inner, cfg.ssm_groups * cfg.ssm_state
    *m, m_dt = cfg.ssm_multipliers
    s = _times(u, cfg.ssm_in_multiplier)
    zx = _times(_mm(s, lp["w_in"]), np.repeat(np.float32(m), (I, I, GN, GN)))
    dt = jax.nn.softplus(jnp.einsum("td,dh->th", s, lp["w_dt"], preferred_element_type=jnp.float32) * m_dt + lp["dt_bias"].astype(jnp.float32))
    return zx[:, :I], zx[:, I:], dt, -jnp.exp(lp["A_log"].astype(jnp.float32)) * dt


def _split(xbc, cfg):
    """The convolution's output [T, C] -> x [T, H, P], B, C [T, G, N]."""
    I, G, N = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state
    return (xbc[:, :I].reshape(-1, cfg.ssm_heads, cfg.ssm_head_dim), xbc[:, I:I + G * N].reshape(-1, G, N),
            xbc[:, I + G * N:].reshape(-1, G, N))


def _ssm_out(y, z, u, lp, cfg):
    """y [T, H, P] -> gate, THEN RMSNorm over each group's channels times the weight, then W_out."""
    t, G = y.shape[0], cfg.ssm_groups
    gated = (y.astype(jnp.float32).reshape(t, -1) * jax.nn.silu(z.astype(jnp.float32))).reshape(t, G, -1)
    normed = L.rms_norm(gated, lp["y_norm"].astype(jnp.float32).reshape(G, -1), cfg.norm_eps)
    return _mm(normed.reshape(t, -1).astype(u.dtype), lp["w_out"])


def _mixed(x, attn, ssm, cfg):
    """The stream after a layer's two mixers: both branches added, each times its way-out scalar."""
    both = cfg.attention_out_multiplier * attn.astype(jnp.float32) + cfg.ssm_out_multiplier * ssm.astype(jnp.float32)
    return x + both.astype(x.dtype)


def _ffn(h, lp, cfg):
    """h [T, D] -> h + down_multiplier ((silu(gate_multiplier (n W_gate)) * (n W_up)) W_down), n = Norm(h)."""
    with jax.named_scope("falcon_h1.ffn"):
        gate_m, down_m = cfg.mlp_multipliers
        n = L.rms_norm(h, lp["ffn_norm"], cfg.norm_eps)
        act = jax.nn.silu(_times(_mm(n, lp["w_gate"]), gate_m)) * _mm(n, lp["w_up"])
        return h + _times(_mm(act, lp["w_down"]), down_m)


def _finish(x, params, cfg):
    """Rows of the trunk -> float32 logits over the held rows of the head."""
    h = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return jnp.einsum("td,vd->tv", h, params["lm_head"], preferred_element_type=jnp.float32) * cfg.lm_head_multiplier


# -- a chunk of one sequence: prefill, and the whole-sequence forward --------------------------------

class Staging(NamedTuple):
    """A request mid-prefill: every layer's keys and values at their true
    positions, every layer's state and convolution tail after `length` positions."""

    k: jax.Array           # [L, 1, Hkv, max_len, dh]
    v: jax.Array
    state: jax.Array       # [L, N, H x P] float32 (ops/ssd.lanes: the state's index down the rows)
    tail: jax.Array        # [L, taps - 1, C]
    length: jax.Array      # [] int32


def _init_staging(cfg: FalconH1Config, max_len: int) -> Staging:
    kv = (cfg.n_layers, 1, cfg.n_kv_heads, max_len, cfg.head_dim)
    return Staging(jnp.zeros(kv, cfg.jdtype), jnp.zeros(kv, cfg.jdtype),
                   jnp.zeros((cfg.n_layers, cfg.ssm_state, cfg.d_inner), jnp.float32),
                   jnp.zeros((cfg.n_layers, cfg.conv_taps - 1, cfg.conv_channels), cfg.jdtype), jnp.zeros((), jnp.int32))


def _chunk(params, tokens, st: Staging, take, cfg: FalconH1Config):
    """tokens [T] at positions st.length .. + T, the first `take` of them real.
    Returns (the trunk's rows [T, D], the staging with the chunk in it)."""
    from tony_tpu.ops.attention import chunk_prefill_attention

    t = tokens.shape[0]
    pos0 = st.length
    rope = _rope(pos0 + jnp.arange(t, dtype=jnp.int32), st.k.shape[3], cfg)
    x = _embed(params, tokens, cfg)
    ks, vs, state, tail = st.k, st.v, st.state, st.tail
    for i, lp in enumerate(params["layers"]):
        u = L.rms_norm(x, lp["norm"], cfg.norm_eps)
        with jax.named_scope("falcon_h1.attn"):
            q, k, v = _qkv(u, lp, rope, cfg)
            ks = jax.lax.dynamic_update_slice(ks, k.transpose(1, 0, 2)[None, None].astype(ks.dtype), (i, 0, 0, pos0, 0))
            vs = jax.lax.dynamic_update_slice(vs, v.transpose(1, 0, 2)[None, None].astype(vs.dtype), (i, 0, 0, pos0, 0))
            o = chunk_prefill_attention(q.transpose(1, 0, 2), ks, vs, pos0, pos0 + t, jnp.int32(i))
            attn = _mm(o.transpose(1, 0, 2).reshape(t, -1), lp["wo"])
        with jax.named_scope("falcon_h1.ssm"):
            z, xbc, dt, g = _ssm_inputs(u, lp, cfg)
            xbc, new_tail = short_conv_chunk(xbc, tail[i], lp["conv"], take, lp["conv_bias"])
            xs, B, C = _split(xbc, cfg)
            y, new = ssd_chunk(xs, dt, g, B, C, lp["D"], state[i], take)
            state, tail = state.at[i].set(new), tail.at[i].set(new_tail.astype(tail.dtype))
            ssm = _ssm_out(y, z, u, lp, cfg)
        x = _ffn(_mixed(x, attn, ssm, cfg), lp, cfg)
    return x, Staging(ks, vs, state, tail, pos0 + take)


def forward(params, tokens, cfg: FalconH1Config, mesh=None):
    """tokens [B, T] -> logits [B, T, V] float32 (one device; T in whole blocks of the scan's and the convolution's)."""
    t = tokens.shape[1]
    return jax.lax.map(lambda row: _finish(_chunk(params, row, _init_staging(cfg, t), jnp.int32(t), cfg)[0], params, cfg), tokens)


# -- serving: what models/serving.ContinuousBatcher asks a model module for -------------------------

class ParallelCache(NamedTuple):
    """The engine's device state for S slots: a page pool over EVERY layer, and
    every layer's state-space state and convolution tail a slot."""

    k: jax.Array           # [L, P, Hkv, page_len, dh]
    v: jax.Array
    lengths: jax.Array     # [S]
    page_table: jax.Array  # [S, max_pages]
    state: tuple           # L arrays [S, N, H x P] float32: a layer's is a buffer of its own, updated in place
    tail: jax.Array        # [L, S, taps - 1, C]


def _init_cache(cfg: FalconH1Config, num_slots: int, max_len: int, page_len: int, num_pages: int) -> ParallelCache:
    if max_len % page_len:
        raise ValueError(f"max_len {max_len} must be a multiple of page_len {page_len}")
    pool = (cfg.n_layers, num_pages, cfg.n_kv_heads, page_len, cfg.head_dim)
    return ParallelCache(
        k=jnp.zeros(pool, cfg.jdtype), v=jnp.zeros(pool, cfg.jdtype),
        lengths=jnp.zeros((num_slots,), jnp.int32),
        page_table=jnp.zeros((num_slots, max_len // page_len), jnp.int32),
        state=tuple(jnp.zeros((num_slots, cfg.ssm_state, cfg.d_inner), jnp.float32) for _ in range(cfg.n_layers)),
        tail=jnp.zeros((cfg.n_layers, num_slots, cfg.conv_taps - 1, cfg.conv_channels), cfg.jdtype),
    )


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(2,))
def prefill_chunk(params, tokens, staging: Staging, take, cfg: FalconH1Config):
    """tokens [1, T] at positions staging.length .. + T, of which the first
    `take` are the prompt's. Returns (logits of row take-1 [1, V], staging')."""
    x, staging = _chunk(params, tokens[0], staging, take, cfg)
    return _finish(jax.lax.dynamic_slice_in_dim(x, take - 1, 1, axis=0), params, cfg), staging


@functools.partial(jax.jit, donate_argnums=(0,))
def insert_prefill(cache: ParallelCache, staging: Staging, fresh_pages, pt_row, slot, true_len, j0, n):
    """Admission: every layer's staged keys and values into the slot's fresh pages
    (the llama family's insert), every layer's state and tail from the staging."""
    from tony_tpu.models.paged_cache import PagedCache, insert_paged_prefill

    paged = insert_paged_prefill(PagedCache(cache.k, cache.v, cache.lengths, cache.page_table),
                                 staging.k, staging.v, fresh_pages, pt_row, slot, true_len, j0, n=n)
    return ParallelCache(
        paged.k, paged.v, paged.lengths, paged.page_table,
        tuple(jax.lax.dynamic_update_slice_in_dim(s, staging.state[i][None], slot, axis=0) for i, s in enumerate(cache.state)),
        jax.lax.dynamic_update_slice_in_dim(cache.tail, staging.tail[:, None], slot, axis=1),
    )


def _decode_one(params, cache: ParallelCache, tokens, cfg: FalconH1Config, staged):
    """One token a slot, the pool read-only: (logits [S, V], lengths', state',
    tail', this step's keys and values [L, S, Hkv, dh] x 2)."""
    from tony_tpu.ops.decode_attention import paged_decode_attention

    sk, sv, step = staged
    S = tokens.shape[0]
    max_len = cache.page_table.shape[1] * cache.k.shape[3]
    pos = jnp.minimum(cache.lengths, max_len - 1)
    rope = _rope(pos, max_len, cfg)
    x = _embed(params, tokens, cfg)
    state, tail = list(cache.state), cache.tail
    new_k, new_v = [], []
    for i, lp in enumerate(params["layers"]):
        u = L.rms_norm(x, lp["norm"], cfg.norm_eps)
        with jax.named_scope("falcon_h1.attn"):
            q, k, v = _qkv(u, lp, rope, cfg)
            k1, v1 = k.astype(cache.k.dtype), v.astype(cache.v.dtype)
            o = paged_decode_attention(q, cache.k, cache.v, pos, cache.page_table, jnp.int32(i), cur_k=k1, cur_v=v1,
                                       staged_k=sk[i], staged_v=sv[i], staged_count=jnp.broadcast_to(step, (S,)))
            new_k.append(k1)
            new_v.append(v1)
            attn = _mm(o.reshape(S, -1), lp["wo"])
        with jax.named_scope("falcon_h1.ssm"):
            z, xbc, dt, g = _ssm_inputs(u, lp, cfg)
            xbc, new_tail = short_conv_step(xbc, tail[i], lp["conv"], lp["conv_bias"])
            xs, B, C = _split(xbc, cfg)
            y, state[i] = ssd_step(xs, dt, g, B, C, lp["D"], state[i])
            tail = tail.at[i].set(new_tail)
            ssm = _ssm_out(y, z, u, lp, cfg)
        x = _ffn(_mixed(x, attn, ssm, cfg), lp, cfg)
    # idle slots (length 0) stay at 0, as in the dense family's step
    lengths = jnp.where(cache.lengths > 0, jnp.minimum(cache.lengths + 1, max_len), 0)
    return _finish(x, params, cfg), lengths, tuple(state), tail, jnp.stack(new_k), jnp.stack(new_v)


@functools.partial(jax.jit, static_argnames=("cfg", "n", "temperature", "top_k"), donate_argnums=(1,))
def decode_steps(params, cache: ParallelCache, tokens, key, cfg: FalconH1Config, n: int, temperature: float = 0.0,
                 top_k: int = 0, samp=None):
    """`n` decode steps in one compiled call: (tokens [S], all tokens [n, S],
    cache'). The page pool is written once, when the chunk is over (the dense
    family's deferred write); the layers' state and tail are carried from step
    to step, a layer's state updated in place."""
    from tony_tpu.models.generate import _sample, sample_logits
    from tony_tpu.models.paged_cache import write_decode_chunk

    stage = jnp.zeros((cfg.n_layers, tokens.shape[0], n, cfg.n_kv_heads, cfg.head_dim), cache.k.dtype)

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
def decode_logits(params, cache: ParallelCache, tokens, cfg: FalconH1Config):
    """A chunk of one step that hands back what it computed: (logits [S, V],
    cache' with the step's keys and values in the pool)."""
    from tony_tpu.models.paged_cache import write_decode_chunk

    stage = jnp.zeros((cfg.n_layers, tokens.shape[0], 1, cfg.n_kv_heads, cfg.head_dim), cache.k.dtype)
    logits, lengths, state, tail, cols_k, cols_v = _decode_one(params, cache, tokens, cfg, (stage, stage, jnp.int32(0)))
    k, v = write_decode_chunk(cache.k, cache.v, cols_k[:, :, None], cols_v[:, :, None], cache.lengths, cache.page_table)
    return logits, cache._replace(k=k, v=v, lengths=lengths, state=state, tail=tail)


@functools.partial(jax.jit, donate_argnums=(0,))
def _release(cache: ParallelCache, mask):
    """Retired slots: length and page-table row to zero. Their state and tail stay
    as they are: the next admission overwrites all of a slot's."""
    return cache._replace(lengths=jnp.where(mask, 0, cache.lengths), page_table=jnp.where(mask[:, None], 0, cache.page_table))


def serving_programs(cfg: FalconH1Config, kv: str):
    from tony_tpu.models.serving import ServingPrograms, _bucket

    if kv != "paged":
        raise ValueError("this model is served from the page pool only (kv='paged'): its decode reads by page")
    page = cfg.page_len

    def prefill(params, tokens, staging, take):
        return prefill_chunk(params, tokens, staging, jnp.int32(take), cfg)

    def pad(take, chunk, room):
        # a last chunk is padded to a page times a power of two (a compiled program a bucket, in whole blocks of
        # the scan's and tiles of the convolution's), never past the chunk or the room
        if chunk % page:
            raise ValueError(f"prefill_chunk {chunk}: this model's chunks are whole pages of {page}")
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
        insert=insert_prefill,
        decode_chunk=functools.partial(decode_steps, cfg=cfg),
        release=_release,
        visible_tokens=lambda n: n,            # every layer's attention reads the whole context
        prefill_path=prefill_path,
    )
