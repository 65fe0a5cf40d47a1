"""The phi4_flash family (Phi-4-mini-flash-reasoning; SambaY, arXiv:2507.06607): a
decoder-hybrid-decoder. A SELF-DECODER of Mamba-1 mixers (ops/selective_scan.py)
beside sliding-window differential attention, ONE full-attention layer whose keys
and values are THE cache, and a CROSS-DECODER whose layers keep nothing: gated
memory units that reuse one scan's output, and cross attention with a query and an
output projection only, over that one layer's keys and values.

With ``M = memory_layer`` (16 of 32 layers), block ``i`` is ``x = x + Mixer_i(LN(x)); x
= x + FFN(LN'(x))``, LayerNorm with weight and bias, ``FFN(h) = (up silu(gate)) W_down``
with ``[gate | up] = h W_gu``, and ``Mixer_i`` is

- ``mamba`` (even ``i <= M``): ``[x | z] = u W_in``; ``x = silu(conv(x) + b)`` causal and
  depthwise; ``[d | B | C] = x W_x``; ``dt = softplus(d W_dt + b_dt)``; ``h = exp(dt A) h +
  (dt x) B^T`` over a float32 state ``[E, N]``, ``A = -exp(A_log)``; ``y = h C + D x``; out
  ``(y silu(z)) W_out``. At layer ``M`` the scan's ``y`` (before the gate) is the MEMORY
  of its position.
- ``window`` (odd ``i < M``) and ``full`` (``i = M + 1``): ``[q | k | v] = u W_qkv + b``, NO
  position term; DIFFERENTIAL attention (ops/attention.py): two softmax maps a head
  pair, both applied to the pair's 2 dh-wide values, ``A1 - lam A2`` with ``lam =
  exp(lq1 . lk1) - exp(lq2 . lk2) + lam0``, ``lam0 = 0.8 - 0.6 exp(-0.3 i)``, RMSNorm
  over 2 dh times ``(1 - lam0)``; ``W_o`` with a bias. ``window`` sees the ``window``
  newest keys, its own among them.
- ``gmu`` (even ``i >= M + 2``): ``(m silu(u W_g)) W_o``, ``m`` the memory of the SAME
  position. No state.
- ``cross`` (odd ``i >= M + 3``): ``q = u W_q + b`` only; keys and values are layer
  ``M + 1``'s; the same differential form with its own lambdas, norm and ``W_o``.

After the last layer a LayerNorm, then the logits over the embedding transposed.
Each of these choices is one function here and one in the benchmark's reference
(benchmark/families/phi4_flash_reference.py; the configuration's ``assumed``).

``params``: ``trunk`` holds the ``M / 2`` periods (mamba, window) below the memory
layer, their leaves stacked a period; ``memory`` and ``full`` the two layers between;
``cross`` the periods (gmu, cross) above, stacked; a scan a stack. ``forward`` is the
whole-sequence program (every layer on every row); ``serving_programs`` is what the
serving engine asks for (models/serving.py):

- THE POOL HAS ONE LAYER, and ``1 + (n_layers - M - 2) / 2`` layers read it (eight
  of 32): the full layer writes a position's kv-head PAIRS once, and it and every
  cross layer read the same pages, the same staged rows of the decode chunk in
  flight and the same current row. A position costs ``2 x n_kv_heads x head_dim``
  elements in all;
- a ring of pairs a window layer and slot (models/paged_cache.py), a float32 state
  ``[N, E]`` and a convolution tail a mamba layer and slot;
- A PREFILL CHUNK RUNS THE CROSS-DECODER ON ONE ROW: layers above ``M + 1`` write
  no cache and no state, so a chunk runs layers ``0 .. M + 1`` on all its rows and
  the rest on the row whose logits the engine takes, with that row's memory and
  the staged keys up to it. Exact, and prefill is linear in the prompt but for one
  layer.

No prefix reuse (a page is not all a prefix leaves behind: rings, states and tails
are the rest and nothing keeps them). Served only: the scan has no backward.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from tony_tpu.models.falcon_h1 import _PREFILL_PAIRS  # the pairs a full layer's causal attention sees, a layer: here ONE layer's
from tony_tpu.obs import metrics as obs_metrics
from tony_tpu.ops import layers as L
from tony_tpu.ops.delta_rule import short_conv_chunk, short_conv_step
from tony_tpu.ops.selective_scan import selective_chunk, selective_step

_CROSS_ROWS = obs_metrics.counter(
    "tony_serve_cross_rows_total",
    "rows that dispatched prefill chunks sent through the cross-decoder (the layers above the one whose keys and "
    "values they read): one a chunk, the row whose logits the engine takes")

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"


@dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200_064
    d_model: int = 2560
    n_layers: int = 32
    memory_layer: int = 16            # the mamba layer whose scan output is the memory; the full layer is the next
    n_heads: int = 40
    n_kv_heads: int = 20
    head_dim: int = 64
    window: int = 512
    d_ff: int = 10_240
    d_inner: int = 5120               # the mamba mixers': expansion 2
    ssm_state: int = 16
    conv_taps: int = 4
    dt_rank: int = 160
    norm_eps: float = 1e-5
    page_len: int = 256               # serving: a prompt's last chunk is padded to a page times a power of two
    dtype: str = "bfloat16"

    def __post_init__(self):
        M = self.memory_layer
        if M % 2 or M < 2 or (self.n_layers - M) % 2 or self.n_layers < M + 4:
            raise ValueError(f"memory_layer {M} of {self.n_layers} layers: even, with a (mamba, window) period below it and "
                             "the full layer and a (gmu, cross) period above")
        if self.n_heads % self.n_kv_heads or self.n_kv_heads % 2:
            raise ValueError(f"{self.n_heads} query heads over {self.n_kv_heads} kv heads: both in two stripes, the one dividing the other")

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def periods(self) -> tuple[int, int]:
        """(mamba, window) periods below the memory layer, (gmu, cross) periods above the full layer."""
        return self.memory_layer // 2, (self.n_layers - self.memory_layer - 2) // 2

    def kind(self, i: int) -> str:
        """ASSUMED layer_kinds: what mixer layer i runs."""
        M = self.memory_layer
        if i <= M:
            return WINDOW if i % 2 else MAMBA
        if i == M + 1:
            return FULL
        return CROSS if i % 2 else GMU

    @property
    def pairs(self) -> int:
        return self.n_kv_heads // 2

    @property
    def pair_dim(self) -> int:
        return 2 * self.head_dim


PHI4_FLASH_TINY = Phi4FlashConfig(
    vocab_size=256, d_model=64, n_layers=8, memory_layer=2, n_heads=8, n_kv_heads=4, head_dim=8, window=8, d_ff=96, d_inner=128,
    ssm_state=16, dt_rank=4, page_len=16, dtype="float32",
)

PRESETS = {"phi4-flash-tiny": PHI4_FLASH_TINY}


def lam0(i):
    """ASSUMED differential_form: a layer's constant part of lambda, by its index."""
    return 0.8 - 0.6 * np.exp(-0.3 * np.asarray(i, np.float32))


def init(key: jax.Array, cfg: Phi4FlashConfig) -> dict:
    """The parameter tree (matrices truncated normal over their fan-in; norms at one; every bias truncated normal x
    0.02; ``A_log = log(1 .. N)`` along the state, ``dt_bias`` the inverse softplus of ``exp U(log 0.001, log 0.1)``,
    ``D = 1``, the four lambda vectors N(0, 0.1), all float32; the embedding over a fan-in of d_model, so that the
    tied logits are of size one). The draw is benchmark/families/phi4_flash_reference.py's, key for key."""
    D, V, dt, F, E, N, R = cfg.d_model, cfg.vocab_size, cfg.jdtype, cfg.d_ff, cfg.d_inner, cfg.ssm_state, cfg.dt_rank
    q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    P, Q = cfg.periods
    ks = iter(jax.random.split(key, 2 + 24 * cfg.n_layers))

    def dense(*shape, fan_in):
        return (jax.random.truncated_normal(next(ks), -2, 2, shape, jnp.float32) * fan_in ** -0.5).astype(dt)

    def bias(*shape):
        return (jax.random.truncated_normal(next(ks), -2, 2, shape, jnp.float32) * 0.02).astype(dt)

    def block():
        return {"norm": jnp.ones((D,), dt), "norm_b": bias(D), "ffn_norm": jnp.ones((D,), dt), "ffn_norm_b": bias(D),
                "w_gu": dense(D, 2 * F, fan_in=D), "w_down": dense(F, D, fan_in=F)}

    def lambdas():
        return {name: 0.1 * jax.random.normal(next(ks), (cfg.head_dim,), jnp.float32) for name in ("lq1", "lk1", "lq2", "lk2")}

    def mamba():
        step = jnp.exp(jax.random.uniform(next(ks), (E,), jnp.float32, np.log(0.001), np.log(0.1)))
        return {**block(), "w_in": dense(D, 2 * E, fan_in=D), "conv": dense(cfg.conv_taps, E, fan_in=cfg.conv_taps), "conv_bias": bias(E),
                "w_x": dense(E, R + 2 * N, fan_in=E), "w_dt": dense(R, E, fan_in=R), "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (E, N)), "D": jnp.ones((E,), jnp.float32),
                "w_out": dense(E, D, fan_in=E)}

    def attention():
        return {**block(), "w_qkv": dense(D, q + 2 * kv, fan_in=D), "b_qkv": bias(q + 2 * kv), "wo": dense(q, D, fan_in=q), "bo": bias(D),
                **lambdas(), "sub_norm": jnp.ones((cfg.pair_dim,), dt)}

    def gmu():
        return {**block(), "w_g": dense(D, E, fan_in=D), "w_o": dense(E, D, fan_in=E)}

    def cross():
        return {**block(), "w_q": dense(D, q, fan_in=D), "b_q": bias(q), "wo": dense(q, D, fan_in=q), "bo": bias(D),
                **lambdas(), "sub_norm": jnp.ones((cfg.pair_dim,), dt)}

    def stack(layer, n):
        layers = [layer() for _ in range(n)]
        return jax.tree.map(lambda *a: jnp.stack(a), *layers)

    # a slice of the rows at a time: the float32 draw of the whole vocabulary is 2 GB at the published sizes
    n = 8 if V % 8 == 0 else 1
    embed = jax.lax.map(lambda k: (jax.random.truncated_normal(k, -2, 2, (V // n, D), jnp.float32) * D ** -0.5).astype(dt),
                        jax.random.split(next(ks), n)).reshape(V, D)
    return {"embed": embed, "trunk": {MAMBA: stack(mamba, P), WINDOW: stack(attention, P)}, "memory": mamba(), "full": attention(),
            "cross": {GMU: stack(gmu, Q), CROSS: stack(cross, Q)}, "final_norm": jnp.ones((D,), dt), "final_norm_b": bias(D)}


# -- the layers, over [T, D] rows (a sequence's positions, or the slots' tokens) --------------------

def _mm(x, w):
    return jnp.einsum("...d,dh->...h", x, w)


def _norm(x, lp, name, cfg):
    """ASSUMED norm: LayerNorm with weight and bias."""
    return L.layer_norm(x, lp[name], lp[name + "_b"], cfg.norm_eps)


def _ffn(h, lp, cfg):
    """h [T, D] -> h + (up silu(gate)) W_down, [gate | up] = LN'(h) W_gu."""
    with jax.named_scope("phi4_flash.ffn"):
        gu = _mm(_norm(h, lp, "ffn_norm", cfg), lp["w_gu"])
        gate, up = gu[..., :cfg.d_ff], gu[..., cfg.d_ff:]
        return h + _mm(up * jax.nn.silu(gate), lp["w_down"])


def _mamba_inputs(u, lp, cfg):
    """u [T, D] -> (x [T, E] before the convolution, the gate z [T, E])."""
    xz = _mm(u, lp["w_in"])
    return xz[:, :cfg.d_inner], xz[:, cfg.d_inner:]


def _scan_inputs(x, lp, cfg):
    """x [T, E] after the convolution -> (dt [T, E] float32 = softplus(d W_dt + b_dt), A [N, E] float32, B, C [T, N])."""
    R, N = cfg.dt_rank, cfg.ssm_state
    dbc = _mm(x, lp["w_x"])
    dt = jax.nn.softplus(jnp.einsum("tr,re->te", dbc[:, :R], lp["w_dt"], preferred_element_type=jnp.float32)
                         + lp["dt_bias"].astype(jnp.float32))
    return dt, -jnp.exp(lp["A_log"].astype(jnp.float32)).T, dbc[:, R:R + N], dbc[:, R + N:]


def _mamba_out(y, z, u, lp):
    """y [T, E] float32 (the scan's output, D x in it) -> (y silu(z)) W_out."""
    return _mm((y * jax.nn.silu(z.astype(jnp.float32))).astype(u.dtype), lp["w_out"])


def _gmu(u, m, lp):
    """ASSUMED gmu: the memory m [T, E] float32 of the rows' own positions, gated by the token: (m silu(u W_g)) W_o."""
    with jax.named_scope("phi4_flash.gmu"):
        return _mm((m * jax.nn.silu(_mm(u, lp["w_g"]).astype(jnp.float32))).astype(u.dtype), lp["w_o"])


def _project(u, w, b):
    """u W + b in float32: the queries are widened and scaled before they are rounded (ops/attention.differential_queries)."""
    return jnp.einsum("td,dh->th", u, w, preferred_element_type=jnp.float32) + b.astype(jnp.float32)


def _qkv(u, lp, cfg):
    """u [T, D] -> q [T, H, dh] float32, the PAIRS k, v [T, Hkv / 2, 2 dh] in the cache's type. No position term."""
    t, qw, kw = u.shape[0], cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    qkv = _project(u, lp["w_qkv"], lp["b_qkv"])
    pairs = lambda a: a.astype(u.dtype).reshape(t, cfg.pairs, cfg.pair_dim)
    return qkv[:, :qw].reshape(t, cfg.n_heads, cfg.head_dim), pairs(qkv[:, qw:qw + kw]), pairs(qkv[:, qw + kw:])


def _q(u, lp, cfg):
    return _project(u, lp["w_q"], lp["b_q"]).reshape(u.shape[0], cfg.n_heads, cfg.head_dim)


def _lam(lp, l0):
    f = lambda a: a.astype(jnp.float32)
    return jnp.exp(jnp.sum(f(lp["lq1"]) * f(lp["lk1"]))) - jnp.exp(jnp.sum(f(lp["lq2"]) * f(lp["lk2"]))) + l0


def _attn_out(o, u, lp, l0, cfg):
    """o [T, H, 2 dh]: the two maps of every pair-row -> the sub-norm of their difference, laid back as heads, W_o + b."""
    from tony_tpu.ops.attention import differential_combine

    d = differential_combine(o, _lam(lp, l0), l0, lp["sub_norm"], cfg.norm_eps)
    return _mm(d.reshape(o.shape[0], -1).astype(u.dtype), lp["wo"]) + lp["bo"]


def _finish(x, params, cfg):
    """Rows of the last layer -> float32 logits over the embedding transposed (tied)."""
    h = L.layer_norm(x, params["final_norm"], params["final_norm_b"], cfg.norm_eps)
    return jnp.einsum("td,vd->tv", h, params["embed"], preferred_element_type=jnp.float32)


def _one_row_attention(q, k, v, pos):
    """A single query row's two maps over a request's staged pairs, in XLA: q [H, 2 dh] widened (its scale in it but
    for the kernels' (2 dh) ** -0.5); k, v [Hkv / 2, Tk, 2 dh]; the keys at positions <= pos count. Returns [H, 2 dh]."""
    H, wide = q.shape
    qg = q.astype(jnp.float32).reshape(k.shape[0], -1, wide) * wide ** -0.5
    s = jnp.einsum("kgd,ktd->kgt", qg, k.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.softmax(jnp.where(jnp.arange(k.shape[1]) <= pos, s, -jnp.inf), axis=-1)
    return jnp.einsum("kgt,ktd->kgd", p, v.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST).reshape(H, wide)


# -- a chunk of one sequence: prefill, and the whole-sequence forward --------------------------------

class Staging(NamedTuple):
    """A request mid-prefill: the full layer's pairs at their true positions, the
    window layers' last `window` positions, every mamba layer's state and tail."""

    fk: jax.Array      # [1, 1, Hkv / 2, max_len, 2 dh]
    fv: jax.Array
    wk: jax.Array      # [P, Hkv / 2, window, 2 dh]: positions length - window .. length - 1
    wv: jax.Array
    state: jax.Array   # [P + 1, N, E] float32 (the memory layer's last)
    tail: jax.Array    # [P + 1, taps - 1, E]
    length: jax.Array  # [] int32


def _init_staging(cfg: Phi4FlashConfig, max_len: int) -> Staging:
    P = cfg.periods[0]
    full, ring = (1, 1, cfg.pairs, max_len, cfg.pair_dim), (P, cfg.pairs, cfg.window, cfg.pair_dim)
    return Staging(jnp.zeros(full, cfg.jdtype), jnp.zeros(full, cfg.jdtype), jnp.zeros(ring, cfg.jdtype), jnp.zeros(ring, cfg.jdtype),
                   jnp.zeros((P + 1, cfg.ssm_state, cfg.d_inner), jnp.float32),
                   jnp.zeros((P + 1, cfg.conv_taps - 1, cfg.d_inner), cfg.jdtype), jnp.zeros((), jnp.int32))


def _mamba_chunk(x, lp, state, tail, take, cfg):
    """A mamba block's mixer over a chunk: (x + mixer, the scan's y [T, E] float32, state', tail')."""
    with jax.named_scope("phi4_flash.mamba"):
        u = _norm(x, lp, "norm", cfg)
        xs, z = _mamba_inputs(u, lp, cfg)
        xs, tail = short_conv_chunk(xs, tail, lp["conv"], take, lp["conv_bias"])
        y, state = selective_chunk(xs, *_scan_inputs(xs, lp, cfg), lp["D"], state, take)
        return x + _mamba_out(y, z, u, lp), y, state, tail


def _chunk(params, tokens, st: Staging, take, cfg: Phi4FlashConfig, row=None):
    """tokens [T] at positions st.length .. + T, the first `take` of them real.
    The self-decoder and the full layer run on every row. The cross-decoder runs on
    every row too (`row` None: the whole-sequence forward), or on row `row` alone.
    Returns (the last layer's rows [T, D] or [1, D], the staging with the chunk in it)."""
    from tony_tpu.ops.attention import differential_chunk_prefill_attention, differential_queries, differential_window_prefill_attention

    t, W = tokens.shape[0], cfg.window
    P, Q = cfg.periods
    pos0 = st.length
    x = jnp.take(params["embed"], tokens, axis=0)

    def period(x, inputs):
        (mp, wp), state, tail, wk, wv, l0 = inputs
        x, _, state, tail = _mamba_chunk(x, mp, state, tail, take, cfg)
        x = _ffn(x, mp, cfg)
        with jax.named_scope("phi4_flash.window"):
            u = _norm(x, wp, "norm", cfg)
            q, k, v = _qkv(u, wp, cfg)
            o, ek, ev = differential_window_prefill_attention(q, k, v, wk, wv, pos0, W)
            wk, wv = (jax.lax.dynamic_slice_in_dim(e, take, W, axis=1) for e in (ek, ev))
            x = x + _attn_out(o, u, wp, l0, cfg)
        return _ffn(x, wp, cfg), (state, tail.astype(st.tail.dtype), wk, wv)

    trunk = (params["trunk"][MAMBA], params["trunk"][WINDOW])
    x, (state, tail, wk, wv) = jax.lax.scan(period, x, (trunk, st.state[:P], st.tail[:P], st.wk, st.wv, lam0(2 * np.arange(P) + 1)))
    x, m, s_m, t_m = _mamba_chunk(x, params["memory"], st.state[P], st.tail[P], take, cfg)
    x = _ffn(x, params["memory"], cfg)
    state, tail = jnp.concatenate([state, s_m[None]]), jnp.concatenate([tail, t_m[None].astype(tail.dtype)])

    fp = params["full"]
    with jax.named_scope("phi4_flash.full"):
        u = _norm(x, fp, "norm", cfg)
        q, k, v = _qkv(u, fp, cfg)
        fk = jax.lax.dynamic_update_slice(st.fk, k.transpose(1, 0, 2)[None, None].astype(st.fk.dtype), (0, 0, 0, pos0, 0))
        fv = jax.lax.dynamic_update_slice(st.fv, v.transpose(1, 0, 2)[None, None].astype(st.fv.dtype), (0, 0, 0, pos0, 0))
        o = differential_chunk_prefill_attention(q, fk, fv, pos0, pos0 + t, jnp.int32(0))
        x = x + _attn_out(o, u, fp, lam0(cfg.memory_layer + 1), cfg)
    x = _ffn(x, fp, cfg)

    if row is not None:     # the cross-decoder's layers write nothing: only the row that is read goes through them
        x, m = (jax.lax.dynamic_slice_in_dim(a, row, 1, axis=0) for a in (x, m))

    def cross_period(x, inputs):
        (gp, cp), l0 = inputs
        x = _ffn(x + _gmu(_norm(x, gp, "norm", cfg), m, gp), gp, cfg)
        with jax.named_scope("phi4_flash.cross"):
            u = _norm(x, cp, "norm", cfg)
            q = _q(u, cp, cfg)
            if row is None:
                o = differential_chunk_prefill_attention(q, fk, fv, pos0, pos0 + t, jnp.int32(0))
            else:
                o = _one_row_attention(differential_queries(q)[0], fk[0, 0], fv[0, 0], pos0 + row)[None]
            x = x + _attn_out(o, u, cp, l0, cfg)
        return _ffn(x, cp, cfg), None

    x, _ = jax.lax.scan(cross_period, x, ((params["cross"][GMU], params["cross"][CROSS]), lam0(cfg.memory_layer + 3 + 2 * np.arange(Q))))
    return x, Staging(fk, fv, wk, wv, state, tail, pos0 + take)


def forward(params, tokens, cfg: Phi4FlashConfig, mesh=None):
    """tokens [B, T] -> logits [B, T, V] float32: every layer on every row (one device; T in whole blocks of the
    scan's and the convolution's)."""
    t = tokens.shape[1]
    return jax.lax.map(lambda seq: _finish(_chunk(params, seq, _init_staging(cfg, t), jnp.int32(t), cfg)[0], params, cfg), tokens)


# -- serving: what models/serving.ContinuousBatcher asks a model module for -------------------------

class SharedCache(NamedTuple):
    """The engine's device state for S slots: a page pool of ONE layer's pairs, which
    the full layer writes and it and every cross layer read; a ring a window layer
    and slot; a state and a convolution tail a mamba layer and slot."""

    k: jax.Array            # [1, pages, Hkv / 2, page_len, 2 dh]
    v: jax.Array
    lengths: jax.Array      # [S]
    page_table: jax.Array   # [S, max_pages]
    wk: jax.Array           # [P, S, Hkv / 2, window + RING_SLACK, 2 dh]
    wv: jax.Array
    ring_table: jax.Array   # [S, ring pages]: every logical page of slot s is page s of wk / wv (the chunk's write)
    state: jax.Array        # [P + 1, S, N, E] float32
    tail: jax.Array         # [P + 1, S, taps - 1, E]


def _init_cache(cfg: Phi4FlashConfig, num_slots: int, max_len: int, page_len: int, num_pages: int) -> SharedCache:
    from tony_tpu.models import paged_cache as pc

    if max_len % page_len:
        raise ValueError(f"max_len {max_len} must be a multiple of page_len {page_len}")
    P = cfg.periods[0]
    pool = (1, num_pages, cfg.pairs, page_len, cfg.pair_dim)
    wk, wv = pc.init_window_rings(P, num_slots, cfg.pairs, cfg.window, cfg.pair_dim, cfg.jdtype)
    return SharedCache(jnp.zeros(pool, cfg.jdtype), jnp.zeros(pool, cfg.jdtype), jnp.zeros((num_slots,), jnp.int32),
                       jnp.zeros((num_slots, max_len // page_len), jnp.int32), wk, wv, pc.ring_table(num_slots, max_len, wk.shape[3]),
                       jnp.zeros((P + 1, num_slots, cfg.ssm_state, cfg.d_inner), jnp.float32),
                       jnp.zeros((P + 1, num_slots, cfg.conv_taps - 1, cfg.d_inner), cfg.jdtype))


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(2,))
def prefill_chunk(params, tokens, staging: Staging, take, cfg: Phi4FlashConfig):
    """tokens [1, T] at positions staging.length .. + T, of which the first `take`
    are the prompt's. Returns (logits of row take-1 [1, V], staging'): layers up to
    the full one on all T rows, the cross-decoder on row take-1."""
    x, staging = _chunk(params, tokens[0], staging, take, cfg, row=take - 1)
    return _finish(x, params, cfg), staging


@functools.partial(jax.jit, donate_argnums=(0,))
def insert_prefill(cache: SharedCache, staging: Staging, fresh_pages, pt_row, slot, true_len, j0, n):
    """Admission: the full layer's staged pairs into the slot's fresh pages (the
    dense family's insert, a pool of one layer), the window layers' last positions
    into the slot's rings, the mamba layers' state and tail into the slot's."""
    from tony_tpu.models import paged_cache as pc

    paged = pc.insert_paged_prefill(pc.PagedCache(cache.k, cache.v, cache.lengths, cache.page_table),
                                    staging.fk, staging.fv, fresh_pages, pt_row, slot, true_len, j0, n=n)
    wk, wv = pc.insert_window_rings(cache.wk, cache.wv, staging.wk, staging.wv, slot, true_len)
    return cache._replace(k=paged.k, v=paged.v, lengths=paged.lengths, page_table=paged.page_table, wk=wk, wv=wv,
                          state=jax.lax.dynamic_update_slice_in_dim(cache.state, staging.state[:, None], slot, axis=1),
                          tail=jax.lax.dynamic_update_slice_in_dim(cache.tail, staging.tail[:, None], slot, axis=1))


def _mamba_step(x, lp, state, tail, cfg):
    """A mamba block's mixer for one token a slot: (x + mixer, the scan's y [S, E] float32, state', tail')."""
    with jax.named_scope("phi4_flash.mamba"):
        u = _norm(x, lp, "norm", cfg)
        xs, z = _mamba_inputs(u, lp, cfg)
        xs, tail = short_conv_step(xs, tail, lp["conv"], lp["conv_bias"])
        y, state = selective_step(xs, *_scan_inputs(xs, lp, cfg), lp["D"], state)
        return x + _mamba_out(y, z, u, lp), y, state, tail


def _decode_one(params, cache: SharedCache, tokens, cfg: Phi4FlashConfig, staged):
    """One token a slot, pool and rings read-only: (logits [S, V], lengths', state',
    tail', this step's pairs of the window layers [P, S, Hkv / 2, 2 dh] x 2 and of
    the full layer [1, S, ...] x 2). The full layer's pages, its staged rows of the
    chunk in flight and its current row are read by it and by every cross layer."""
    from tony_tpu.ops.decode_attention import differential_paged_decode_attention, differential_ring_decode_attention

    (swk, swv, sfk, sfv), step = staged
    S = tokens.shape[0]
    P, Q = cfg.periods
    max_len = cache.page_table.shape[1] * cache.k.shape[3]
    pos = jnp.minimum(cache.lengths, max_len - 1)
    count = jnp.broadcast_to(step, (S,))
    x = jnp.take(params["embed"], tokens, axis=0)

    def period(x, inputs):
        (mp, wp), state, tail, sk, sv, l0, i = inputs
        x, _, state, tail = _mamba_step(x, mp, state, tail, cfg)
        x = _ffn(x, mp, cfg)
        with jax.named_scope("phi4_flash.window"):
            u = _norm(x, wp, "norm", cfg)
            q, k, v = _qkv(u, wp, cfg)
            o = differential_ring_decode_attention(q, cache.wk, cache.wv, pos, i, cur_k=k, cur_v=v, window=cfg.window,
                                                   staged_k=sk, staged_v=sv, staged_count=count)
            x = x + _attn_out(o, u, wp, l0, cfg)
        return _ffn(x, wp, cfg), (state, tail, k, v)

    trunk = (params["trunk"][MAMBA], params["trunk"][WINDOW])
    x, (state, tail, wk1, wv1) = jax.lax.scan(
        period, x, (trunk, cache.state[:P], cache.tail[:P], swk, swv, lam0(2 * np.arange(P) + 1), jnp.arange(P, dtype=jnp.int32)))
    x, m, s_m, t_m = _mamba_step(x, params["memory"], cache.state[P], cache.tail[P], cfg)
    x = _ffn(x, params["memory"], cfg)
    state, tail = jnp.concatenate([state, s_m[None]]), jnp.concatenate([tail, t_m[None]])

    def shared(q):   # the one pool, through the one table: whoever reads
        return differential_paged_decode_attention(q, cache.k, cache.v, pos, cache.page_table, jnp.int32(0), cur_k=fk1, cur_v=fv1,
                                                   staged_k=sfk[0], staged_v=sfv[0], staged_count=count)

    fp = params["full"]
    with jax.named_scope("phi4_flash.full"):
        u = _norm(x, fp, "norm", cfg)
        q, fk1, fv1 = _qkv(u, fp, cfg)
        x = x + _attn_out(shared(q), u, fp, lam0(cfg.memory_layer + 1), cfg)
    x = _ffn(x, fp, cfg)

    def cross_period(x, inputs):
        (gp, cp), l0 = inputs
        x = _ffn(x + _gmu(_norm(x, gp, "norm", cfg), m, gp), gp, cfg)
        with jax.named_scope("phi4_flash.cross"):
            u = _norm(x, cp, "norm", cfg)
            x = x + _attn_out(shared(_q(u, cp, cfg)), u, cp, l0, cfg)
        return _ffn(x, cp, cfg), None

    x, _ = jax.lax.scan(cross_period, x, ((params["cross"][GMU], params["cross"][CROSS]), lam0(cfg.memory_layer + 3 + 2 * np.arange(Q))))
    # idle slots (length 0) stay at 0, as in the dense family's step
    lengths = jnp.where(cache.lengths > 0, jnp.minimum(cache.lengths + 1, max_len), 0)
    return _finish(x, params, cfg), lengths, state, tail.astype(cache.tail.dtype), (wk1, wv1, fk1[None], fv1[None])


def _stages(cfg: Phi4FlashConfig, cache: SharedCache, S: int, n: int):
    """A decode chunk's staging: the window layers' pairs [P, S, n, Hkv / 2, 2 dh] x 2 and the full layer's [1, ...] x 2."""
    stage = lambda layers: jnp.zeros((layers, S, n, cfg.pairs, cfg.pair_dim), cache.k.dtype)
    return stage(cfg.periods[0]), stage(cfg.periods[0]), stage(1), stage(1)


def _write_chunk(cache: SharedCache, stages, lengths, state, tail) -> SharedCache:
    """A decode chunk's one write: the full layer's staged pairs into the pool, the window layers' into the rings."""
    from tony_tpu.models.paged_cache import write_decode_chunk

    swk, swv, sfk, sfv = stages
    k, v = write_decode_chunk(cache.k, cache.v, sfk, sfv, cache.lengths, cache.page_table)
    wk, wv = write_decode_chunk(cache.wk, cache.wv, swk, swv, cache.lengths, cache.ring_table)
    return cache._replace(k=k, v=v, wk=wk, wv=wv, lengths=lengths, state=state, tail=tail)


@functools.partial(jax.jit, static_argnames=("cfg", "n", "temperature", "top_k"), donate_argnums=(1,))
def decode_steps(params, cache: SharedCache, tokens, key, cfg: Phi4FlashConfig, n: int, temperature: float = 0.0,
                 top_k: int = 0, samp=None):
    """`n` decode steps in one compiled call: (tokens [S], all tokens [n, S],
    cache'). The pool and the rings are written once, when the chunk is over (the
    dense family's deferred write); the states and tails are carried from step to
    step."""
    from tony_tpu.models.generate import _sample, sample_logits
    from tony_tpu.models.paged_cache import RING_SLACK

    if n > RING_SLACK:
        raise ValueError(f"a decode chunk of {n} steps: a window layer's ring has room for {RING_SLACK}")

    def body(carry, k_step):
        lengths, toks, state, tail, stages, i = carry
        view = cache._replace(lengths=lengths, state=state, tail=tail)
        logits, lengths, state, tail, cols = _decode_one(params, view, toks, cfg, (stages, i))
        nxt = sample_logits(logits, k_step, *samp) if samp is not None else _sample(logits, k_step, temperature, top_k)
        stages = tuple(jax.lax.dynamic_update_slice(s, c[:, :, None], (0, 0, i, 0, 0)) for s, c in zip(stages, cols))
        return (lengths, nxt, state, tail, stages, i + 1), nxt

    (lengths, toks, state, tail, stages, _), seq = jax.lax.scan(
        body, (cache.lengths, tokens, cache.state, cache.tail, _stages(cfg, cache, tokens.shape[0], n), jnp.int32(0)),
        jax.random.split(key, n))
    return toks, seq, _write_chunk(cache, stages, lengths, state, tail)


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def decode_logits(params, cache: SharedCache, tokens, cfg: Phi4FlashConfig):
    """A chunk of one step that hands back what it computed: (logits [S, V],
    cache' with the step's pairs in the pool and the rings)."""
    logits, lengths, state, tail, cols = _decode_one(params, cache, tokens, cfg, (_stages(cfg, cache, tokens.shape[0], 1), jnp.int32(0)))
    return logits, _write_chunk(cache, tuple(c[:, :, None] for c in cols), lengths, state, tail)


@functools.partial(jax.jit, donate_argnums=(0,))
def _release(cache: SharedCache, mask):
    """Retired slots: length and page-table row to zero. Their rings, states and
    tails stay as they are: a step reads a ring by position, and the next
    admission overwrites all of a slot's."""
    return cache._replace(lengths=jnp.where(mask, 0, cache.lengths), page_table=jnp.where(mask[:, None], 0, cache.page_table))


def serving_programs(cfg: Phi4FlashConfig, kv: str):
    from tony_tpu.models.serving import ServingPrograms, _bucket

    if kv != "paged":
        raise ValueError("this model is served from the page pool only (kv='paged'): its decode reads by page")
    page = cfg.page_len
    P, Q = cfg.periods

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
        _CROSS_ROWS.inc(1)
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
        # over the layers that attend: a window layer reads its window, the full layer and every cross layer the whole context
        visible_tokens=lambda n: (P * np.minimum(n, cfg.window) + (Q + 1) * n) / (P + Q + 1),
        prefill_path=prefill_path,
        # no gather_prefix: a page is not all a prefix leaves behind (rings, states and tails are the rest)
    )
