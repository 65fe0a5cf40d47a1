"""The dots3_note family (dots3-note-prev's language model): latent attention on
every layer, in two kinds with widths of their own, and a routed FFN after a
leading dense layer.

- ``full_attention``: MLA (a q latent and a kv latent, both normed and rescaled
  by sqrt(d_model / rank); ``n_heads`` heads of ``nope + rope``; one rotary key a
  position shared by the heads; a head-wise sigmoid gate on the output) read
  through a LEARNED INDEXER: ``index_heads`` index queries from the q latent,
  one ``index_dim``-wide index key a position, ``I[t, s] = sum_h w[t, h]
  relu(qI[t, h] . kI[s])`` in float32, and position t attends to the
  ``index_topk`` positions of largest ``I[t, .]`` and to no other (all of them
  while t < index_topk).
- ``sliding_attention``: MLA of its own widths (``swa_*``) under a window that
  counts the position itself; no indexer.
- FFN: the first ``dense_layers`` layers a SwiGLU of ``d_ff``; every other layer
  a router over ``num_experts`` (float32 sigmoid scores, the ``top_k`` of score +
  bias chosen, renormalised, x ``routed_scale``) and a shared expert, of which
  the replica holds ``held`` (parallel/expert.held_expert_ffn): what the absent
  experts would add is left out, and no code stands in for them.

The two kinds have different parameter shapes, so ``params["layers"]`` is a list
with one dict of leaves a layer and the programs unroll it (a layer's index
among its kind is its layer of that kind's cache); the routed layers' experts
are stacked in ``params["banks"]`` (the grouped product takes every layer's bank
and a layer index).

One path a kind and phase. Prefill computes the EXPANDED form (keys and values
built from latent rows a tile at a time, ops/latent_attention.
latent_prefill_attention) under a mask: on a full layer the indexer's choice
(ops/sparse_attention.index_scores_prefill -> index_select), on a window layer
the band. Decode computes the ABSORBED form (latent_rows_attention) over a block
of rows a slot: on a full layer the rows of the positions the indexer chose this
step (index_scores_decode, `kth_largest_key`, `compact_chosen`, one gather), on
a window layer the slot's ring whole, masked by position.

Serving (``serving_programs``, models/serving.py): the full layers keep a paged
LATENT pool (a row of ``kv_rank + rope``, in whole lanes, a position for all heads) and beside it
the index keys' pool, both through the engine's page table; a window layer
keeps a latent ring a slot (models/paged_cache.py). No train step and no
sharding rules: served only. The vision and audio towers of the published model
are not here: the traffic is token ids.
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
from tony_tpu.parallel.expert import MoEConfig, held_expert_ffn, held_ffn_form, held_step_counts

FULL, SLIDING = "full_attention", "sliding_attention"
BANKS = ("we_gate", "we_up", "we_down")

_INDEX_POSITIONS = obs_metrics.counter(
    "tony_serve_index_positions_total",
    "positions a learned indexer scored (a query's context, a full layer), by phase: decode steps, prefill chunks",
    labelnames=("phase",))


class Widths(NamedTuple):
    """One kind of layer's attention widths."""

    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    theta: float

    @property
    def scale(self) -> float:
        return (self.nope + self.rope) ** -0.5

    @property
    def row(self) -> int:
        """What the layer caches a position: the latent, the shared rope key, and zeros up to whole
        lanes of 128 (a row of 576 would have the device lay a pool out page-length-minor, and every
        read of rows would copy the pool)."""
        return -(-(self.kv_rank + self.rope) // 128) * 128


@dataclass(frozen=True)
class Dots3NoteConfig:
    vocab_size: int = 152_064
    d_model: int = 5120
    layer_types: tuple = (FULL,) + (FULL, SLIDING, SLIDING, SLIDING) * 11 + (FULL,)
    dense_layers: int = 1
    d_ff: int = 13_824
    d_expert: int = 1536
    num_experts: int = 256
    held: tuple = (0, 256)            # (first, count) of the experts this replica holds
    top_k: int = 8
    routed_scale: float = 1.0
    shared_experts: int = 1
    n_heads: int = 128                # full layers
    q_rank: int = 1024
    kv_rank: int = 512
    nope: int = 128
    rope: int = 64
    v_dim: int = 128
    rope_theta: float = 80_000_000.0
    swa_heads: int = 64               # sliding layers
    swa_q_rank: int = 1024
    swa_kv_rank: int = 1024
    swa_nope: int = 192
    swa_rope: int = 64
    swa_v_dim: int = 128
    swa_rope_theta: float = 50_000.0
    window: int = 513                 # the position itself and the 512 before it
    index_heads: int = 64
    index_dim: int = 128
    index_topk: int = 2048
    max_seq: int = 8192
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"

    def __post_init__(self):
        if set(self.layer_types) - {FULL, SLIDING}:
            raise ValueError(f"layer_types {set(self.layer_types)}: {FULL!r} and {SLIDING!r} are the kinds")
        if not 0 <= self.dense_layers <= len(self.layer_types):
            raise ValueError(f"dense_layers {self.dense_layers} of {len(self.layer_types)} layers")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"held {self.held} is not a range of the {self.num_experts} experts")
        if not self.count(FULL) or not self.count(SLIDING):
            raise ValueError("a layer of each kind is wanted: the programs keep a cache for each")
        if self.index_dim < self.rope:
            raise ValueError(f"an index key of {self.index_dim} cannot carry {self.rope} rotated dims")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def moe(self) -> MoEConfig:
        return MoEConfig(num_experts=self.num_experts, top_k=self.top_k, scoring="sigmoid",
                         routed_scale=self.routed_scale, held=self.held)

    def widths(self, kind: str) -> Widths:
        if kind == FULL:
            return Widths(self.n_heads, self.q_rank, self.kv_rank, self.nope, self.rope, self.v_dim, self.rope_theta)
        return Widths(self.swa_heads, self.swa_q_rank, self.swa_kv_rank, self.swa_nope, self.swa_rope, self.swa_v_dim,
                      self.swa_rope_theta)

    def count(self, kind: str) -> int:
        return sum(1 for t in self.layer_types if t == kind)

    def kind_index(self) -> tuple:
        """A layer's place among the layers of its kind: its layer of the cache of that kind."""
        seen, out = {FULL: 0, SLIDING: 0}, []
        for t in self.layer_types:
            out.append(seen[t])
            seen[t] += 1
        return tuple(out)

    @property
    def tail(self) -> int:
        """Positions before a prefill chunk that a window layer's queries can reach, in whole eights."""
        return -(-(self.window - 1) // 8) * 8


DOTS3_NOTE_TINY = Dots3NoteConfig(
    vocab_size=256, d_model=64, layer_types=(FULL, FULL, SLIDING, SLIDING, SLIDING), dense_layers=1, d_ff=128, d_expert=32,
    num_experts=8, held=(0, 4), top_k=2, n_heads=4, q_rank=32, kv_rank=16, nope=16, rope=8, v_dim=16, rope_theta=10_000.0,
    swa_heads=2, swa_q_rank=32, swa_kv_rank=32, swa_nope=24, swa_rope=8, swa_v_dim=16, swa_rope_theta=1000.0, window=9,
    index_heads=4, index_dim=16, index_topk=16, max_seq=256, dtype="float32",
)

PRESETS = {"dots3-note-tiny": DOTS3_NOTE_TINY}


def init(key: jax.Array, cfg: Dots3NoteConfig) -> dict:
    """The parameter tree (truncated normal, fan-in scaled; norms at one; the
    router float32, its bias small and not zero). What reads a rescaled latent is
    drawn x sqrt(rank / d_model): queries, keys and values at unit scale."""
    D, V, dt = cfg.d_model, cfg.vocab_size, cfg.jdtype
    Fe, held, n_routed = cfg.d_expert, cfg.held[1], cfg.n_layers - cfg.dense_layers
    ks = iter(jax.random.split(key, 8 + 24 * cfg.n_layers))

    def draw(k, shape, fan_in, dtype, scale):
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32) * (scale * fan_in ** -0.5)).astype(dtype)

    def dense(*shape, fan_in, dtype=dt, scale=1.0):
        return draw(next(ks), shape, fan_in, dtype, scale)

    def stack(n, *shape, fan_in):
        return jax.lax.map(lambda k: draw(k, shape, fan_in, dt, 1.0), jax.random.split(next(ks), n))

    def attention(kind):
        a = cfg.widths(kind)
        lp = {"attn_norm": jnp.ones((D,), dt), "mlp_norm": jnp.ones((D,), dt),
              "wq_a": dense(D, a.q_rank, fan_in=D), "q_a_norm": jnp.ones((a.q_rank,), dt),
              "wq_b": dense(a.q_rank, a.heads * (a.nope + a.rope), fan_in=a.q_rank, scale=(a.q_rank / D) ** 0.5),
              "wkv_a": dense(D, a.kv_rank + a.rope, fan_in=D), "kv_a_norm": jnp.ones((a.kv_rank,), dt),
              "w_uk": dense(a.heads, a.kv_rank, a.nope, fan_in=a.kv_rank, scale=(a.kv_rank / D) ** 0.5),
              "w_uv": dense(a.heads, a.kv_rank, a.v_dim, fan_in=a.kv_rank, scale=(a.kv_rank / D) ** 0.5),
              "wg": dense(D, a.heads, fan_in=D), "wo": dense(a.heads * a.v_dim, D, fan_in=a.heads * a.v_dim)}
        if kind == FULL:
            lp.update(idx_wq=dense(a.q_rank, cfg.index_heads * cfg.index_dim, fan_in=a.q_rank, scale=(a.q_rank / D) ** 0.5),
                      idx_wk=dense(D, cfg.index_dim, fan_in=D), idx_k_norm=jnp.ones((cfg.index_dim,), dt),
                      idx_ww=dense(D, cfg.index_heads, fan_in=D))
        return lp

    def ffn(l):
        if l < cfg.dense_layers:
            return {"w_gate": dense(D, cfg.d_ff, fan_in=D), "w_up": dense(D, cfg.d_ff, fan_in=D),
                    "w_down": dense(cfg.d_ff, D, fan_in=cfg.d_ff)}
        Fs = Fe * cfg.shared_experts
        return {"router": dense(D, cfg.num_experts, fan_in=D, dtype=jnp.float32),
                "router_bias": dense(cfg.num_experts, fan_in=1.0, dtype=jnp.float32, scale=0.1),
                "ws_gate": dense(D, Fs, fan_in=D), "ws_up": dense(D, Fs, fan_in=D), "ws_down": dense(Fs, D, fan_in=Fs)}

    return {"embed": dense(V, D, fan_in=1.0),
            "layers": [{**attention(kind), **ffn(l)} for l, kind in enumerate(cfg.layer_types)],
            "banks": {"we_gate": stack(n_routed, held, D, Fe, fan_in=D), "we_up": stack(n_routed, held, D, Fe, fan_in=D),
                      "we_down": stack(n_routed, held, Fe, D, fan_in=Fe)},
            "final_norm": jnp.ones((D,), dt), "lm_head": dense(D, V, fan_in=D)}


# -- a layer over [T, D] rows (a sequence's positions, or the slots' tokens) ------------------------

def _mm(x, w):
    return jnp.einsum("...d,dh->...h", x, w)


def _latent(a, w, cfg):
    """RMSNorm over a latent, times sqrt(d_model / rank) (`apply_mla_qkv_lora_rescale`)."""
    return L.rms_norm(a, w, cfg.norm_eps) * (cfg.d_model / a.shape[-1]) ** 0.5


def _rope(a, positions, theta):
    """a [T, ..., dr] at `positions` [T], rotated whole (rotate-half; the caller hands the rope dims only)."""
    dr = a.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr))
    ang = jnp.outer(positions.astype(jnp.float32), inv).reshape(positions.shape[0], *([1] * (a.ndim - 2)), dr // 2)
    a1, a2 = jnp.split(a.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([a1 * jnp.cos(ang) - a2 * jnp.sin(ang), a2 * jnp.cos(ang) + a1 * jnp.sin(ang)], -1).astype(a.dtype)


class Projected(NamedTuple):
    """What a layer's attention reads of its rows."""

    qn: jax.Array   # [T, H, nope]
    qr: jax.Array   # [T, H, rope], rotated
    ckr: jax.Array  # [T, row]: the row the layer caches (the latent, the rope key, zeros)
    qi: jax.Array   # [T, Hi, di] index queries, or None on a window layer
    w: jax.Array    # [T, Hi] float32
    ki: jax.Array   # [T, di]: the index key the layer caches


def _project(h, lp, a: Widths, positions, cfg) -> Projected:
    t = h.shape[0]
    cq = _latent(_mm(h, lp["wq_a"]), lp["q_a_norm"], cfg)
    q = _mm(cq, lp["wq_b"]).reshape(t, a.heads, a.nope + a.rope)
    kv = _mm(h, lp["wkv_a"])
    ckr = jnp.concatenate([_latent(kv[:, :a.kv_rank], lp["kv_a_norm"], cfg), _rope(kv[:, a.kv_rank:], positions, a.theta),
                           jnp.zeros((t, a.row - a.kv_rank - a.rope), kv.dtype)], -1)
    qi = w = ki = None
    if "idx_wq" in lp:
        dr = a.rope
        qi = _mm(cq, lp["idx_wq"]).reshape(t, cfg.index_heads, cfg.index_dim)
        qi = jnp.concatenate([_rope(qi[..., :dr], positions, a.theta), qi[..., dr:]], -1)
        ki = L.rms_norm(_mm(h, lp["idx_wk"]), lp["idx_k_norm"], cfg.norm_eps)
        ki = jnp.concatenate([_rope(ki[:, :dr], positions, a.theta), ki[:, dr:]], -1)
        w = jnp.einsum("td,dh->th", h, lp["idx_ww"], preferred_element_type=jnp.float32) * (
            cfg.index_heads ** -0.5 * cfg.index_dim ** -0.5)
    return Projected(q[..., :a.nope], _rope(q[..., a.nope:], positions, a.theta), ckr, qi, w, ki)


def _block(x, params, l, positions, attend, cfg, live=None, name="moe_swiglu_prefill"):
    """Layer l over rows x [T, D] at `positions`. `attend(l, kind, p: Projected)
    -> o [T, H, dv]` is the caller's (it knows the cache, and keeps what it must
    of the layer's rows). A routed layer's experts are the stacked banks at the
    layer's index among the routed ones, and `name` its grouped product's in a
    trace. Returns (x', rows [count]: each held expert's rows from the tokens
    `live` marks, or None for a dense layer)."""
    lp, kind, t = params["layers"][l], cfg.layer_types[l], x.shape[0]
    a = cfg.widths(kind)
    h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    o = attend(l, kind, _project(h, lp, a, positions, cfg))
    gate = jax.nn.sigmoid(_mm(h, lp["wg"]).astype(jnp.float32)).astype(o.dtype)                  # head-wise
    x = x + _mm((o * gate[:, :, None]).reshape(t, a.heads * a.v_dim), lp["wo"])
    h = L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    if "router" not in lp:
        return x + L.swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]), None
    banks = tuple(params["banks"][k] for k in BANKS)
    y, rows = held_expert_ffn(h, lp["router"], lp["router_bias"], *banks, jnp.int32(l - cfg.dense_layers), cfg.moe,
                              count_mask=live, name=name)
    return x + y + L.swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"]), rows


def _finish(x, params, cfg):
    """Rows of the trunk -> float32 logits."""
    return _mm(L.rms_norm(x, params["final_norm"], cfg.norm_eps), params["lm_head"]).astype(jnp.float32)


def absorb(p: Projected, lp, dtype) -> jax.Array:
    """The query of the absorbed form: [q_nope W_uk[h]^T ; q_rope ; zeros], [T, H, row]."""
    folded = jnp.einsum("thd,hrd->thr", p.qn, lp["w_uk"]).astype(dtype)
    pad = p.ckr.shape[-1] - folded.shape[-1] - p.qr.shape[-1]
    return jnp.concatenate([folded, p.qr.astype(dtype), jnp.zeros((*folded.shape[:2], pad), dtype)], -1)


def unabsorb(o_latent, lp, dtype) -> jax.Array:
    """Each head's weighted sum of latents [T, H, kv_rank] through W_uv: [T, H, dv]."""
    return jnp.einsum("thr,hrd->thd", o_latent.astype(dtype), lp["w_uv"])


# -- a chunk of one sequence: prefill, and the whole-sequence forward --------------------------------

class Staging(NamedTuple):
    """A request mid-prefill: its full layers' rows and index keys at their true
    positions, and its window layers' last `cfg.tail` rows."""

    c: jax.Array       # [Lf, max_len, row]
    ki: jax.Array      # [Lf, max_len, index_dim]
    tail: jax.Array    # [Lw, tail, the window layers' row]: positions length - tail .. length - 1
    length: jax.Array  # [] int32


def _init_staging(cfg: Dots3NoteConfig, max_len: int) -> Staging:
    dt = cfg.jdtype
    return Staging(jnp.zeros((cfg.count(FULL), max_len, cfg.widths(FULL).row), dt),
                   jnp.zeros((cfg.count(FULL), max_len, cfg.index_dim), dt),
                   jnp.zeros((cfg.count(SLIDING), cfg.tail, cfg.widths(SLIDING).row), dt), jnp.zeros((), jnp.int32))


def _chunk(params, tokens, st: Staging, take, cfg: Dots3NoteConfig):
    """tokens [T] at positions st.length .. + T, the first `take` of them real.
    Returns (the trunk's rows [T, D], the staging with the chunk in it). Both
    kinds run the expanded form under a mask laid out in tiles of keys: a full
    layer over the request's staged rows (the chunk's own written first) under
    the indexer's choice, a window layer over [the last `tail` rows ; the
    chunk's] under the band."""
    from tony_tpu.ops.latent_attention import divisor, latent_prefill_attention, tile_major
    from tony_tpu.ops.sparse_attention import index_scores_prefill, index_select

    t, max_len, W, tail = tokens.shape[0], st.c.shape[1], cfg.window, cfg.tail
    pos0 = st.length
    positions = pos0 + jnp.arange(t, dtype=jnp.int32)
    bq = divisor(t, 1024)
    q_end = (jnp.arange(t // bq, dtype=jnp.int32) + 1) * bq - 1                   # a query tile's last row
    kind_index = cfg.kind_index()

    def attend(l, kind, p: Projected):
        nonlocal st
        a, lp, i = cfg.widths(kind), params["layers"][l], kind_index[l]
        qn, qr = p.qn.transpose(1, 0, 2), p.qr.transpose(1, 0, 2)
        if kind == FULL:
            c = jax.lax.dynamic_update_slice(st.c, p.ckr[None].astype(st.c.dtype), (i, pos0, 0))
            ki = jax.lax.dynamic_update_slice(st.ki, p.ki[None].astype(st.ki.dtype), (i, pos0, 0))
            st = st._replace(c=c, ki=ki)
            bk = divisor(max_len, 512)                                    # the tile of keys the mask is laid out in
            keys = index_scores_prefill(p.qi.astype(ki.dtype), p.w, ki[i], pos0, block_k=bk)
            mask = index_select(keys, jnp.minimum(pos0 + t, max_len), topk=cfg.index_topk)
            first, last = jnp.zeros_like(q_end), jnp.minimum((pos0 + q_end) // bk, max_len // bk - 1)
            rows = c[i]
        else:
            rows = jnp.concatenate([st.tail[i], p.ckr.astype(st.tail.dtype)], axis=0)           # [tail + t, row]
            st = st._replace(tail=jax.lax.dynamic_update_slice(
                st.tail, jax.lax.dynamic_slice_in_dim(rows, take, tail, axis=0)[None], (i, 0, 0)))
            bk = divisor(tail + t, 512)
            kpos = pos0 - tail + jnp.arange(tail + t, dtype=jnp.int32)
            seen = (kpos[None, :] >= 0) & (kpos[None, :] <= positions[:, None]) & (positions[:, None] - kpos[None, :] < W)
            mask = tile_major(seen.astype(jnp.int8), bk)
            first = jnp.maximum(q_end - bq + 1 + tail - (W - 1), 0) // bk
            last = (q_end + tail) // bk
        o = latent_prefill_attention(qn, qr, rows, lp["w_uk"], lp["w_uv"], mask, first, last, scale=a.scale, block_q=bq)
        return o.transpose(1, 0, 2)

    x = jnp.take(params["embed"], tokens, axis=0)
    for l in range(cfg.n_layers):
        x, _ = _block(x, params, l, positions, attend, cfg)
    return x, st._replace(length=pos0 + take)


def hidden_states(params, tokens, cfg: Dots3NoteConfig):
    """tokens [T] -> the trunk after the last layer [T, D] (before the final norm)."""
    t = tokens.shape[0]
    return _chunk(params, tokens, _init_staging(cfg, t), jnp.int32(t), cfg)[0]


def forward(params, tokens, cfg: Dots3NoteConfig, mesh=None):
    """tokens [B, T] -> logits [B, T, V] float32 (one device)."""
    return jax.lax.map(lambda row: _finish(hidden_states(params, row, cfg), params, cfg), tokens)


# -- serving: what models/serving.ContinuousBatcher asks a model module for -------------------------

class LatentCache(NamedTuple):
    """The engine's device state for S slots: the full layers' paged latent pool
    with the index keys' pool beside it (one page table for both), and a latent
    ring a slot for each window layer (models/paged_cache.py)."""

    c: jax.Array            # [Lf, P, page_len, row]
    ki: jax.Array           # [Lf, P, page_len, index_dim]
    lengths: jax.Array      # [S]
    page_table: jax.Array   # [S, max_pages]
    ring: jax.Array         # [Lw, S, ring, the window layers' row]
    ring_table: jax.Array   # [S, ring pages]: every logical page of slot s is page s of `ring` (the chunk's write)


def _init_cache(cfg: Dots3NoteConfig, num_slots: int, max_len: int, page_len: int, num_pages: int) -> LatentCache:
    from tony_tpu.models import paged_cache as pc

    if max_len % page_len or max_len % 128:
        raise ValueError(f"max_len {max_len} must be whole pages of {page_len} and whole blocks of 128 (the chosen "
                         "positions are counted a block of 128 at a time)")
    c, ki = pc.init_latent_pools(cfg.count(FULL), num_pages, page_len, (cfg.widths(FULL).row, cfg.index_dim), cfg.jdtype)
    ring_len = pc.latent_ring_len(cfg.window)
    ring = jnp.zeros((cfg.count(SLIDING), num_slots, ring_len, cfg.widths(SLIDING).row), cfg.jdtype)
    return LatentCache(c, ki, jnp.zeros((num_slots,), jnp.int32), jnp.zeros((num_slots, max_len // page_len), jnp.int32),
                       ring, pc.ring_table(num_slots, max_len, ring_len))


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(2,))
def prefill_chunk(params, tokens, staging: Staging, take, cfg: Dots3NoteConfig):
    """tokens [1, T] at positions staging.length .. + T, of which the first
    `take` are the prompt's. Returns (logits of row take-1 [1, V], staging')."""
    x, staging = _chunk(params, tokens[0], staging, take, cfg)
    return _finish(jax.lax.dynamic_slice_in_dim(x, take - 1, 1, axis=0), params, cfg), staging


@functools.partial(jax.jit, donate_argnums=(0,))
def insert_prefill(cache: LatentCache, staging: Staging, fresh_pages, pt_row, slot, true_len, j0, n):
    """Admission: the full layers' staged rows and index keys into the slot's
    fresh pages, the window layers' last rows into the slot's rings."""
    from tony_tpu.models import paged_cache as pc

    c, ki = pc.insert_latent_prefill((cache.c, cache.ki), (staging.c, staging.ki), fresh_pages, j0, n)
    return LatentCache(c, ki, cache.lengths.at[slot].set(true_len), cache.page_table.at[slot].set(pt_row),
                       pc.insert_latent_rings(cache.ring, staging.tail, slot, true_len), cache.ring_table)


def _pad_rows(a, rows: int):
    """[S, n, ...] -> [S, rows, ...]: the chunk's own rows beside a block of cached ones, in whole tiles."""
    return jnp.pad(a, ((0, 0), (0, rows - a.shape[1])) + ((0, 0),) * (a.ndim - 2))


def _decode_one(params, cache: LatentCache, tokens, cfg: Dots3NoteConfig, staged):
    """One token a slot, pools and rings read-only: (logits [S, V], this step's
    rows for each kind ([Lf, S, row], [Lf, S, di], [Lw, S, row]), the routed
    layers' held rows [Lr, count], the live slots)."""
    from tony_tpu.models.paged_cache import latent_ring_valid
    from tony_tpu.ops.latent_attention import latent_rows_attention
    from tony_tpu.ops.sparse_attention import (KEY_MIN, compact_chosen, index_scores_decode, kth_largest_key,
                                               order_keys)

    sc, ski, sring, step = staged                       # [Lf, S, n, row], [Lf, S, n, di], [Lw, S, n, row]: the chunk's steps
    S, n = tokens.shape[0], sc.shape[2]
    page_len, max_pages = cache.c.shape[2], cache.page_table.shape[1]
    max_len = max_pages * page_len
    len0 = cache.lengths                                # what lies in the pools: the chunk's own rows are staged
    live = len0 > 0
    pos = jnp.minimum(len0 + step, max_len - 1)
    E = -(-n // 128) * 128
    own = jnp.broadcast_to(jnp.arange(E)[None, :] <= step, (S, E))   # the chunk's rows up to this step's (its own is put there first)
    kind_index = cfg.kind_index()
    new = {FULL: [], "ki": [], SLIDING: []}

    def attend(l, kind, p: Projected):
        a, lp, i = cfg.widths(kind), params["layers"][l], kind_index[l]
        q = absorb(p, lp, cache.c.dtype)
        row = p.ckr.astype(cache.c.dtype)
        new[kind].append(row)
        if kind == FULL:
            ki1 = p.ki.astype(cache.ki.dtype)
            new["ki"].append(ki1)
            own_rows = jax.lax.dynamic_update_slice(sc[i], row[:, None], (0, step, 0))
            own_ki = jax.lax.dynamic_update_slice(ski[i], ki1[:, None], (0, step, 0))
            qi = p.qi.astype(cache.ki.dtype)
            pooled = index_scores_decode(qi, p.w, cache.ki, jnp.int32(i), cache.page_table, len0)           # [S, max_len]
            in_pool = jnp.arange(max_len)[None, :] < len0[:, None]
            own_score = (jax.nn.relu(jnp.einsum("shd,snd->shn", qi, own_ki, preferred_element_type=jnp.float32))
                         * p.w[:, :, None]).sum(axis=1)                                                    # [S, n] float32
            keys = jnp.concatenate([jnp.where(in_pool, order_keys(pooled), KEY_MIN),
                                    jnp.where(own[:, :n], order_keys(own_score), KEY_MIN)], axis=1)
            kth = kth_largest_key(keys, cfg.index_topk)
            chosen = keys >= kth
            idx, count = compact_chosen(chosen[:, :max_len], cfg.index_topk)
            page = jnp.take_along_axis(cache.page_table, idx // page_len, axis=1)
            flat = (i * cache.c.shape[1] + page) * page_len + idx % page_len
            rows = jnp.take(cache.c.reshape(-1, a.row), flat, axis=0)[None]                                 # [1, S, topk, row]
            valid = jnp.arange(cfg.index_topk)[None, :] < count[:, None]
            o = latent_rows_attention(q, rows, jnp.int32(0), valid, _pad_rows(own_rows, E), _pad_rows(chosen[:, max_len:], E),
                                      r=a.kv_rank, scale=a.scale, name="latent_decode")
        else:
            own_rows = jax.lax.dynamic_update_slice(sring[i], row[:, None], (0, step, 0))
            valid = latent_ring_valid(cache.ring.shape[2], len0, pos, cfg.window)
            o = latent_rows_attention(q, cache.ring, jnp.int32(i), valid, _pad_rows(own_rows, E), own,
                                      r=a.kv_rank, scale=a.scale, name="latent_ring_decode")
        return unabsorb(o, lp, cache.c.dtype)

    x = jnp.take(params["embed"], tokens, axis=0)
    rows = []
    for l in range(cfg.n_layers):
        x, r = _block(x, params, l, pos, attend, cfg, live=live, name="moe_swiglu_decode")
        if r is not None:
            rows.append(r)
    return _finish(x, params, cfg), (jnp.stack(new[FULL]), jnp.stack(new["ki"]), jnp.stack(new[SLIDING])), jnp.stack(rows), live


@functools.partial(jax.jit, static_argnames=("cfg", "n", "temperature", "top_k"), donate_argnums=(1,))
def decode_steps(params, cache: LatentCache, tokens, key, cfg: Dots3NoteConfig, n: int, temperature: float = 0.0,
                 top_k: int = 0, samp=None):
    """`n` decode steps in one compiled call: (tokens [S], all tokens [n, S],
    cache', counts [4] int32). The pools and the rings are written once, when
    the chunk is over; a step reads the chunk's earlier rows from the staged
    ones. `counts` as models/exaone_moe.decode_steps: rows that landed on a held
    expert, the fullest held expert's rows, the choices made, the held experts
    a row chose."""
    from tony_tpu.models.generate import _sample, sample_logits
    from tony_tpu.models.paged_cache import RING_SLACK, write_latent_chunk

    if n > RING_SLACK:
        raise ValueError(f"a decode chunk of {n} steps: a window layer's ring has room for {RING_SLACK}")
    S, dt = tokens.shape[0], cache.c.dtype
    max_len = cache.page_table.shape[1] * cache.c.shape[2]
    stage = (jnp.zeros((cache.c.shape[0], S, n, cache.c.shape[3]), dt), jnp.zeros((cache.ki.shape[0], S, n, cache.ki.shape[3]), dt),
             jnp.zeros((cache.ring.shape[0], S, n, cache.ring.shape[3]), dt))
    live = cache.lengths > 0

    def body(carry, k_step):
        toks, stage, i, counts = carry
        logits, cols, rows, _ = _decode_one(params, cache, toks, cfg, (*stage, i))
        nxt = sample_logits(logits, k_step, *samp) if samp is not None else _sample(logits, k_step, temperature, top_k)
        stage = tuple(jax.lax.dynamic_update_slice(st, col[:, :, None], (0, 0, i, 0)) for st, col in zip(stage, cols))
        counts = counts + held_step_counts(rows, live, cfg.top_k)
        return (nxt, stage, i + 1, counts), nxt

    (toks, (sc, ski, sring), _, counts), seq = jax.lax.scan(
        body, (tokens, stage, jnp.int32(0), jnp.zeros((4,), jnp.int32)), jax.random.split(key, n))
    c, ki = write_latent_chunk((cache.c, cache.ki), (sc, ski), cache.lengths, cache.page_table)
    (ring,) = write_latent_chunk((cache.ring,), (sring,), cache.lengths, cache.ring_table)
    # idle slots (length 0) stay at 0, as in the dense family's step
    lengths = jnp.where(live, jnp.minimum(cache.lengths + n, max_len), 0)
    return toks, seq, LatentCache(c, ki, lengths, cache.page_table, ring, cache.ring_table), counts


@functools.partial(jax.jit, donate_argnums=(0,))
def _release(cache: LatentCache, mask):
    """Retired slots: length and page-table row to zero. Their rings stay as
    they are: a step reads a ring by position, and a slot's next tenant writes
    every position its steps may read."""
    return cache._replace(lengths=jnp.where(mask, 0, cache.lengths), page_table=jnp.where(mask[:, None], 0, cache.page_table))


def serving_programs(cfg: Dots3NoteConfig, kv: str):
    from tony_tpu.models.serving import ServingPrograms, _bucket

    if kv != "paged":
        raise ValueError("this model is served from the page pool only (kv='paged'): a latent pool and latent rings")
    n_full, n_window = cfg.count(FULL), cfg.count(SLIDING)

    def prefill(params, tokens, staging, take):
        return prefill_chunk(params, tokens, staging, jnp.int32(take), cfg)

    # the engine calls these two once a decode chunk and once a prefill chunk, from the host's own lengths:
    # the positions the indexer scores are counted where the engine counts the positions attention may read
    def visible_tokens(context):
        _INDEX_POSITIONS.inc(int(n_full * np.sum(context)), phase="decode")
        return (n_full * np.minimum(context, cfg.index_topk) + n_window * np.minimum(context, cfg.window)) / cfg.n_layers

    def prefill_path(pos, take):
        _INDEX_POSITIONS.inc(int(n_full * (take * pos + take * (take + 1) // 2)), phase="prefill")
        return "sparse" if pos + take > cfg.index_topk else "dense"

    return ServingPrograms(
        init_cache=functools.partial(_init_cache, cfg),
        init_staging=functools.partial(_init_staging, cfg),
        prefill_chunk=prefill,
        # one shape a chunk length: a last chunk is padded to the whole chunk (its `take` says what counts)
        prefill_pad=lambda take, chunk, room: min(chunk or _bucket(take), room) - take,
        insert=insert_prefill,
        decode_chunk=functools.partial(decode_steps, cfg=cfg),
        release=_release,
        # no gather_prefix: a shared page is not all a prefix leaves behind (the window layers' last rows at
        # the page's edge are the rest, and nothing keeps them). No page is shared.
        visible_tokens=visible_tokens,
        prefill_path=prefill_path,
        routed_ffn_form=lambda rows: held_ffn_form(cfg.moe, rows, cfg.d_model, cfg.d_expert, cfg.jdtype),
    )
