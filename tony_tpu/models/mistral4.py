"""The mistral4 family (Mistral-Small-4's language model): DENSE latent attention
on every layer and a routed FFN on every layer. One kind of layer.

- Attention: MLA. A q latent and a kv latent, both RMS-normed; ``n_heads`` heads
  of ``nope + rope``; one rotary key a position shared by the heads; no gate, no
  q/k head norm, no bias. A query reads EVERY earlier position: no indexer, no
  window.
- Rope on the ``rope`` dims, INTERLEAVED pairs (2i, 2i + 1), under YaRN
  (ops/layers.rope_frequencies, kind "yarn"); the query at position p is
  multiplied by ``1 + query_scale_beta ln(1 + floor(p / original_max))``; scores
  by ``(nope + rope)^-0.5 yarn_mscale(factor, mscale_all_dim)^2``.
- FFN: a float32 router over ``num_experts``, softmax over all of them, the
  ``top_k`` largest renormalised to sum 1, and a shared expert, each a SwiGLU of
  ``d_expert``. The replica holds ``held`` of the experts (parallel/expert.
  held_expert_ffn): what the absent experts would add is left out, and no code
  stands in for them.

Every layer has one parameter shape, so ``params["layers"]`` holds the layers'
leaves STACKED and every program scans over them (the compiled program holds one
layer whatever the depth). The experts' banks are not among the scan's slices:
the grouped kernel takes every layer's bank and a layer index, as the latent
pool's calls take the whole pool and a layer index (a slice handed to a Mosaic
call is a copy).

One path a phase. Prefill computes the EXPANDED form (ops/latent_attention.
latent_prefill_attention) over the request's staged rows under the causal mask,
tiles past the causal edge neither fetched nor computed. Decode computes the
ABSORBED form over the slot's pages (``latent_paged_decode``): the query folded
through ``W_uk`` with its position's scale, ``W_uv`` after the sum.

Serving (``serving_programs``, models/serving.py): one paged LATENT pool (a row
of ``kv_rank + rope`` in whole lanes, a position for all heads) through the
engine's page table. With neither ring nor index key a page is ALL a prefix
leaves behind, so full prompt pages are shared between requests
(``gather_prefix``: models/paged_cache.gather_latent_prefix). No train step and
no sharding rules: served only, token ids only (the published model's vision
tower is not here), one chip's share of the chips that share a layer.
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

BANKS = ("we_gate", "we_up", "we_down")

_PAGES = obs_metrics.counter(
    "tony_serve_pages_total",
    "pages of a request's prompt at its insert, by kind: attached from the prefix chain (shared), or written from its "
    "staged rows (fresh)",
    labelnames=("kind",))
_PREFILL_PAIRS = obs_metrics.counter(
    "tony_serve_prefill_pairs_total",
    "query-key pairs the causal attention of dispatched prefill chunks sees, a layer: a chunk's rows x the positions "
    "before it, and its own causal half")


@dataclass(frozen=True)
class Mistral4Config:
    vocab_size: int = 131_072
    d_model: int = 4096
    n_layers: int = 36
    n_heads: int = 32
    q_rank: int = 1024
    kv_rank: int = 256
    nope: int = 64
    rope: int = 64
    v_dim: int = 128
    d_expert: int = 2048
    num_experts: int = 128
    held: tuple = (0, 128)            # (first, count) of the experts this replica holds
    top_k: int = 4
    shared_experts: int = 1
    rope_theta: float = 10_000.0
    # YaRN: (factor, beta_fast, beta_slow, original_max_position_embeddings, mscale, mscale_all_dim)
    yarn: tuple = (128.0, 32.0, 1.0, 8192, 1.0, 1.0)
    query_scale_beta: float = 0.1     # llama_4_scaling_beta
    max_seq: int = 8192
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"

    def __post_init__(self):
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"held {self.held} is not a range of the {self.num_experts} experts")
        if self.rope % 2 or len(self.yarn) != 6:
            raise ValueError(f"rope {self.rope} is rotated in pairs; yarn {self.yarn} is (factor, beta_fast, beta_slow, "
                             "original_max, mscale, mscale_all_dim)")

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def moe(self) -> MoEConfig:
        return MoEConfig(num_experts=self.num_experts, top_k=self.top_k, scoring="softmax", held=self.held)

    @property
    def row(self) -> int:
        """What a layer caches a position: the latent, the shared rope key, and zeros up to whole lanes of 128
        (a row of 320 would have the device lay a pool out page-length-minor, and every read of rows would
        copy the pool: PERF.md section 4, PR 43)."""
        return -(-(self.kv_rank + self.rope) // 128) * 128

    @property
    def scale(self) -> float:
        """What scores are multiplied by: (nope + rope)^-0.5 yarn_mscale(factor, mscale_all_dim)^2."""
        return (self.nope + self.rope) ** -0.5 * L.yarn_mscale(self.yarn[0], self.yarn[5]) ** 2

    @property
    def rope_scaling(self) -> tuple:
        return ("yarn", *self.yarn)


MISTRAL4_TINY = Mistral4Config(
    vocab_size=256, d_model=64, n_layers=3, n_heads=4, q_rank=32, kv_rank=16, nope=8, rope=16, v_dim=16, d_expert=32,
    num_experts=8, held=(0, 4), top_k=2, rope_theta=10_000.0, yarn=(8.0, 4.0, 1.0, 32, 1.0, 1.0), max_seq=256,
    dtype="float32",
)

PRESETS = {"mistral4-tiny": MISTRAL4_TINY}


def init(key: jax.Array, cfg: Mistral4Config) -> dict:
    """The parameter tree (truncated normal, fan-in scaled; norms at one; the
    router float32). Every layer's leaves stacked; banks drawn a layer at a time
    (one draw of every layer's is a float32 temporary of their whole size)."""
    D, V, dt, n = cfg.d_model, cfg.vocab_size, cfg.jdtype, cfg.n_layers
    H, Fe, held = cfg.n_heads, cfg.d_expert, cfg.held[1]
    Fs = Fe * cfg.shared_experts
    ks = iter(jax.random.split(key, 24))

    def draw(k, shape, fan_in, dtype):
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32) * fan_in ** -0.5).astype(dtype)

    def dense(*shape, fan_in):
        return draw(next(ks), shape, fan_in, dt)

    def stack(*shape, fan_in, dtype=dt):
        return jax.lax.map(lambda k: draw(k, shape, fan_in, dtype), jax.random.split(next(ks), n))

    layers = {
        "attn_norm": jnp.ones((n, D), dt), "mlp_norm": jnp.ones((n, D), dt),
        "wq_a": stack(D, cfg.q_rank, fan_in=D), "q_a_norm": jnp.ones((n, cfg.q_rank), dt),
        "wq_b": stack(cfg.q_rank, H * (cfg.nope + cfg.rope), fan_in=cfg.q_rank),
        "wkv_a": stack(D, cfg.kv_rank + cfg.rope, fan_in=D), "kv_a_norm": jnp.ones((n, cfg.kv_rank), dt),
        "w_uk": stack(H, cfg.kv_rank, cfg.nope, fan_in=cfg.kv_rank), "w_uv": stack(H, cfg.kv_rank, cfg.v_dim, fan_in=cfg.kv_rank),
        "wo": stack(H * cfg.v_dim, D, fan_in=H * cfg.v_dim),
        "router": stack(D, cfg.num_experts, fan_in=D, dtype=jnp.float32),
        "ws_gate": stack(D, Fs, fan_in=D), "ws_up": stack(D, Fs, fan_in=D), "ws_down": stack(Fs, D, fan_in=Fs),
        "we_gate": stack(held, D, Fe, fan_in=D), "we_up": stack(held, D, Fe, fan_in=D), "we_down": stack(held, Fe, D, fan_in=Fe),
    }
    return {"embed": dense(V, D, fan_in=1.0), "layers": layers, "final_norm": jnp.ones((D,), dt), "lm_head": dense(D, V, fan_in=D)}


# -- a layer over [T, D] rows (a sequence's positions, or the slots' tokens) ------------------------

def _mm(x, w):
    return jnp.einsum("...d,dh->...h", x, w)


def _rope(a, cos, sin, positions):
    """a [T, ..., dr] at `positions` [T], pairs (2i, 2i + 1) rotated by the table's row. The pairs are
    taken apart first (all even members, then all odd ones) and the halves rotated, as HF's interleaved
    rope does: what comes out is the rotated vector in that order, on queries and keys alike, and a
    score sums the same products."""
    half = a.shape[-1] // 2
    a = a.astype(jnp.float32).reshape(*a.shape[:-1], half, 2)
    a1, a2 = a[..., 0], a[..., 1]
    c = cos[positions].reshape(positions.shape[0], *([1] * (a.ndim - 3)), half)
    s = sin[positions].reshape(c.shape)
    return jnp.concatenate([a1 * c - a2 * s, a2 * c + a1 * s], -1)


def query_scale(positions, cfg: Mistral4Config):
    """[T] float32: what the query at each position is multiplied by (`llama_4_scaling_beta`)."""
    return 1.0 + cfg.query_scale_beta * jnp.log1p(jnp.floor(positions.astype(jnp.float32) / float(cfg.yarn[3])))


class Projected(NamedTuple):
    """What a layer's attention reads of its rows."""

    qn: jax.Array   # [T, H, nope], times the position's query scale
    qr: jax.Array   # [T, H, rope], rotated, times the position's query scale
    ckr: jax.Array  # [T, row]: the row the layer caches (the latent, the rotated rope key, zeros)


def _project(h, lp, positions, tables, cfg) -> Projected:
    t, dn, r, dr = h.shape[0], cfg.nope, cfg.kv_rank, cfg.rope
    cq = L.rms_norm(_mm(h, lp["wq_a"]), lp["q_a_norm"], cfg.norm_eps)
    q = _mm(cq, lp["wq_b"]).reshape(t, cfg.n_heads, dn + dr)
    kv = _mm(h, lp["wkv_a"])
    ckr = jnp.concatenate([L.rms_norm(kv[:, :r], lp["kv_a_norm"], cfg.norm_eps), _rope(kv[:, r:], *tables, positions).astype(kv.dtype),
                           jnp.zeros((t, cfg.row - r - dr), kv.dtype)], -1)
    qs = query_scale(positions, cfg)[:, None, None]
    return Projected((q[..., :dn] * qs).astype(q.dtype), (_rope(q[..., dn:], *tables, positions) * qs).astype(q.dtype), ckr)


def _layer(x, lp, banks, li, positions, tables, attend, cfg, live=None, name="moe_swiglu_prefill"):
    """One layer over rows x [T, D] at `positions`. `attend(p: Projected) -> (o
    [T, H, dv], what the caller keeps)` is the caller's (it knows the cache).
    The experts are `banks` (every layer's, stacked) at index `li`, and `name`
    their grouped product's in a trace. Returns (x', what `attend` kept, rows
    [count]: each held expert's rows from the tokens `live` marks)."""
    t = x.shape[0]
    h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    o, kept = attend(_project(h, lp, positions, tables, cfg))
    x = x + _mm(o.reshape(t, cfg.n_heads * cfg.v_dim), lp["wo"])
    h = L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    y, rows = held_expert_ffn(h, lp["router"], None, *banks, li, cfg.moe, count_mask=live, name=name)
    return x + y + L.swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"]), kept, rows


def _scan_xs(params, cfg):
    """(the layers' leaves but the banks, a layer's index), stacked for a scan; and the banks."""
    layers = params["layers"]
    return ({k: v for k, v in layers.items() if k not in BANKS}, jnp.arange(cfg.n_layers, dtype=jnp.int32)), tuple(
        layers[k] for k in BANKS)


def _finish(x, params, cfg):
    """Rows of the trunk -> float32 logits."""
    return _mm(L.rms_norm(x, params["final_norm"], cfg.norm_eps), params["lm_head"]).astype(jnp.float32)


def _tables(cfg: Mistral4Config, max_len: int):
    return L.rope_frequencies(cfg.rope, max(max_len, cfg.max_seq), cfg.rope_theta, cfg.rope_scaling)


# -- a chunk of one sequence: prefill, and the whole-sequence forward --------------------------------

class Staging(NamedTuple):
    """A request mid-prefill: every layer's rows at their true positions."""

    c: jax.Array       # [L, max_len, row]
    length: jax.Array  # [] int32


def _init_staging(cfg: Mistral4Config, max_len: int) -> Staging:
    return Staging(jnp.zeros((cfg.n_layers, max_len, cfg.row), cfg.jdtype), jnp.zeros((), jnp.int32))


def _chunk(params, tokens, st: Staging, take, cfg: Mistral4Config):
    """tokens [T] at positions st.length .. + T, the first `take` of them real.
    Returns (the trunk's rows [T, D], the staging with the chunk in it). The
    expanded form over the request's staged rows (the chunk's own written
    first) under the causal mask, laid out in tiles of keys; a query tile's
    `last` is the tile of its last row's position, so nothing past the causal
    edge is fetched or computed."""
    from tony_tpu.ops.latent_attention import divisor, latent_prefill_attention, tile_major

    t, max_len = tokens.shape[0], st.c.shape[1]
    pos0 = st.length
    positions = pos0 + jnp.arange(t, dtype=jnp.int32)
    tables = _tables(cfg, max_len)
    bq, bk = divisor(t, 1024), divisor(max_len, 512)
    q_end = (jnp.arange(t // bq, dtype=jnp.int32) + 1) * bq - 1                   # a query tile's last row
    first, last = jnp.zeros_like(q_end), jnp.minimum((pos0 + q_end) // bk, max_len // bk - 1)
    mask = tile_major((jnp.arange(max_len, dtype=jnp.int32)[None, :] <= positions[:, None]).astype(jnp.int8), bk)
    xs, banks = _scan_xs(params, cfg)

    def body(carry, inputs):
        x, c = carry
        lp, li = inputs

        def attend(p: Projected):
            rows = jax.lax.dynamic_update_slice(c, p.ckr[None].astype(c.dtype), (li, pos0, 0))
            o = latent_prefill_attention(p.qn.transpose(1, 0, 2), p.qr.transpose(1, 0, 2), rows[li], lp["w_uk"], lp["w_uv"],
                                         mask, first, last, scale=cfg.scale, block_q=bq)
            return o.transpose(1, 0, 2), rows

        x, c, _ = _layer(x, lp, banks, li, positions, tables, attend, cfg)
        return (x, c), None

    (x, c), _ = jax.lax.scan(body, (jnp.take(params["embed"], tokens, axis=0), st.c), xs)
    return x, Staging(c, pos0 + take)


def hidden_states(params, tokens, cfg: Mistral4Config):
    """tokens [T] -> the trunk after the last layer [T, D] (before the final norm)."""
    t = tokens.shape[0]
    return _chunk(params, tokens, _init_staging(cfg, t), jnp.int32(t), cfg)[0]


def forward(params, tokens, cfg: Mistral4Config, mesh=None):
    """tokens [B, T] -> logits [B, T, V] float32 (one device)."""
    return jax.lax.map(lambda row: _finish(hidden_states(params, row, cfg), params, cfg), tokens)


# -- serving: what models/serving.ContinuousBatcher asks a model module for -------------------------

class LatentCache(NamedTuple):
    """The engine's device state for S slots: one paged latent pool for every layer."""

    c: jax.Array            # [L, P, page_len, row]
    lengths: jax.Array      # [S]
    page_table: jax.Array   # [S, max_pages]


def _init_cache(cfg: Mistral4Config, num_slots: int, max_len: int, page_len: int, num_pages: int) -> LatentCache:
    from tony_tpu.models import paged_cache as pc

    if max_len % page_len:
        raise ValueError(f"max_len {max_len} must be a multiple of page_len {page_len}")
    (c,) = pc.init_latent_pools(cfg.n_layers, num_pages, page_len, (cfg.row,), cfg.jdtype)
    return LatentCache(c, jnp.zeros((num_slots,), jnp.int32), jnp.zeros((num_slots, max_len // page_len), jnp.int32))


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(2,))
def prefill_chunk(params, tokens, staging: Staging, take, cfg: Mistral4Config):
    """tokens [1, T] at positions staging.length .. + T, of which the first
    `take` are the prompt's. Returns (logits of row take-1 [1, V], staging')."""
    x, staging = _chunk(params, tokens[0], staging, take, cfg)
    return _finish(jax.lax.dynamic_slice_in_dim(x, take - 1, 1, axis=0), params, cfg), staging


@functools.partial(jax.jit, donate_argnums=(0,))
def insert_prefill(cache: LatentCache, staging: Staging, fresh_pages, pt_row, slot, true_len, j0, n):
    """Admission: logical pages j0 .. j0 + n of the staged rows into the slot's
    fresh pages; the pages below j0 are shared ones, attached by the table's row."""
    from tony_tpu.models import paged_cache as pc

    (c,) = pc.insert_latent_prefill((cache.c,), (staging.c,), fresh_pages, j0, n)
    return LatentCache(c, cache.lengths.at[slot].set(true_len), cache.page_table.at[slot].set(pt_row))


@functools.partial(jax.jit, donate_argnums=(0,))
def gather_prefix(staging: Staging, cache: LatentCache, pages, n):
    """A prefix hit: the matched pages' rows into the request's staging, its length past them."""
    from tony_tpu.models import paged_cache as pc

    (c,) = pc.gather_latent_prefix((staging.c,), (cache.c,), pages, n)
    return Staging(c, n * cache.c.shape[2])


def _decode_one(params, cache: LatentCache, tokens, cfg: Mistral4Config, staged, step):
    """One token a slot, the pool read-only: (logits [S, V], this step's rows [L,
    S, row], the layers' held rows [L, count])."""
    from tony_tpu.models.dots3_note import absorb, unabsorb       # the absorbed form's two folds are that family's
    from tony_tpu.ops.latent_attention import latent_paged_decode

    n = staged.shape[2]
    max_len = cache.page_table.shape[1] * cache.c.shape[2]
    len0 = cache.lengths                                # what lies in the pool: the chunk's own rows are staged
    live = len0 > 0
    pos = jnp.minimum(len0 + step, max_len - 1)
    tables = _tables(cfg, max_len)
    E = -(-n // 128) * 128                              # the chunk's rows beside the pages', in whole tiles
    xs, banks = _scan_xs(params, cfg)

    def body(x, inputs):
        (lp, li), own = inputs

        def attend(p: Projected):
            row = p.ckr.astype(cache.c.dtype)
            own_rows = jax.lax.dynamic_update_slice(own, row[:, None], (0, step, 0))      # the current token's at `step`
            o = latent_paged_decode(absorb(p, lp, cache.c.dtype), cache.c, li, len0, cache.page_table,
                                    jnp.pad(own_rows, ((0, 0), (0, E - n), (0, 0))), step, r=cfg.kv_rank, scale=cfg.scale)
            return unabsorb(o, lp, cache.c.dtype), row

        x, row, rows = _layer(x, lp, banks, li, pos, tables, attend, cfg, live=live, name="moe_swiglu_decode")
        return x, (row, rows)

    x, (new, rows) = jax.lax.scan(body, jnp.take(params["embed"], tokens, axis=0), (xs, staged))
    return _finish(x, params, cfg), new, rows


@functools.partial(jax.jit, static_argnames=("cfg", "n", "temperature", "top_k"), donate_argnums=(1,))
def decode_steps(params, cache: LatentCache, tokens, key, cfg: Mistral4Config, n: int, temperature: float = 0.0,
                 top_k: int = 0, samp=None):
    """`n` decode steps in one compiled call: (tokens [S], all tokens [n, S],
    cache', counts [4] int32). The pool is written once, when the chunk is over;
    a step reads the chunk's earlier rows from the staged ones. `counts` as
    models/exaone_moe.decode_steps: rows that landed on a held expert, the
    fullest held expert's rows, the choices made, the held experts a row chose."""
    from tony_tpu.models.generate import _sample, sample_logits
    from tony_tpu.models.paged_cache import write_latent_chunk

    S = tokens.shape[0]
    max_len = cache.page_table.shape[1] * cache.c.shape[2]
    stage = jnp.zeros((cfg.n_layers, S, n, cache.c.shape[3]), cache.c.dtype)
    live = cache.lengths > 0

    def body(carry, k_step):
        toks, stage, i, counts = carry
        logits, col, rows = _decode_one(params, cache, toks, cfg, stage, i)
        nxt = sample_logits(logits, k_step, *samp) if samp is not None else _sample(logits, k_step, temperature, top_k)
        stage = jax.lax.dynamic_update_slice(stage, col[:, :, None], (0, 0, i, 0))
        counts = counts + held_step_counts(rows, live, cfg.top_k)
        return (nxt, stage, i + 1, counts), nxt

    (toks, stage, _, counts), seq = jax.lax.scan(
        body, (tokens, stage, jnp.int32(0), jnp.zeros((4,), jnp.int32)), jax.random.split(key, n))
    (c,) = write_latent_chunk((cache.c,), (stage,), cache.lengths, cache.page_table)
    # idle slots (length 0) stay at 0, as in the dense family's step
    lengths = jnp.where(live, jnp.minimum(cache.lengths + n, max_len), 0)
    return toks, seq, LatentCache(c, lengths, cache.page_table), counts


@functools.partial(jax.jit, donate_argnums=(0,))
def _release(cache: LatentCache, mask):
    """Retired slots: length and page-table row to zero."""
    return cache._replace(lengths=jnp.where(mask, 0, cache.lengths), page_table=jnp.where(mask[:, None], 0, cache.page_table))


def serving_programs(cfg: Mistral4Config, kv: str):
    from tony_tpu.models.serving import ServingPrograms, _bucket

    if kv != "paged":
        raise ValueError("this model is served from the page pool only (kv='paged'): a latent pool")

    def prefill(params, tokens, staging, take):
        return prefill_chunk(params, tokens, staging, jnp.int32(take), cfg)

    def insert(cache, staging, fresh_pages, pt_row, slot, true_len, j0, n):
        _PAGES.inc(int(j0), kind="shared")
        _PAGES.inc(int(n), kind="fresh")
        return insert_prefill(cache, staging, fresh_pages, pt_row, slot, true_len, j0, n)

    def gather(staging, cache, pages, n):
        # the table's width whatever was matched: one compiled variant, the count traced
        padded = np.zeros(cache.page_table.shape[1], np.int32)
        padded[:n] = np.asarray(pages)
        return gather_prefix(staging, cache, padded, jnp.int32(n))

    def prefill_path(pos, take):
        # the engine calls this once a prefill chunk, from the host's own lengths
        _PREFILL_PAIRS.inc(take * pos + take * (take + 1) // 2)
        return "dense"

    return ServingPrograms(
        init_cache=functools.partial(_init_cache, cfg),
        init_staging=functools.partial(_init_staging, cfg),
        prefill_chunk=prefill,
        # a last chunk is padded to a power of two (a compiled program a bucket; a question of 64 rows after a
        # shared document must not pay for the whole chunk against its 33k staged rows), never under 32 rows
        # (the mask's int8 tiles), never past the chunk or the room
        prefill_pad=lambda take, chunk, room: min(max(_bucket(take), 32), chunk or room, room) - take,
        insert=insert,
        decode_chunk=functools.partial(decode_steps, cfg=cfg),
        release=_release,
        visible_tokens=lambda n: n,            # every layer reads the whole context
        prefill_path=prefill_path,
        gather_prefix=gather,
        routed_ffn_form=lambda rows: held_ffn_form(cfg.moe, rows, cfg.d_model, cfg.d_expert, cfg.jdtype),
    )
