"""MiniCPM-SALA: a decoder whose layers take turns between two mixers.

`mixer_types` gives each layer its kind:

- ``minicpm4`` — grouped-query softmax attention with a learned block-sparse
  read past ``sparse.dense_len`` positions of context (InfLLM-v2:
  ops/sparse_attention.py), RMSNorm on q and k per head, no rotary embedding,
  a sigmoid output gate;
- ``lightning-attn`` — linear attention with a per-head decay and a float32
  ``[d, d]`` state a head (ops/linear_attention.py), RMSNorm on q and k, rotary
  embedding, an RMSNorm on each head's output, a sigmoid output gate.

Around them the MiniCPM trunk (muP): embeddings x ``scale_emb``; each residual
branch x ``scale_depth / sqrt(depth_scale_layers)`` (the PUBLISHED depth, also
where fewer layers are held); logits from the final norm over ``d_model /
dim_model_base``; SwiGLU; head not tied.

``params["layers"]`` is a list with one dict of leaves a layer, in order, shaped
by the layer's kind (the layers are unrolled, not scanned: two shapes, and a
slice of stacked weights handed to a loop is a copy of them). ``forward`` is the whole-sequence program; ``serving_programs`` is
what the serving engine asks for (models/serving.py): a page pool over the
sparse layers only, with their compressed keys, and beside it the linear
layers' state a slot.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from tony_tpu.ops import layers as L
from tony_tpu.ops.linear_attention import linear_attention_chunk, linear_attention_step, log_decay
from tony_tpu.ops.sparse_attention import (
    SparseSpec,
    block_scores,
    block_select,
    chosen_blocks,
    compress_keys,
    masked_prefill_attention,
    prefill_mask,
    stride_sums,
    visible_pages,
)

SPARSE, LINEAR = "minicpm4", "lightning-attn"


@dataclass(frozen=True)
class SalaConfig:
    vocab_size: int = 73_448
    d_model: int = 4096
    mixer_types: tuple = (SPARSE, LINEAR, LINEAR, LINEAR)
    depth_scale_layers: int = 32      # the published depth: the residual scale is its, at any cut
    n_heads: int = 32                 # sparse layers: query heads
    n_kv_heads: int = 2
    head_dim: int = 128
    lin_heads: int = 32               # linear layers: heads (queries, keys and values alike)
    lin_head_dim: int = 128
    d_ff: int = 16_384
    max_seq: int = 8192
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-6
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    sparse: SparseSpec = SparseSpec(kernel=32, stride=16, block=64, topk=64, init_blocks=1, window=2048,
                                    dense_len=8192)
    dtype: str = "bfloat16"

    @property
    def n_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.depth_scale_layers)

    def count(self, kind: str) -> int:
        return sum(1 for m in self.mixer_types if m == kind)


SALA_TINY = SalaConfig(
    vocab_size=256, d_model=64, mixer_types=(SPARSE, LINEAR, LINEAR, LINEAR) * 2, depth_scale_layers=8,
    n_heads=4, n_kv_heads=2, head_dim=16, lin_heads=4, lin_head_dim=16, d_ff=128, max_seq=256,
    dim_model_base=32, dtype="float32",
    sparse=SparseSpec(kernel=4, stride=2, block=8, topk=3, init_blocks=1, window=16, dense_len=32),
)

PRESETS = {"sala-tiny": SALA_TINY}


def init(key: jax.Array, cfg: SalaConfig) -> dict:
    """The parameter tree (truncated normal, fan-in scaled; norms at one):
    ``layers`` is a list with one dict of leaves a layer, in order, shaped by
    the layer's kind."""
    D, F, V, dt = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.jdtype
    ks = iter(jax.random.split(key, 3 + 9 * cfg.n_layers))

    def dense(*shape, fan_in):
        return (jax.random.truncated_normal(next(ks), -2, 2, shape, jnp.float32) * fan_in ** -0.5).astype(dt)

    def layer(kind):
        sparse = kind == SPARSE
        hd = cfg.head_dim if sparse else cfg.lin_head_dim
        q = (cfg.n_heads if sparse else cfg.lin_heads) * hd
        kv = cfg.n_kv_heads * hd if sparse else q
        lp = {"attn_norm": jnp.ones((D,), dt), "mlp_norm": jnp.ones((D,), dt),
              "w_gate": dense(D, F, fan_in=D), "w_up": dense(D, F, fan_in=D), "w_down": dense(F, D, fan_in=F),
              "wq": dense(D, q, fan_in=D), "wk": dense(D, kv, fan_in=D), "wv": dense(D, kv, fan_in=D),
              "wg": dense(D, q, fan_in=D), "wo": dense(q, D, fan_in=q),
              "q_norm": jnp.ones((hd,), dt), "k_norm": jnp.ones((hd,), dt)}
        return lp if sparse else {**lp, "o_norm": jnp.ones((hd,), dt)}

    return {"embed": dense(V, D, fan_in=1.0), "layers": [layer(kind) for kind, _ in _layers(cfg)],
            "final_norm": jnp.ones((D,), dt), "lm_head": dense(D, V, fan_in=D)}


# -- the layers, over [T, D] rows (a sequence's positions, or the slots' tokens) --

def _layers(cfg: SalaConfig):
    """(kind, index among the layers of its kind) of every layer, in order: the
    index is the layer's place in the cache of its kind (pages, or state)."""
    seen = {SPARSE: 0, LINEAR: 0}
    for kind in cfg.mixer_types:
        if kind not in seen:
            raise ValueError(f"mixer {kind!r} is neither {SPARSE!r} nor {LINEAR!r}")
        yield kind, seen[kind]
        seen[kind] += 1


def _mm(x, w):
    return jnp.einsum("...d,dh->...h", x, w)


def _ffn(x, lp, cfg):
    h = L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    return _mm(jax.nn.silu(_mm(h, lp["w_gate"])) * _mm(h, lp["w_up"]), lp["w_down"])


def _sparse_qkv(h, lp, cfg):
    """h [T, D] -> q [T, H, dh], k, v [T, Hkv, dh]; q and k normed per head."""
    t, dh = h.shape[0], cfg.head_dim
    q = L.rms_norm(_mm(h, lp["wq"]).reshape(t, cfg.n_heads, dh), lp["q_norm"], cfg.norm_eps)
    k = L.rms_norm(_mm(h, lp["wk"]).reshape(t, cfg.n_kv_heads, dh), lp["k_norm"], cfg.norm_eps)
    return q, k, _mm(h, lp["wv"]).reshape(t, cfg.n_kv_heads, dh)


def _linear_qkv(h, lp, cfg, cos, sin, positions):
    """h [T, D] -> q, k, v [T, Hl, dl]; q and k normed per head, then rotated."""
    t, shape = h.shape[0], (h.shape[0], cfg.lin_heads, cfg.lin_head_dim)

    def rot(a):  # apply_rope reads [B, H, T, D]
        return L.apply_rope(a.transpose(1, 0, 2)[None], cos, sin, positions=positions)[0].transpose(1, 0, 2)

    q = rot(L.rms_norm(_mm(h, lp["wq"]).reshape(shape), lp["q_norm"], cfg.norm_eps))
    k = rot(L.rms_norm(_mm(h, lp["wk"]).reshape(shape), lp["k_norm"], cfg.norm_eps))
    return q, k, _mm(h, lp["wv"]).reshape(shape)


def _gate_out(o, h, lp):
    """o [T, heads, d] x sigmoid(W_g h), then W_o."""
    gate = jax.nn.sigmoid(_mm(h, lp["wg"]).astype(jnp.float32)).astype(o.dtype)
    return _mm(o.reshape(o.shape[0], -1) * gate, lp["wo"])


def _sparse_attend(q, keys, values, positions, longest, n_keys, cfg):
    """q [T, H, dh] at `positions` against keys/values [Hkv, Tk, dh] (the
    queries' own among them): each query over its visible set. `longest` [] is
    the longest context among the rows that count: at or below the dense
    length every block is chosen by definition and nothing is scored (the
    inequality of `serving_programs`' `prefill_path`, which counts the chunks)."""
    t, g, sp = q.shape[0], cfg.n_heads // cfg.n_kv_heads, cfg.sparse
    nB = keys.shape[1] // sp.block

    def choose():
        kc = compress_keys(keys.transpose(1, 0, 2), sp)
        score, kth = block_select(q.reshape(t, cfg.n_kv_heads, g, cfg.head_dim), kc, positions + 1, sp)
        return chosen_blocks(score, positions + 1, sp, kth)

    chosen = jax.lax.cond(longest > sp.dense_len, choose, lambda: jnp.ones((t, cfg.n_kv_heads, nB), bool))
    mask = prefill_mask(chosen, positions, keys.shape[1], sp)
    qh = q.reshape(t, cfg.n_kv_heads, g, cfg.head_dim).transpose(1, 2, 0, 3)
    o = masked_prefill_attention(qh, keys, values, mask, n_keys)
    return o.transpose(2, 0, 1, 3).reshape(t, cfg.n_heads, cfg.head_dim)


def _finish(x, params, cfg):
    """Rows of the trunk -> float32 logits."""
    h = L.rms_norm(x, params["final_norm"], cfg.norm_eps) / (cfg.d_model / cfg.dim_model_base)
    return _mm(h.astype(cfg.jdtype), params["lm_head"]).astype(jnp.float32)


def _sequence(params, tokens, cfg: SalaConfig):
    """tokens [T] -> logits [T, V]: one sequence from its first position."""
    t = tokens.shape[0]
    if t % cfg.sparse.block:
        raise ValueError(f"{t} positions are not whole blocks of {cfg.sparse.block}")
    cfg.sparse.check(t)
    positions = jnp.arange(t, dtype=jnp.int32)
    cos, sin = L.rope_frequencies(cfg.lin_head_dim, t, cfg.rope_theta)
    slopes, s = log_decay(cfg.lin_heads), cfg.residual_scale
    x = jnp.take(params["embed"], tokens, axis=0) * cfg.scale_emb
    for (kind, i), lp in zip(_layers(cfg), params["layers"], strict=True):
        h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        if kind == SPARSE:
            q, k, v = _sparse_qkv(h, lp, cfg)
            o = _sparse_attend(q, k.transpose(1, 0, 2), v.transpose(1, 0, 2), positions, jnp.int32(t), jnp.int32(t), cfg)
        else:
            q, k, v = (a.transpose(1, 0, 2)[None] for a in _linear_qkv(h, lp, cfg, cos, sin, positions))
            state = jnp.zeros((1, cfg.lin_heads, cfg.lin_head_dim, cfg.lin_head_dim), jnp.float32)
            o, _ = linear_attention_chunk(q, k, v, state, slopes, block=math.gcd(t, 256))
            o = L.rms_norm(o[0].transpose(1, 0, 2), lp["o_norm"], cfg.norm_eps)
        x = x + s * _gate_out(o, h, lp)
        x = x + s * _ffn(x, lp, cfg)
    return _finish(x, params, cfg)


def forward(params, tokens, cfg: SalaConfig, mesh=None):
    """tokens [B, T] -> logits [B, T, V] float32 (one device; T whole blocks)."""
    return jax.lax.map(lambda row: _sequence(params, row, cfg), tokens)


# -- serving: what models/serving.ContinuousBatcher asks a model module for ----

class Staging(NamedTuple):
    """A request mid-prefill: its sparse layers' keys and values at their true
    positions, and its linear layers' state after `length` positions."""

    k: jax.Array       # [Ls, 1, Hkv, max_len, dh]
    v: jax.Array
    state: jax.Array   # [Ll, 1, Hl, dl, dl] float32
    length: jax.Array  # [] int32


class SalaCache(NamedTuple):
    """The engine's device state for S slots. A page pool over the SPARSE
    layers only (page = one selectable block), their compressed keys a slot,
    and the linear layers' state a slot."""

    k: jax.Array           # [Ls, P, Hkv, page_len, dh]
    v: jax.Array
    lengths: jax.Array     # [S]
    page_table: jax.Array  # [S, max_pages]
    ck: jax.Array          # [Ls, S, max_len // stride, Hkv, dh] float32: compressed keys
    csum: jax.Array        # [Ls, S, kernel // stride, Hkv, dh] float32: the newest strides' key sums, the open one last
    state: jax.Array       # [Ll, S, Hl, dl, dl] float32


def _init_cache(cfg: SalaConfig, num_slots: int, max_len: int, page_len: int, num_pages: int) -> SalaCache:
    sp = cfg.sparse
    sp.check(max_len)
    if page_len != sp.block:
        raise ValueError(f"page_len {page_len}: this model reads its cache by blocks of {sp.block} tokens, "
                         "and a page is a block")
    ns, nl = cfg.count(SPARSE), cfg.count(LINEAR)
    pool = (ns, num_pages, cfg.n_kv_heads, page_len, cfg.head_dim)
    return SalaCache(
        k=jnp.zeros(pool, cfg.jdtype), v=jnp.zeros(pool, cfg.jdtype),
        lengths=jnp.zeros((num_slots,), jnp.int32),
        page_table=jnp.zeros((num_slots, max_len // page_len), jnp.int32),
        ck=jnp.zeros((ns, num_slots, max_len // sp.stride, cfg.n_kv_heads, cfg.head_dim), jnp.float32),
        csum=jnp.zeros((ns, num_slots, sp.kernel // sp.stride, cfg.n_kv_heads, cfg.head_dim), jnp.float32),
        state=jnp.zeros((nl, num_slots, cfg.lin_heads, cfg.lin_head_dim, cfg.lin_head_dim), jnp.float32),
    )


def _init_staging(cfg: SalaConfig, max_len: int) -> Staging:
    kv = (cfg.count(SPARSE), 1, cfg.n_kv_heads, max_len, cfg.head_dim)
    return Staging(jnp.zeros(kv, cfg.jdtype), jnp.zeros(kv, cfg.jdtype),
                   jnp.zeros((cfg.count(LINEAR), 1, cfg.lin_heads, cfg.lin_head_dim, cfg.lin_head_dim), jnp.float32),
                   jnp.zeros((), jnp.int32))


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(2,))
def prefill_chunk(params, tokens, staging: Staging, take, cfg: SalaConfig):
    """tokens [1, T] at positions staging.length .. + T, of which the first
    `take` are the prompt's (a last chunk is padded to the chunk's one shape).
    Returns (logits of row take-1 [1, V], the staging with the chunk in it)."""
    t, max_len = tokens.shape[1], staging.k.shape[3]
    pos0 = staging.length
    positions = pos0 + jnp.arange(t, dtype=jnp.int32)
    cos, sin = L.rope_frequencies(cfg.lin_head_dim, max_len, cfg.rope_theta)
    slopes, s = log_decay(cfg.lin_heads), cfg.residual_scale
    x = jnp.take(params["embed"], tokens[0], axis=0) * cfg.scale_emb
    ks, vs, state = staging.k, staging.v, staging.state
    for (kind, i), lp in zip(_layers(cfg), params["layers"], strict=True):
        h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        if kind == SPARSE:
            q, k, v = _sparse_qkv(h, lp, cfg)
            ks = jax.lax.dynamic_update_slice(ks, k.transpose(1, 0, 2)[None, None].astype(ks.dtype), (i, 0, 0, pos0, 0))
            vs = jax.lax.dynamic_update_slice(vs, v.transpose(1, 0, 2)[None, None].astype(vs.dtype), (i, 0, 0, pos0, 0))
            o = _sparse_attend(q, ks[i, 0], vs[i, 0], positions, pos0 + take, pos0 + t, cfg)
        else:
            q, k, v = (a.transpose(1, 0, 2)[None] for a in _linear_qkv(h, lp, cfg, cos, sin, positions))
            o, new = linear_attention_chunk(q, k, v, state[i], slopes, valid=take, block=math.gcd(t, 256))
            state = state.at[i].set(new)
            o = L.rms_norm(o[0].transpose(1, 0, 2), lp["o_norm"], cfg.norm_eps)
        x = x + s * _gate_out(o, h, lp)
        x = x + s * _ffn(x, lp, cfg)
    last = jax.lax.dynamic_slice_in_dim(x, take - 1, 1, axis=0)
    return _finish(last, params, cfg), Staging(ks, vs, state, pos0 + take)


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("cfg",))
def insert_prefill(cache: SalaCache, staging: Staging, fresh_pages, pt_row, slot, true_len, j0, n, cfg: SalaConfig):
    """Admission: the staged keys and values into the slot's fresh pages (as the
    dense family's insert), its compressed keys and open stride sums worked out
    from the staged keys, and its state taken from the staging."""
    from tony_tpu.models.paged_cache import PagedCache, insert_paged_prefill

    sp = cfg.sparse
    r = sp.kernel // sp.stride
    paged = insert_paged_prefill(PagedCache(cache.k, cache.v, cache.lengths, cache.page_table),
                                 staging.k, staging.v, fresh_pages, pt_row, slot, true_len, j0, n=n)
    max_len = staging.k.shape[3]
    held = (jnp.arange(max_len) < true_len)[None, :, None]

    def per_layer(k):                                    # [Hkv, max_len, dh]
        k = k.transpose(1, 0, 2)
        sums = stride_sums(jnp.where(held.transpose(1, 0, 2), k, 0), sp)
        idx = true_len // sp.stride - (r - 1) + jnp.arange(r)
        newest = jnp.where((idx >= 0)[:, None, None], sums[jnp.maximum(idx, 0)], 0.0)
        return compress_keys(k, sp), newest

    ck, csum = jax.vmap(per_layer)(staging.k[:, 0])
    return SalaCache(
        paged.k, paged.v, paged.lengths, paged.page_table,
        jax.lax.dynamic_update_slice(cache.ck, ck[:, None], (0, slot, 0, 0, 0)),
        jax.lax.dynamic_update_slice(cache.csum, csum[:, None], (0, slot, 0, 0, 0)),
        jax.lax.dynamic_update_slice(cache.state, staging.state, (0, slot, 0, 0, 0)),
    )


def _push_key(ck, csum, k1, pos, sp: SparseSpec):
    """One more key a slot at position `pos`: into the open stride's sum, and
    where it closes a stride, the kernel that ends there into the compressed
    keys. ck [S, nK, Hkv, dh], csum [S, r, Hkv, dh], k1 [S, Hkv, dh]."""
    r, slots = sp.kernel // sp.stride, jnp.arange(k1.shape[0])
    csum = csum.at[:, r - 1].add(k1.astype(jnp.float32))
    closes = (pos + 1) % sp.stride == 0
    j = pos // sp.stride - (r - 1)                       # the kernel this stride completes
    at = jnp.where(closes & (j >= 0), j, ck.shape[1])    # past the end: dropped
    ck = ck.at[slots, at].set(csum.sum(1) / sp.kernel, mode="drop")
    shifted = jnp.concatenate([csum[:, 1:], jnp.zeros_like(csum[:, :1])], axis=1)
    return ck, jnp.where(closes[:, None, None, None], shifted, csum)


def _decode_one(params, cache: SalaCache, tokens, cfg: SalaConfig, staged):
    """One token a slot, the pool read-only: (logits [S, V], lengths', ck',
    csum', state', this step's keys and values [Ls, S, Hkv, dh] x 2)."""
    from tony_tpu.ops.decode_attention import sparse_paged_decode_attention

    sk, sv, step = staged
    S, sp = tokens.shape[0], cfg.sparse
    max_len = cache.page_table.shape[1] * cache.k.shape[3]
    pos = jnp.minimum(cache.lengths, max_len - 1)
    cos, sin = L.rope_frequencies(cfg.lin_head_dim, max_len, cfg.rope_theta)
    slopes, s = log_decay(cfg.lin_heads), cfg.residual_scale
    g = cfg.n_heads // cfg.n_kv_heads
    x = jnp.take(params["embed"], tokens, axis=0) * cfg.scale_emb
    ck, csum, state = cache.ck, cache.csum, cache.state
    new_k, new_v = [], []
    for (kind, i), lp in zip(_layers(cfg), params["layers"], strict=True):
        h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        if kind == SPARSE:
            q, k, v = _sparse_qkv(h, lp, cfg)
            k1, v1 = k.astype(cache.k.dtype), v.astype(cache.v.dtype)
            ck_i, csum_i = _push_key(ck[i], csum[i], k1, pos, sp)
            ck, csum = ck.at[i].set(ck_i), csum.at[i].set(csum_i)
            qg = q.reshape(S, 1, cfg.n_kv_heads, g, cfg.head_dim)
            score = jax.vmap(block_scores, in_axes=(0, 0, 0, None))(qg, ck_i, (pos + 1)[:, None], sp)[:, 0]
            chosen = chosen_blocks(score, pos + 1, sp)
            logical, full, counts, win_lo = visible_pages(chosen, jnp.maximum(pos - step, 0), pos, sp)
            pages = jnp.take_along_axis(cache.page_table[:, None, :], logical, axis=2)
            o = sparse_paged_decode_attention(
                q, cache.k, cache.v, jnp.int32(i), pages, logical, full, counts, pos, win_lo,
                cur_k=k1, cur_v=v1, staged_k=sk[i], staged_v=sv[i],
                staged_count=jnp.broadcast_to(step, (S,)))
            new_k.append(k1)
            new_v.append(v1)
        else:
            q, k, v = _linear_qkv(h, lp, cfg, cos, sin, pos)
            o, new = linear_attention_step(q, k, v, state[i], slopes)
            state = state.at[i].set(new)
            o = L.rms_norm(o, lp["o_norm"], cfg.norm_eps)
        x = x + s * _gate_out(o, h, lp)
        x = x + s * _ffn(x, lp, cfg)
    logits = _finish(x, params, cfg)
    # idle slots (length 0) stay at 0, as in the dense family's step
    lengths = jnp.where(cache.lengths > 0, jnp.minimum(cache.lengths + 1, max_len), 0)
    return logits, lengths, ck, csum, state, jnp.stack(new_k), jnp.stack(new_v)


@functools.partial(jax.jit, static_argnames=("cfg", "n", "temperature", "top_k"), donate_argnums=(1,))
def decode_steps(params, cache: SalaCache, tokens, key, cfg: SalaConfig, n: int, temperature: float = 0.0,
                 top_k: int = 0, samp=None):
    """`n` decode steps in one compiled call: (tokens [S], all tokens [n, S],
    cache'). The page pool is written once, when the chunk is over (the dense
    family's deferred write); compressed keys, stride sums and the linear
    layers' state are carried from step to step."""
    from tony_tpu.models.generate import _sample, sample_logits
    from tony_tpu.models.paged_cache import write_decode_chunk

    ns, S = cache.k.shape[0], tokens.shape[0]
    stage = jnp.zeros((ns, S, n, cfg.n_kv_heads, cfg.head_dim), cache.k.dtype)

    def body(carry, k_step):
        lengths, toks, ck, csum, state, sk, sv, i = carry
        view = cache._replace(lengths=lengths, ck=ck, csum=csum, state=state)
        logits, lengths, ck, csum, state, cols_k, cols_v = _decode_one(params, view, toks, cfg, (sk, sv, i))
        nxt = sample_logits(logits, k_step, *samp) if samp is not None else _sample(logits, k_step, temperature, top_k)
        sk = jax.lax.dynamic_update_slice(sk, cols_k[:, :, None], (0, 0, i, 0, 0))
        sv = jax.lax.dynamic_update_slice(sv, cols_v[:, :, None], (0, 0, i, 0, 0))
        return (lengths, nxt, ck, csum, state, sk, sv, i + 1), nxt

    (lengths, toks, ck, csum, state, sk, sv, _), seq = jax.lax.scan(
        body, (cache.lengths, tokens, cache.ck, cache.csum, cache.state, stage, stage, jnp.int32(0)),
        jax.random.split(key, n))
    k, v = write_decode_chunk(cache.k, cache.v, sk, sv, cache.lengths, cache.page_table)
    return toks, seq, SalaCache(k, v, lengths, cache.page_table, ck, csum, state)


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def decode_logits(params, cache: SalaCache, tokens, cfg: SalaConfig):
    """A chunk of one step that hands back what it computed: (logits [S, V],
    cache' with the step's keys and values in the pool)."""
    from tony_tpu.models.paged_cache import write_decode_chunk

    stage = jnp.zeros((cache.k.shape[0], tokens.shape[0], 1, cfg.n_kv_heads, cfg.head_dim), cache.k.dtype)
    logits, lengths, ck, csum, state, cols_k, cols_v = _decode_one(params, cache, tokens, cfg, (stage, stage, jnp.int32(0)))
    k, v = write_decode_chunk(cache.k, cache.v, cols_k[:, :, None], cols_v[:, :, None], cache.lengths, cache.page_table)
    return logits, SalaCache(k, v, lengths, cache.page_table, ck, csum, state)


@functools.partial(jax.jit, donate_argnums=(0,))
def _release(cache: SalaCache, mask):
    """Retired slots: length and page-table row to zero (their garbage column a
    step then lands in the sacrificial page). Their state and compressed keys
    stay as they are: the next admission overwrites all of a slot's."""
    return cache._replace(lengths=jnp.where(mask, 0, cache.lengths),
                          page_table=jnp.where(mask[:, None], 0, cache.page_table))


def serving_programs(cfg: SalaConfig, kv: str):
    from tony_tpu.models.serving import ServingPrograms, _bucket

    if kv != "paged":
        raise ValueError("this model is served from the page pool only (kv='paged'): its decode reads by page")
    sp = cfg.sparse
    seen = sp.topk * sp.block + sp.window

    def prefill(params, tokens, staging, take):
        return prefill_chunk(params, tokens, staging, jnp.int32(take), cfg)

    return ServingPrograms(
        init_cache=functools.partial(_init_cache, cfg),
        init_staging=functools.partial(_init_staging, cfg),
        prefill_chunk=prefill,
        # one shape a chunk length: a last chunk is padded to the whole chunk (its `take` says what counts)
        prefill_pad=lambda take, chunk, room: min(chunk or _bucket(take), room) - take,
        insert=functools.partial(insert_prefill, cfg=cfg),
        decode_chunk=functools.partial(decode_steps, cfg=cfg),
        release=_release,
        # no gather_prefix: a page's keys are half of what a request's prefix leaves behind, the other half
        # is the linear layers' state at the page's edge, which nothing keeps. No page is shared.
        visible_tokens=lambda n: np.where(n <= sp.dense_len, n, np.minimum(n, seen)),
        prefill_path=lambda pos, take: "sparse" if pos + take > sp.dense_len else "dense",
    )
