"""Learned block-sparse attention (InfLLM-v2, as MiniCPM4's `sparse_config`).

Past ``dense_len`` positions of context a query no longer reads every key. It
scores COMPRESSED keys (the mean of ``kernel`` keys, one every ``stride``), a
block of ``block`` tokens takes the largest softmax weight among the
compressed keys that overlap it, summed over the query heads of one kv group,
and the query reads: the first ``init_blocks`` blocks, the ``topk`` blocks of
the highest score (the first ones counted among them), and the last ``window``
tokens. One set a kv group and query position. At or below ``dense_len`` it is
plain causal attention.

Here: the compressed keys; the block scores (float32: a block is chosen or it
is not, and the choice must not hang on bf16 rounding) in two forms that agree
but for the last bits of a row's softmax sum: ``block_scores``, plain XLA over
a ``[rows, heads, compressed keys]`` array, for the one query a slot of a
decode step and as the tests' oracle, and ``block_select``, one fused Pallas
call for the rows of a prefill chunk, which keeps a running maximum and sum as
flash does, pools the RAW scores a block (``exp(x - m) / l`` is monotone in
``x``), never holds that array and neither fetches nor computes a tile of
compressed keys past the chunk's context; the chosen blocks
(``chosen_blocks``: the ``topk``-th largest score by an exact search over the
bits of a float32, ``kth_largest``, no sort; ties to the earlier block); and
the two forms the chosen set takes: a token mask for a prefill chunk
(``masked_prefill_attention``: a flash kernel that takes the mask as an
operand; it computes every staged key under the mask and skips none yet) and a
list of pages a (slot, kv head) for decode (``visible_pages`` ->
ops/decode_attention.sparse_paged_decode_attention, which reads only those).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from tony_tpu.ops.interpret import interpret

_HI = jax.lax.Precision.HIGHEST


class SparseSpec(NamedTuple):
    kernel: int       # keys a compressed key averages
    stride: int       # distance between compressed keys
    block: int        # tokens a selectable block
    topk: int         # blocks a query reads (the first ones among them)
    init_blocks: int  # blocks at the start always read
    window: int       # last tokens always read
    dense_len: int    # contexts up to this read everything

    def check(self, max_len: int) -> None:
        if self.kernel % self.stride or self.block % self.stride or max_len % self.block:
            raise ValueError(f"sparse sizes {self} at max_len {max_len}: kernel and block must be whole strides "
                             "and max_len whole blocks")

    def list_len(self) -> int:
        """The most pages one (slot, head) list can name, a page a block."""
        return max(self.topk + -(-self.window // self.block) + 1, -(-self.dense_len // self.block))


def stride_sums(k: jax.Array, spec: SparseSpec) -> jax.Array:
    """k [T, Hkv, d] -> float32 [T // stride, Hkv, d]: the sum of each stride's keys."""
    T, hkv, d = k.shape
    return k.astype(jnp.float32).reshape(T // spec.stride, spec.stride, hkv, d).sum(1)


def compress_keys(k: jax.Array, spec: SparseSpec) -> jax.Array:
    """k [T, Hkv, d] -> float32 [T // stride, Hkv, d]: compressed key j is the
    mean of k[stride*j : stride*j + kernel]; the last ``kernel/stride - 1``
    entries run past T and stand for no kernel (never valid)."""
    a = stride_sums(k, spec)
    r = spec.kernel // spec.stride
    pad = jnp.pad(a, ((0, r - 1), (0, 0), (0, 0)))
    return sum(pad[i:i + a.shape[0]] for i in range(r)) / spec.kernel


def _n_valid(n_ctx: jax.Array, spec: SparseSpec) -> jax.Array:
    """The kernels that end inside a context of n_ctx positions: the first that many."""
    return jnp.maximum((n_ctx - spec.kernel) // spec.stride + 1, 0)


def block_scores(q: jax.Array, kc: jax.Array, n_ctx: jax.Array, spec: SparseSpec) -> jax.Array:
    """q [T, Hkv, G, d] (G query heads a group), kc [nK, Hkv, d] compressed
    keys, n_ctx [T] the context of each query (its position + 1). Returns
    float32 [T, Hkv, nB]: the block's score; -1 for a block no finished kernel
    overlaps; +inf for the first ``init_blocks``."""
    nK, d = kc.shape[0], kc.shape[-1]
    per, extra = spec.block // spec.stride, spec.kernel // spec.stride - 1
    nB = nK // per
    s = jnp.einsum("tgrd,kgd->tgrk", q.astype(jnp.float32), kc.astype(jnp.float32), precision=_HI) * d ** -0.5
    n_valid = _n_valid(n_ctx, spec)
    valid = (jnp.arange(nK)[None, :] < n_valid[:, None])[:, None, None, :]
    m = jnp.max(jnp.where(valid, s, -1e30), axis=-1, keepdims=True)
    e = jnp.where(valid, jnp.exp(s - m), 0.0)
    p = e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30)
    # block b overlaps kernels b*per - extra .. b*per + per - 1
    pp = jnp.pad(p, ((0, 0), (0, 0), (0, 0), (extra, 0)))
    best = functools.reduce(jnp.maximum, [pp[..., o:o + nB * per:per] for o in range(per + extra)])
    score = best.sum(axis=2)                                                    # over the group's heads
    b = jnp.arange(nB)
    has_kernel = jnp.maximum(b * per - extra, 0)[None, :] < n_valid[:, None]
    score = jnp.where(has_kernel[:, None, :], score, -1.0)
    return jnp.where((b < spec.init_blocks)[None, None, :], jnp.inf, score)


def kth_largest(score: jax.Array, k: int) -> jax.Array:
    """[..., n] float32 -> [..., 1]: the k-th largest of the last axis, or 0
    where fewer than k entries are >= 0. Exact, and no sort: a float32 that is
    not negative orders as its bits do, so the answer is built bit by bit, the
    largest t with k entries >= t (a negative entry is below every t; no NaN).
    Plain array code: `block_select` runs it on a tile in VMEM."""
    bits = jax.lax.bitcast_convert_type(score, jnp.int32)

    def step(i, t):
        cand = t | jnp.left_shift(jnp.int32(1), 30 - i)
        count = (bits >= cand).astype(jnp.float32).sum(axis=-1, keepdims=True)  # whole and under 2**24: exact
        return jnp.where(count >= k, cand, t)

    t = jax.lax.fori_loop(0, 31, step, jnp.zeros((*score.shape[:-1], 1), jnp.int32))
    return jax.lax.bitcast_convert_type(t, jnp.float32)


def chosen_blocks(score: jax.Array, n_ctx: jax.Array, spec: SparseSpec,
                  kth: jax.Array | None = None) -> jax.Array:
    """[T, Hkv, nB] bool: the blocks a query reads whole. All of them where the
    context is dense; else the ``topk`` of the highest score, ties to the
    earlier block. `kth` [T, Hkv, 1]: the ``topk``-th largest score, where the
    caller has it already."""
    k = min(spec.topk, score.shape[-1])
    if kth is None:
        kth = kth_largest(score, k)
    # neighbouring blocks share a compressed key, so equal scores are common:
    # of those that tie for the last places the earlier blocks are taken
    above, tied = score > kth, score == kth
    room = k - above.sum(-1, keepdims=True)
    top = (above | (tied & (jnp.cumsum(tied, axis=-1) <= room))) & (score >= 0)
    return top | (n_ctx <= spec.dense_len)[:, None, None]


def prefill_mask(chosen: jax.Array, q_pos: jax.Array, n_keys: int, spec: SparseSpec) -> jax.Array:
    """chosen [T, Hkv, nB], q_pos [T] -> int8 [Hkv, T, n_keys]: 1 where the
    query may read the key (causal; its block chosen or inside the window)."""
    key = jnp.arange(n_keys)
    by_block = jnp.repeat(chosen.transpose(1, 0, 2), spec.block, axis=2)[:, :, :n_keys]
    near = key[None, :] > q_pos[:, None] - spec.window
    return ((key[None, :] <= q_pos[:, None])[None] & (by_block | near[None])).astype(jnp.int8)


def visible_pages(chosen: jax.Array, pool_len: jax.Array, length: jax.Array, spec: SparseSpec):
    """The decode form of a chosen set. chosen [S, Hkv, nB]; pool_len [S]
    positions that lie in the page pool; length [S] the current token's
    position. A page is a block. Returns (logical [S, Hkv, N] ascending, full
    [S, Hkv, N], counts [S, Hkv], win_lo [S]): the pages that hold a visible
    pool position, whether all of the page is visible or only the window's
    part of it, how many, and where the window starts."""
    nB, N = chosen.shape[-1], min(spec.list_len(), chosen.shape[-1])
    b = jnp.arange(nB)
    win_lo = jnp.maximum(length + 1 - spec.window, 0)
    in_pool = (b[None, :] * spec.block < pool_len[:, None])[:, None, :]
    in_window = (b[None, :] >= (win_lo // spec.block)[:, None])[:, None, :]
    shown = in_pool & (chosen | in_window)
    order, logical = jax.lax.top_k(jnp.where(shown, nB - b, 0), N)             # shown pages first, ascending
    full = jnp.take_along_axis(chosen, logical, axis=-1) & (order > 0)
    return logical, full, shown.sum(-1).astype(jnp.int32), win_lo


def _flash_masked_kernel(kmax_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, m_sc, l_sc, acc_sc, *, n_rep, scale):
    from jax.experimental import pallas as pl

    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, -1e30)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(kb <= kmax_ref[pl.program_id(1)])
    def _tile():
        q, k, v = q_ref[0, 0], k_ref[0], v_ref[0]                   # [n_rep*bq, d], [bk, d]
        bq = q.shape[0] // n_rep
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        ok = mask_ref[0].astype(jnp.float32) > 0                    # [bq, bk], one mask for the group's heads
        s = jnp.where(ok[None], s.reshape(n_rep, bq, -1), -1e30).reshape(n_rep * bq, -1)
        m_new = jnp.maximum(m_sc[...], s.max(axis=1, keepdims=True))
        p = jnp.where(s > -1e29, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_sc[...] - m_new)
        l_sc[...] = l_sc[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(kb == pl.num_programs(2) - 1)
    def _done():
        o_ref[0, 0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k"))
def masked_prefill_attention(q, k, v, mask, n_keys, *, block_q: int = 128, block_k: int = 512):
    """q [Hkv, G, T, d]; k, v [Hkv, Tk, d] (a request's staged keys, the
    chunk's own among them); mask int8 [Hkv, T, Tk]; n_keys [] int32, the keys
    that exist (tiles wholly past them are neither fetched nor computed).
    Returns [Hkv, G, T, d]. Softmax over the keys the mask lets through, in
    float32; the group's G query heads share a mask row, so a tile's scores
    are one [G x block_q, block_k] product."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hkv, g, t, d = q.shape
    tk = k.shape[1]
    bq, bk = min(block_q, t), min(block_k, tk)
    if t % bq or tk % bk:
        raise ValueError(f"{t} queries / {tk} keys do not divide into tiles of {bq} / {bk}")
    nq, nk = t // bq, tk // bk
    # a q tile's rows: head r's bq queries, then head r+1's
    qt = q.reshape(hkv, g, nq, bq, d).transpose(0, 2, 1, 3, 4).reshape(hkv, nq, g * bq, d)
    last = jnp.broadcast_to(jnp.maximum(n_keys - 1, 0) // bk, (nq,)).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(hkv, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g * bq, d), lambda h, i, j, last: (h, i, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j, last: (h, jnp.minimum(j, last[i]), 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j, last: (h, jnp.minimum(j, last[i]), 0)),
            pl.BlockSpec((1, bq, bk), lambda h, i, j, last: (h, i, jnp.minimum(j, last[i]))),
        ],
        out_specs=pl.BlockSpec((1, 1, g * bq, d), lambda h, i, j, last: (h, i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((g * bq, 1), jnp.float32), pltpu.VMEM((g * bq, 1), jnp.float32),
                        pltpu.VMEM((g * bq, d), jnp.float32)],
    )
    o = pl.pallas_call(
        functools.partial(_flash_masked_kernel, n_rep=g, scale=d ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret(),
        cost_estimate=pl.CostEstimate(flops=4 * hkv * g * t * tk * d, transcendentals=hkv * g * t * tk,
                                      bytes_accessed=hkv * t * tk + 2 * hkv * nq * tk * d * k.dtype.itemsize),
    )(last, qt, k, v, mask)
    return o.reshape(hkv, nq, g, bq, d).transpose(0, 2, 1, 3, 4).reshape(hkv, g, t, d)


def _block_select_kernel(tiles_ref, q_ref, kc_ref, nv_ref, o_ref, kth_ref, m_sc, l_sc, tail_sc, pooled_sc, *,
                         n_rep, extra, init_blocks, topk, scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    j, tiles = pl.program_id(2), tiles_ref[pl.program_id(1)]
    n_tiles, rows, tb = pooled_sc.shape
    per, bq = kc_ref.shape[2], rows // n_rep

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, -1e30)
        l_sc[...] = jnp.zeros_like(l_sc)
        tail_sc[...] = jnp.full_like(tail_sc, -1e30)

    @pl.when(j < tiles)
    def _tile():
        # column c of phase o is compressed key (j*tb + c)*per + o: a block's own `per` keys are a column
        q, nv = q_ref[0, 0], nv_ref[0]                              # [rows, d], [rows, 1]
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, tb), 1)
        s = []
        for o in range(per):
            so = jax.lax.dot_general(q, kc_ref[0, 0, o], (((1,), (1,)), ((), ())), precision=_HI,
                                     preferred_element_type=jnp.float32) * scale
            s.append(jnp.where((j * tb + lane) * per + o < nv, so, -1e30))
        best = functools.reduce(jnp.maximum, s)
        # the block also overlaps the last `extra` keys of the block before it: the column to the left, the
        # same numbers (not a second product: blocks that share a key must tie to the bit)
        tail = functools.reduce(jnp.maximum, s[per - extra:])
        left = jnp.where(lane == 0, pltpu.roll(tail_sc[...], 1, 1), pltpu.roll(tail, 1, 1))
        pooled_sc[j] = jnp.maximum(best, left)
        tail_sc[...] = tail
        # the row's maximum and sum, a lane at a time: put together when the last tile is through
        m_old = m_sc[...]
        m_new = jnp.maximum(m_old, best)
        seen = sum(jnp.where(so > -1e29, jnp.exp(so - m_new), 0.0) for so in s)
        l_sc[...] = l_sc[...] * jnp.exp(m_old - m_new) + seen
        m_sc[...] = m_new

    @pl.when(j == n_tiles - 1)
    def _done():
        m = m_sc[...].max(axis=1, keepdims=True)
        l = jnp.maximum((l_sc[...] * jnp.exp(m_sc[...] - m)).sum(axis=1, keepdims=True), 1e-30)
        nv = nv_ref[0, :bq]
        every = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape[1:], 1)
        o_ref[0] = jnp.where(every < init_blocks, jnp.inf, -1.0)     # what a tile past the context holds
        for jj in range(n_tiles):
            @pl.when(jj < tiles)
            def _scores(jj=jj):
                b = jj * tb + jax.lax.broadcasted_iota(jnp.int32, (bq, tb), 1)
                pooled = pooled_sc[jj]
                p = jnp.where(pooled > -1e29, jnp.exp(pooled - m), 0.0) / l
                score = jnp.where(jnp.maximum(b * per - extra, 0) < nv, p.reshape(n_rep, bq, tb).sum(axis=0), -1.0)
                o_ref[0, :, jj * tb:(jj + 1) * tb] = jnp.where(b < init_blocks, jnp.inf, score)

        kth_ref[0] = kth_largest(o_ref[0], topk)


@functools.partial(jax.jit, static_argnames=("spec", "block_q", "block_b"))
def block_select(q, kc, n_ctx, spec: SparseSpec, *, block_q: int = 64, block_b: int = 128):
    """``block_scores`` for the rows of a prefill chunk, fused: q [T, Hkv, G, d],
    kc [nK, Hkv, d], n_ctx [T] -> (float32 [T, Hkv, nB], and its ``topk``-th
    largest [T, Hkv, 1], found while the row is in VMEM: `chosen_blocks`
    takes both). A tile is `block_b` blocks' compressed keys against `block_q`
    queries' G heads; a row's scores exist a tile at a time. Tiles wholly past
    the kernels that end inside the tile's largest context are neither fetched
    nor computed."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, hkv, g, d = q.shape
    nK = kc.shape[0]
    per, extra = spec.block // spec.stride, spec.kernel // spec.stride - 1
    if not 0 < extra <= per:
        raise ValueError(f"a kernel of {spec.kernel} every {spec.stride} must overlap the block of {spec.block} before "
                         "its own, and no other")
    nB = nK // per
    bq, tb = math.gcd(block_q, t), min(block_b, nB)
    nq, nt = t // bq, -(-nB // tb)
    # a q tile's rows: head r's bq queries, then head r+1's
    qt = q.astype(jnp.float32).reshape(nq, bq, hkv, g, d).transpose(2, 0, 3, 1, 4).reshape(hkv, nq, g * bq, d)
    # a kc tile: [per, tb, d], phase o of block b is compressed key b*per + o
    kt = jnp.pad(kc.astype(jnp.float32)[:nB * per], ((0, (nt * tb - nB) * per), (0, 0), (0, 0)))
    kt = kt.reshape(nt, tb, per, hkv, d).transpose(3, 0, 2, 1, 4)
    n_valid = _n_valid(n_ctx, spec).astype(jnp.int32)
    nv = jnp.broadcast_to(n_valid.reshape(nq, 1, bq, 1), (nq, g, bq, 1)).reshape(nq, g * bq, 1)
    # the tiles that hold a block some finished kernel overlaps, a q tile
    most = n_valid.reshape(nq, bq).max(axis=1)
    tiles = jnp.where(most > 0, (most + extra - 1) // per // tb + 1, 0).astype(jnp.int32)

    def key_tile(h, i, j, tiles):
        return (h, jnp.minimum(j, jnp.maximum(tiles[i] - 1, 0)), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(hkv, nq, nt),
        in_specs=[
            pl.BlockSpec((1, 1, g * bq, d), lambda h, i, j, tiles: (h, i, 0, 0)),
            pl.BlockSpec((1, 1, per, tb, d), key_tile),
            pl.BlockSpec((1, g * bq, 1), lambda h, i, j, tiles: (i, 0, 0)),
        ],
        out_specs=[pl.BlockSpec((1, bq, nt * tb), lambda h, i, j, tiles: (h, i, 0)),
                   pl.BlockSpec((1, bq, 1), lambda h, i, j, tiles: (h, i, 0))],
        scratch_shapes=[pltpu.VMEM((g * bq, tb), jnp.float32), pltpu.VMEM((g * bq, tb), jnp.float32),
                        pltpu.VMEM((g * bq, tb), jnp.float32), pltpu.VMEM((nt, g * bq, tb), jnp.float32)],
    )
    score, kth = pl.pallas_call(
        functools.partial(_block_select_kernel, n_rep=g, extra=extra, init_blocks=spec.init_blocks,
                          topk=min(spec.topk, nB), scale=d ** -0.5),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((hkv, t, nt * tb), jnp.float32),
                   jax.ShapeDtypeStruct((hkv, t, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                                             vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret(),
        name="block_select",
        cost_estimate=pl.CostEstimate(flops=2 * t * hkv * g * nB * per * d, transcendentals=2 * t * hkv * g * nB * per,
                                      bytes_accessed=4 * (t * hkv * g * d + nq * hkv * nB * per * d + t * hkv * nB)),
    )(tiles, qt, kt, nv)
    return score.transpose(1, 0, 2)[:, :, :nB], kth.transpose(1, 0, 2)


# -- a learned indexer that chooses single positions (DSA) ----------------------------------------
#
# A full layer of a model with an indexer reads, for the query at position t, the `topk` positions s <= t of
# largest I[t, s] = sum_h w[t, h] relu(qI[t, h] . kI[s]): Hi index heads, ONE di-wide index key a position.
# Scores are float32 (a position is read or it is not). The choice is a threshold, found with no sort: a
# float32's bits, made to order as a signed integer orders (`order_keys`), and the `topk`-th largest of a row
# by the bit-search `kth_largest` runs on block scores, here over all 32 bits of a key (`kth_largest_key`,
# and inside `index_select` for the rows of a prefill chunk). A score equal to the threshold is read too
# (sums of 64 float32 products: a tie is an accident). Two forms of the chosen set: a tile-major int8 mask
# for a prefill chunk (ops/latent_attention.latent_prefill_attention), and for a decode step a list of
# positions a slot (`compact_chosen`: sort-free too), whose rows the caller gathers.

KEY_MIN = -2 ** 31   # the key of a position a query may not read: below every score's


def order_keys(score: jax.Array) -> jax.Array:
    """float32 -> int32 that orders as the floats do (negative floats' bits are flipped)."""
    bits = jax.lax.bitcast_convert_type(score.astype(jnp.float32), jnp.int32)
    return bits ^ (jnp.right_shift(bits, 31) & jnp.int32(0x7FFFFFFF))


def key_scores(keys: jax.Array) -> jax.Array:
    """`order_keys` undone (the map is its own inverse): for tests that read scores."""
    return jax.lax.bitcast_convert_type(keys ^ (jnp.right_shift(keys, 31) & jnp.int32(0x7FFFFFFF)), jnp.float32)


def _kth_key(count_ge, shape, k: int) -> jax.Array:
    """The largest int32 t with `count_ge(t) >= k` entries at or above it, or KEY_MIN + 1 where fewer than k
    entries are readable at all (then every readable entry is at or above it, and no unreadable one):
    built from the sign bit down, in offset binary. `count_ge(t)` -> float32 counts shaped `shape`."""
    t = jnp.where(count_ge(jnp.zeros(shape, jnp.int32)) >= k, 0, KEY_MIN).astype(jnp.int32)

    def bit(i, t):
        cand = t | jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(count_ge(cand) >= k, cand, t)

    return jnp.maximum(jax.lax.fori_loop(0, 31, bit, t), KEY_MIN + 1)


def kth_largest_key(keys: jax.Array, k: int) -> jax.Array:
    """int32 [..., n] (`order_keys`; KEY_MIN where unreadable) -> [..., 1]: the threshold a row's `k`
    largest keys lie at or above. Exact, no sort; plain array code (a decode step's rows)."""
    return _kth_key(lambda t: (keys >= t).astype(jnp.float32).sum(axis=-1, keepdims=True), (*keys.shape[:-1], 1), k)


def _index_prefill_kernel(pos_ref, q_ref, w_ref, k_ref, o_ref, *, heads):
    from jax.experimental import pallas as pl

    i, j = pl.program_id(0), pl.program_id(1)
    bq, bk = o_ref.shape[1], o_ref.shape[2]
    pos0 = pos_ref[0]
    last = (pos0 + (i + 1) * bq - 1) // bk

    @pl.when(j <= last)
    def _tile():
        s = jax.lax.dot_general(q_ref[...], k_ref[...], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        score = (jnp.maximum(s, 0.0) * w_ref[...]).reshape(bq, heads, bk).sum(axis=1)           # [bq, bk] float32
        col = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        qpos = pos0 + i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        o_ref[0] = jnp.where(col <= qpos, order_keys(score), KEY_MIN)

    @pl.when(j > last)
    def _past():
        o_ref[0] = jnp.full((bq, bk), KEY_MIN, jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k"))
def index_scores_prefill(qi, w, ki, pos0, *, block_q: int = 32, block_k: int = 512):
    """The index scores of a prefill chunk's queries against a request's staged
    index keys, as ordered keys. qi [T, Hi, di], w [T, Hi] float32, ki [Tk, di]
    (the chunk's own keys among them, at positions pos0 ..), pos0 [] int32.
    Returns int32 [Tk // bk, T, bk], tile-major: `order_keys(I[t, s])` where s
    <= pos0 + t, KEY_MIN elsewhere. A tile of keys wholly past its queries is
    neither fetched nor multiplied."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, hi, di = qi.shape
    tk = ki.shape[0]
    bq, bk = math.gcd(block_q, t), math.gcd(block_k, tk)
    nq, nk = t // bq, tk // bk

    def key_tile(i, j, pos):
        return (jnp.minimum(j, (pos[0] + (i + 1) * bq - 1) // bk), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nq, nk),
        in_specs=[
            pl.BlockSpec((bq * hi, di), lambda i, j, pos: (i, 0)),
            pl.BlockSpec((bq * hi, 1), lambda i, j, pos: (i, 0)),
            pl.BlockSpec((bk, di), key_tile),
        ],
        out_specs=pl.BlockSpec((1, bq, bk), lambda i, j, pos: (j, i, 0)),
    )
    return pl.pallas_call(
        functools.partial(_index_prefill_kernel, heads=hi),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nk, t, bk), jnp.int32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                             vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret(),
        name="index_scores_prefill",
        cost_estimate=pl.CostEstimate(flops=2 * t * hi * tk * di, transcendentals=0,
                                      bytes_accessed=nq * tk * di * ki.dtype.itemsize + 4 * t * tk),
    )(jnp.reshape(pos0, (1,)).astype(jnp.int32), qi.reshape(t * hi, di), w.astype(jnp.float32).reshape(t * hi, 1), ki)


def _index_select_kernel(n_ref, keys_ref, mask_ref, *, topk):
    n = n_ref[0]
    rows = keys_ref.shape[1]

    def count_ge(t):
        return jax.lax.fori_loop(
            0, n, lambda j, c: c + (keys_ref[j] >= t).astype(jnp.float32).sum(axis=-1, keepdims=True),
            jnp.zeros((rows, 1), jnp.float32))

    kth = _kth_key(count_ge, (rows, 1), topk)

    def write(j, carry):
        mask_ref[j] = jnp.where(keys_ref[j] >= kth, 1, 0).astype(jnp.int8)
        return carry

    jax.lax.fori_loop(0, n, write, 0)


@functools.partial(jax.jit, static_argnames=("topk", "block_q"))
def index_select(keys, n_keys, *, topk: int, block_q: int = 32):
    """keys int32 [nk, T, bk] (`index_scores_prefill`), n_keys [] int32 (the
    positions that exist: the chunk's end) -> int8 [nk, T, bk]: 1 at a query's
    `topk` largest keys (every readable position where there are fewer). Tiles at
    or past ceil(n_keys / bk) are neither counted nor written: the attention that
    reads the mask does not fetch them either."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nk, t, bk = keys.shape
    bq = math.gcd(block_q, t)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t // bq,),
        in_specs=[pl.BlockSpec((nk, bq, bk), lambda i, n: (0, i, 0))],
        out_specs=pl.BlockSpec((nk, bq, bk), lambda i, n: (0, i, 0)),
    )
    tiles = jnp.minimum((n_keys + bk - 1) // bk, nk)
    return pl.pallas_call(
        functools.partial(_index_select_kernel, topk=topk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nk, t, bk), jnp.int8),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",), vmem_limit_bytes=96 * 1024 * 1024),
        interpret=interpret(),
        name="index_select",
        cost_estimate=pl.CostEstimate(flops=3 * 32 * nk * t * bk, transcendentals=0, bytes_accessed=5 * nk * t * bk),
    )(jnp.reshape(tiles, (1,)).astype(jnp.int32), keys)


def _index_decode_kernel(layer_ref, pages_ref, table_ref, q_ref, w_ref, k_ref, o_ref):
    from jax.experimental import pallas as pl

    s, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j < pages_ref[s])
    def _page():
        sc = jax.lax.dot_general(q_ref[0], k_ref[0, 0], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        o_ref[0, 0] = (jnp.maximum(sc, 0.0) * w_ref[0]).sum(axis=0, keepdims=True)             # [1, page_len]


@jax.jit
def index_scores_decode(qi, w, pool, layer, page_table, pool_len):
    """One query a slot against the slot's index keys in the page pool. qi [S,
    Hi, di], w [S, Hi] float32, pool [L, P, page_len, di], layer [] int32,
    page_table [S, max_pages], pool_len [S] (positions of the slot that lie in the
    pool). Returns float32 [S, max_pages * page_len]: I[s, position]; what lies at
    or past pool_len[s] is not computed and holds anything (the caller masks it).
    A slot's pages are read once, its live ones only."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, hi, di = qi.shape
    page_len, max_pages = pool.shape[2], page_table.shape[1]
    pages = ((pool_len + page_len - 1) // page_len).astype(jnp.int32)

    def page(s, j, layer, pages, table):
        return (layer[0], table[s, jnp.minimum(j, jnp.maximum(pages[s] - 1, 0))], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, max_pages),
        in_specs=[
            pl.BlockSpec((1, hi, di), lambda s, j, *_: (s, 0, 0)),
            pl.BlockSpec((1, hi, 1), lambda s, j, *_: (s, 0, 0)),
            pl.BlockSpec((1, 1, page_len, di), page),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, page_len), lambda s, j, *_: (s, j, 0, 0)),
    )
    out = pl.pallas_call(
        _index_decode_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, max_pages, 1, page_len), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret(),
        name="index_scores_decode",
        cost_estimate=pl.CostEstimate(flops=2 * S * hi * max_pages * page_len * di, transcendentals=0,
                                      bytes_accessed=S * max_pages * page_len * (di * pool.dtype.itemsize + 4)),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), pages, page_table.astype(jnp.int32), qi,
      w.astype(jnp.float32)[:, :, None], pool)
    return out.reshape(S, max_pages * page_len)


def compact_chosen(chosen: jax.Array, k: int, lanes: int = 128):
    """chosen bool [S, N] with at most k set a row -> (idx int32 [S, k]: the set
    positions ascending, then anything; count [S]). No sort and no scatter: the
    j-th set position lies in the first block of `lanes` whose running count
    passes j (a comparison against the blocks' running counts), that block's
    bits are fetched by a one-hot product, and the lane is the first whose
    running count inside the block passes what is left of j."""
    S, N = chosen.shape
    if N % lanes:
        raise ValueError(f"{N} positions are not whole blocks of {lanes}")
    B = N // lanes
    bits = chosen.reshape(S, B, lanes)
    per_block = bits.sum(axis=-1, dtype=jnp.int32)
    running = jnp.cumsum(per_block, axis=-1)                                      # [S, B] inclusive
    j = jnp.arange(k, dtype=jnp.int32)
    before = running[:, None, :] <= j[None, :, None]                              # [S, k, B]: blocks wholly before the j-th
    block = jnp.minimum(before.sum(axis=-1, dtype=jnp.int32), B - 1)
    left = j[None, :] - jnp.where(before, per_block[:, None, :], 0).sum(axis=-1)  # the j-th is the `left`-th of its block
    one_hot = (jnp.arange(B, dtype=jnp.int32)[None, None, :] == block[:, :, None]).astype(jnp.bfloat16)
    row = jnp.einsum("skb,sbl->skl", one_hot, bits.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
    inside = jnp.cumsum(row, axis=-1)                                             # small whole numbers: exact
    lane = (inside <= left[:, :, None].astype(jnp.float32)).sum(axis=-1, dtype=jnp.int32)
    return block * lanes + jnp.minimum(lane, lanes - 1), running[:, -1]
