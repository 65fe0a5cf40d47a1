"""Latent attention (MLA): keys and values that exist as one low-rank row a position.

A layer caches, for ALL its heads, one row a position: the normed latent ``c``
(``r`` wide) and beside it the one rotary key ``kr`` (``dr`` wide) the heads
share. Head h's key is ``[c W_uk[h] ; kr]`` and its value ``c W_uv[h]``. Two forms
compute the same attention:

- EXPANDED, for a prefill chunk (``latent_prefill_attention``): keys and values
  are built from the latent rows a tile at a time inside the kernel, for a group
  of heads that share the tile's read, under a mask handed in tile by tile (the
  indexer's choice on a full layer, the band on a window layer). Nothing of
  ``[heads, positions, width]`` ever exists in HBM.
- ABSORBED, for a decode step (``latent_rows_attention``): the query is folded
  through ``W_uk`` (``q_nope W_uk[h]^T``, ``r`` wide), scores are taken against
  the latent rows themselves, the weighted sum of rows goes through ``W_uv``
  after the sum. All heads share one read of a row: (r + dr) x 2 bytes against
  heads x (2r + dr) x 2 operations. The rows are a block a slot: the positions
  an indexer chose, gathered (models/dots3_note.py), or a window layer's ring
  whole, with a validity a row; and the decode chunk's own rows beside them.
  A DENSE layer reads every row of a slot's context (``latent_paged_decode``):
  the same products over the slot's pages of the latent pool, walked through
  the page table with a running softmax, a page's fetch in flight while the
  page before it is computed from.

Softmax statistics are float32 in both. `tests/test_dots3_note.py` holds the two
forms equal.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tony_tpu.ops.interpret import interpret


def divisor(n: int, most: int, align: int = 8) -> int:
    """The largest block <= most that divides n and is a multiple of `align` (n itself if none)."""
    for b in range(min(most, n), 0, -1):
        if n % b == 0 and b % align == 0:
            return b
    return n


def tile_major(mask: jax.Array, block_k: int) -> jax.Array:
    """[T, Tk] -> [Tk // block_k, T, block_k]: the layout the prefill kernel reads a mask in."""
    t, tk = mask.shape
    return mask.reshape(t, tk // block_k, block_k).transpose(1, 0, 2)


def _latent_prefill_kernel(first_ref, last_ref, qn_ref, qr_ref, ckr_ref, wuk_ref, wuv_ref, mask_ref, o_ref,
                           m_sc, l_sc, acc_sc, *, r, scale):
    from jax.experimental import pallas as pl

    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, -1e30)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when((j >= first_ref[i]) & (j <= last_ref[i]))
    def _tile():
        ckr = ckr_ref[...]                                          # [bk, row]: read once for the group's heads
        c, kr = ckr[:, :r], ckr[:, r:r + qr_ref.shape[2]]
        ok = mask_ref[0].astype(jnp.float32) > 0                    # [bq, bk]: one mask for every head

        def head(g, carry):
            kn = jnp.dot(c, wuk_ref[g], preferred_element_type=jnp.float32).astype(c.dtype)       # [bk, dn]
            s = (jax.lax.dot_general(qn_ref[g], kn, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(qr_ref[g], kr, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)) * scale
            s = jnp.where(ok, s, -1e30)
            m_old = m_sc[g]
            m_new = jnp.maximum(m_old, s.max(axis=1, keepdims=True))
            p = jnp.where(s > -1e29, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_old - m_new)
            l_sc[g] = l_sc[g] * alpha + p.sum(axis=1, keepdims=True)
            v = jnp.dot(c, wuv_ref[g], preferred_element_type=jnp.float32).astype(c.dtype)        # [bk, dv]
            acc_sc[g] = acc_sc[g] * alpha + jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_sc[g] = m_new
            return carry

        jax.lax.fori_loop(0, qn_ref.shape[0], head, 0)

    @pl.when(j == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_q", "head_group"))
def latent_prefill_attention(qn, qr, ckr, w_uk, w_uv, mask, first, last, *, scale: float, block_q: int = 1024,
                             head_group: int = 8):
    """The expanded form over a request's staged latent rows. qn [H, T, dn], qr
    [H, T, dr] (rotated); ckr [Tk, row >= r + dr] (the latent, then the rotated
    rope key, then whatever fills the row; the chunk's own rows among them); w_uk [H, r, dn]; w_uv [H, r, dv]; mask int8
    [Tk // bk, T, bk] (`tile_major`): 1 where the query reads the position, the
    same for every head; first, last [T // bq] int32: the key tiles of each
    query tile that hold a visible pair (tiles outside are neither fetched nor
    computed). Returns [H, T, dv]. Every query must see a position."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H, t, dn = qn.shape
    nk, _, bk = mask.shape
    r, dv, dr = w_uk.shape[1], w_uv.shape[2], qr.shape[2]
    bq, G = divisor(t, block_q), divisor(H, head_group, 1)
    nq = t // bq
    row = ckr.shape[1]
    if ckr.shape[0] != nk * bk or row < r + dr or mask.shape[1] != t or first.shape != (nq,):
        raise ValueError(f"rows {ckr.shape}, mask {mask.shape}, first {first.shape} for {t} queries in tiles of {bq}, "
                         f"latent {r} + {dr}")

    def key_tile(h, i, j, first, last):
        return jnp.clip(j, first[i], last[i])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(H // G, nq, nk),
        in_specs=[
            pl.BlockSpec((G, bq, dn), lambda h, i, j, first, last: (h, i, 0)),
            pl.BlockSpec((G, bq, dr), lambda h, i, j, first, last: (h, i, 0)),
            pl.BlockSpec((bk, row), lambda h, i, j, first, last: (key_tile(h, i, j, first, last), 0)),
            pl.BlockSpec((G, r, dn), lambda h, i, j, first, last: (h, 0, 0)),
            pl.BlockSpec((G, r, dv), lambda h, i, j, first, last: (h, 0, 0)),
            pl.BlockSpec((1, bq, bk), lambda h, i, j, first, last: (key_tile(h, i, j, first, last), i, 0)),
        ],
        out_specs=pl.BlockSpec((G, bq, dv), lambda h, i, j, first, last: (h, i, 0)),
        scratch_shapes=[pltpu.VMEM((G, bq, 1), jnp.float32), pltpu.VMEM((G, bq, 1), jnp.float32),
                        pltpu.VMEM((G, bq, dv), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_latent_prefill_kernel, r=r, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, t, dv), qn.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                                             vmem_limit_bytes=96 * 1024 * 1024),
        interpret=interpret(),
        name="latent_prefill",
        cost_estimate=pl.CostEstimate(flops=2 * H * t * nk * bk * (dn + dr + dv) + 2 * (H // G) * nq * nk * bk * G * r * (dn + dv),
                                      transcendentals=H * t * nk * bk,
                                      bytes_accessed=(H // G) * nq * nk * bk * (row * ckr.dtype.itemsize + bq)),
    )(first.astype(jnp.int32), last.astype(jnp.int32), qn, qr, ckr, w_uk, w_uv, mask)


def _latent_rows_kernel(layer_ref, q_ref, rows_ref, valid_ref, extra_ref, extra_valid_ref, o_ref, *, r, scale):
    q, rows, extra = q_ref[0], rows_ref[0, 0], extra_ref[0]                 # [H, W], [R, W], [E, W]
    contract = (((1,), (1,)), ((), ()))
    s1 = jax.lax.dot_general(q, rows, contract, preferred_element_type=jnp.float32) * scale
    s2 = jax.lax.dot_general(q, extra, contract, preferred_element_type=jnp.float32) * scale
    s1 = jnp.where(valid_ref[0] > 0, s1, -1e30)                              # [1, R] a row's validity, every head's
    s2 = jnp.where(extra_valid_ref[0] > 0, s2, -1e30)
    m = jnp.maximum(s1.max(axis=1, keepdims=True), s2.max(axis=1, keepdims=True))
    p1 = jnp.where(s1 > -1e29, jnp.exp(s1 - m), 0.0)
    p2 = jnp.where(s2 > -1e29, jnp.exp(s2 - m), 0.0)
    l = p1.sum(axis=1, keepdims=True) + p2.sum(axis=1, keepdims=True)
    acc = (jnp.dot(p1.astype(rows.dtype), rows[:, :r], preferred_element_type=jnp.float32)
           + jnp.dot(p2.astype(extra.dtype), extra[:, :r], preferred_element_type=jnp.float32))
    o_ref[0] = acc / jnp.maximum(l, 1e-30)


@functools.partial(jax.jit, static_argnames=("r", "scale", "name"))
def latent_rows_attention(q, rows, layer, valid, extra, extra_valid, *, r: int, scale: float, name: str = "latent_decode"):
    """The absorbed form over a block of latent rows a slot. q [S, H, row]
    (the query folded through W_uk, then its rotated rope part, zeros where a row
    holds filling); rows [L, S, R, row] (slot s reads rows[layer, s]: positions
    gathered for it, or its ring); valid [S, R]: whether a row is read; extra [S,
    E, row], extra_valid [S, E]:
    the decode chunk's own rows. Returns float32 [S, H, r]: each head's weighted
    sum of LATENTS (W_uv is the caller's, after the sum). One read of a row
    serves every head. A slot must read a row (its current one is in `extra`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, W = q.shape
    R, E = rows.shape[2], extra.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, H, W), lambda s, layer: (s, 0, 0)),
            pl.BlockSpec((1, 1, R, W), lambda s, layer: (layer[0], s, 0, 0)),
            pl.BlockSpec((1, 1, R), lambda s, layer: (s, 0, 0)),
            pl.BlockSpec((1, E, W), lambda s, layer: (s, 0, 0)),
            pl.BlockSpec((1, 1, E), lambda s, layer: (s, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, r), lambda s, layer: (s, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_latent_rows_kernel, r=r, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, r), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",), vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret(),
        name=name,
        cost_estimate=pl.CostEstimate(flops=2 * S * H * (R + E) * (W + r), transcendentals=S * H * (R + E),
                                      bytes_accessed=S * (R + E) * W * rows.dtype.itemsize),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), q, rows, valid.astype(jnp.float32)[:, None, :], extra,
      extra_valid.astype(jnp.float32)[:, None, :])


def _latent_paged_kernel(len_ref, step_ref, pt_ref, layer_ref, q_ref, extra_ref, pool_hbm, o_ref, buf, sem, start_ref,
                         *, r, scale, page_len):
    """One grid instance a slot, the slots in order: ops/decode_attention._kernel's page walk over
    rows that have no head axis. The two page buffers, their DMA semaphores and `start_ref` are the
    call's scratch, alive over the whole grid: while a slot computes from its last page it starts the
    fetch of the next slot's first, and that slot waits for it and starts nothing of its own (both
    sides decide by one predicate over the same prefetched scalars). A slot with nothing in the pool
    neither receives nor hands on."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_i, S = pl.program_id(0), pl.num_programs(0)
    n = len_ref[s_i]
    c1 = pl.cdiv(n, page_len)
    before, after = jnp.maximum(s_i - 1, 0), jnp.minimum(s_i + 1, S - 1)
    reads = c1 > 0
    handed = (s_i > 0) & (len_ref[before] > 0) & reads
    hands_on = (s_i + 1 < S) & (len_ref[after] > 0) & reads
    first = jnp.where(handed, start_ref[0], 0)           # the buffer the predecessor put this slot's first page in

    def fetch(b, s, c):
        return pltpu.make_async_copy(pool_hbm.at[layer_ref[0], pt_ref[s, c]], buf.at[b], sem.at[b])

    @pl.when(reads & jnp.logical_not(handed))
    def _warm_up():
        fetch(first, s_i, 0).start()

    q = q_ref[0]                                          # [H, row], the pool's dtype
    contract = (((1,), (1,)), ((), ()))

    def page(c, carry):
        m, l, acc = carry
        cur = (first + c) % 2
        more = c + 1 < c1

        @pl.when(more | hands_on)
        def _():
            fetch(1 - cur, jnp.where(more, s_i, after), jnp.where(more, c + 1, 0)).start()

        fetch(cur, s_i, c).wait()
        rows = buf[cur]                                   # [page_len, row]: read once for every head
        s = jax.lax.dot_general(q, rows, contract, preferred_element_type=jnp.float32) * scale
        pos = c * page_len + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < n, s, -1e30)                  # a last page's rows past the slot's length
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=1, keepdims=True)
        acc = acc * alpha + jnp.dot(p.astype(rows.dtype), rows[:, :r], preferred_element_type=jnp.float32)
        return m_new, l, acc

    H = q.shape[0]
    m, l, acc = jax.lax.fori_loop(0, c1, page, (jnp.full((H, 1), -1e30, jnp.float32), jnp.zeros((H, 1), jnp.float32),
                                                jnp.zeros((H, r), jnp.float32)))

    @pl.when(hands_on)
    def _():
        start_ref[0] = (first + c1) % 2

    # the chunk's own rows (its earlier steps' and the current token's, at index `step`): one softmax
    # step joined to the pages' running m, l, acc, as `_chunk_fold` joins a key-value chunk's
    extra = extra_ref[0]                                  # [E, row]
    s2 = jax.lax.dot_general(q, extra, contract, preferred_element_type=jnp.float32) * scale
    shown = jax.lax.broadcasted_iota(jnp.int32, s2.shape, 1) <= step_ref[0]
    s2 = jnp.where(shown, s2, -1e30)
    m_new = jnp.maximum(m, s2.max(axis=1, keepdims=True))
    p2 = jnp.where(shown, jnp.exp(s2 - m_new), 0.0)
    alpha = jnp.exp(m - m_new)
    l = l * alpha + p2.sum(axis=1, keepdims=True)
    acc = acc * alpha + jnp.dot(p2.astype(extra.dtype), extra[:, :r], preferred_element_type=jnp.float32)
    o_ref[0] = acc / l


@functools.partial(jax.jit, static_argnames=("r", "scale"))
def latent_paged_decode(q, pool, layer, lengths, page_table, extra, step, *, r: int, scale: float):
    """The absorbed form over EVERY row of a slot's context, through the page
    table. q [S, H, row] (folded through W_uk, then the rotated rope part, zeros
    where a row holds filling; any scale a position asks of the query folded
    in); pool [L, P, page_len, row]: the WHOLE latent pool with `layer` [] the
    index of this layer's (a slice handed to a Mosaic call would be a copy of
    it); lengths [S]: the rows of slot s that lie in the pool, logical page j of
    them in physical page page_table[s, j]; extra [S, E, row]: the decode
    chunk's own rows, of which those at index <= `step` [] are read (the
    current token's is at `step`). Returns float32 [S, H, r]: each head's
    weighted sum of LATENTS over pool rows [0, lengths[s]) and the shown extra
    rows (W_uv is the caller's, after the sum). HBM traffic is the slots' live
    pages, each read once for all heads: ceil(len / page_len) * page_len * row
    * itemsize a slot. A slot always reads a row (its current one)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, W = q.shape
    if pool.ndim != 4 or pool.shape[3] != W or extra.shape[2] != W:
        raise ValueError(f"pool {pool.shape} / extra {extra.shape}: the whole pool [L, P, page_len, row] with a layer "
                         f"index and rows [S, E, row], row = the query's {W}")
    page_len, E = pool.shape[2], extra.shape[1]
    live_pages = -(-lengths.shape[0] * page_table.shape[1] // 2)           # the estimate's guess: half the table live
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # lengths [S], step [1], page_table [S, max_pages], layer [1]
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, H, W), lambda s, LN, ST, PT, LY: (s, 0, 0)),
            pl.BlockSpec((1, E, W), lambda s, LN, ST, PT, LY: (s, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),            # the pool stays in HBM, all layers of it
        ],
        out_specs=pl.BlockSpec((1, H, r), lambda s, LN, ST, PT, LY: (s, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, page_len, W), pool.dtype), pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_latent_paged_kernel, r=r, scale=scale, page_len=page_len),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, r), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret(),
        name="latent_paged_decode",
        cost_estimate=pl.CostEstimate(flops=2 * H * (live_pages * page_len + S * E) * (W + r),
                                      transcendentals=H * (live_pages * page_len + S * E),
                                      bytes_accessed=(live_pages * page_len + S * E) * W * pool.dtype.itemsize),
    )(lengths.astype(jnp.int32), jnp.reshape(step, (1,)).astype(jnp.int32), page_table.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), q, extra, pool)


def expanded_attention(qn, qr, ckr, w_uk, w_uv, seen, *, scale: float):
    """The expanded form in plain array code: the tests' oracle for both kernels.
    qn [H, T, dn], qr [H, T, dr], ckr [Tk, row], seen bool [T, Tk] -> float32 [H, T, dv]."""
    r = w_uk.shape[1]
    hi = jax.lax.Precision.HIGHEST
    c, kr = ckr[:, :r].astype(jnp.float32), ckr[:, r:r + qr.shape[2]].astype(jnp.float32)
    kn = jnp.einsum("sr,hrd->hsd", c, w_uk.astype(jnp.float32), precision=hi)
    v = jnp.einsum("sr,hrd->hsd", c, w_uv.astype(jnp.float32), precision=hi)
    s = (jnp.einsum("htd,hsd->hts", qn.astype(jnp.float32), kn, precision=hi)
         + jnp.einsum("htd,sd->hts", qr.astype(jnp.float32), kr, precision=hi)) * scale
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hts,hsd->htd", p, v, precision=hi)
