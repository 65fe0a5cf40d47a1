"""Decode attention for the serving engine (Pallas TPU): one new token a slot over that slot's cache.

Four calls. ``paged_decode_attention`` walks a slot's pages of a page pool and
``ragged_decode_attention`` the slabs of a dense cache a slot (the same body,
``_kernel``); ``ring_decode_attention`` reads a window layer's ring whole;
``sparse_paged_decode_attention`` reads the pages a learned score chose. All
read each slot's cache RAGGED: slot s costs its own ``lengths[s]`` positions
of HBM traffic, not the longest slot's bucket, so a step's KV traffic is
Σ_s len_s. ``lengths`` counts CACHE positions only: the current token's K/V
arrive via ``cur_k``/``cur_v`` (the cache is read-only here and the engine
writes it once a chunk), and the positions a decode chunk has produced but not
yet written arrive in a staged block. Sliding-window models read the cache
from ``max(0, len + 1 - window)``: whole slabs below the window are skipped.

Whose keys a call reads is the CALLER's: the pool, the table, the staged rows
and the current row are operands, and nothing here says that the layer that
attends wrote them (models/phi4_flash.py hands one layer's to eight layers).
Nor is a "head" a published head: ``Dh`` is what a row of the cache holds for a
kv head, a whole tile of 128 lanes for most families and, for differential
attention at heads of 64, a PAIR of kv heads side by side
(``differential_paged_decode_attention`` and ``differential_ring_decode_attention``
at the end of this file: both softmax maps of a head pair as one call, with
queries widened by zeros; ops/attention.py says how).

The page walk (``_kernel``). The grid is (S,), one instance a slot, run in
order. An instance streams its slot's ``[Hkv, chunk, Dh]`` K and V SLABS (all
kv heads a DMA; a page is one slab) through two VMEM buffers, one computed
from while the other fills, with a flash-style online softmax in float32 over
Hkv-batched dots; GQA is native, q arrives grouped ``[Hkv, n_rep, Dh]``. The
cache stays in HBM (``memory_space=ANY``); lengths, counts, the page table
and the layer index arrive by scalar prefetch, so slab counts are per-slot
loop bounds, not padding. Two things keep the HBM queue and the vector units
busy (PERF.md, PR 44):

* **A fetch is handed from slot to slot.** The buffers and their DMA
  semaphores are the call's scratch, alive over the whole grid. While a slot
  computes its last slab it starts the fetch of the NEXT slot's first slab
  (from the same prefetched scalars) into the other buffer, and that slot
  waits for it and starts nothing of its own; the buffer a slot starts in is
  where its predecessor put the slab (an SMEM scalar), so the parity follows
  the running count of slabs. A slot that reads nothing from the pool (idle,
  or every position still staged) neither receives nor hands on: the slot
  after it starts its own first fetch. Both sides decide by one predicate
  over the same scalars, so no DMA is left in flight or waited for twice.
* **One fold.** The chunk's staged rows and the current token are one small
  matrix a slot and fold in as ONE softmax step joined to the slabs' running
  m, l, acc: a score matmul under an own-head and a shown mask, a value
  matmul, no loop (``_chunk_fold``, which the ring's call shares).

No reference counterpart (the reference does not serve); the calls are tested
against plain attention over the explicit positions (tests/test_paged.py) and
the engines against the XLA masked-attention decode path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tony_tpu.ops.interpret import interpret

# cache positions a DMA slab of the DENSE cache holds (a paged cache's slab is its page); shrunk by
# halving to divide the cache length.
CHUNK = 256


def _chunk_fold(H, Hkv, W, Dh):
    """The fold of a decode chunk's staged rows and the current token, which the page walk and
    the ring share: ``(scores, values)``, built inside a kernel's body.

    The staged rows and the current token of ALL kv heads are one small matrix a slot, rows
    ``(j, h')``: a query row ``(h, r)`` takes the columns of its own head and a mask hides the
    others, so the fold is two matmuls and no loop (a step a row, each over ``[Hkv, n_rep, 1]``
    arrays, cost a ring's read: PERF.md, PR 35). Staged row ``j`` is position ``pool_len + j``,
    shown iff ``j < count`` and the position is at least ``lo``; the current token (step ``W``)
    is always shown, so every maximum is a real score and a masked column's weight is exactly 0.
    The refs are a grid step's blocks, ``b`` the slot among them; ``W == 0`` (no staging: the
    staged ref is None) folds the current token alone.
    """
    n_rep = H // Hkv
    row_head = jax.lax.broadcasted_iota(jnp.int32, (H, (W + 1) * Hkv), 0) // n_rep
    col = jax.lax.broadcasted_iota(jnp.int32, (H, (W + 1) * Hkv), 1)
    own_head, step = row_head == col % Hkv, col // Hkv                       # step W is the current token

    def with_current(staged_ref, cur_ref, b):                                # [(W + 1) * Hkv, Dh], rows (j, h')
        if staged_ref is None:
            return cur_ref[b].astype(jnp.float32)
        staged = staged_ref[b].astype(jnp.float32).reshape(W * Hkv, Dh)
        return jnp.concatenate([staged, cur_ref[b].astype(jnp.float32)], axis=0)

    def scores(qf, sk_ref, ck_ref, b, count, pool_len, lo):                  # qf [Hkv, n_rep, Dh], scaled
        s2 = jax.lax.dot_general(qf.reshape(H, Dh), with_current(sk_ref, ck_ref, b), (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)         # [H, (W + 1) * Hkv]
        shown = own_head & ((step == W) | ((step < count) & (pool_len + step >= lo)))
        return jnp.where(shown, s2, -1e30)

    def values(p2, sv_ref, cv_ref, b):                                       # p2 [H, (W + 1) * Hkv]
        return jax.lax.dot_general(p2, with_current(sv_ref, cv_ref, b), (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32).reshape(Hkv, n_rep, Dh)

    return scores, values


def _kernel(len_ref, count_ref, q_ref, ck_ref, cv_ref, staged_refs, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sem, start_ref, *, slab, chunk, window):
    """The page walk's body, one grid instance a slot, the slots in order. ``slab(ref, s, c)`` is
    slab ``c`` of slot ``s`` in the HBM operand: ``[Hkv, chunk, Dh]`` positions ``c * chunk ..``
    of a dense cache, or the physical page a table names (a page holds exactly one slab's
    positions, so the position arithmetic is one). The two slab buffers, their DMA semaphores and
    ``start_ref`` are the CALL's scratch, alive over the whole grid, so a fetch started by one
    slot can be waited for by the next."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_i, S = pl.program_id(0), pl.num_programs(0)
    Hkv, n_rep, Dh = q_ref.shape[1:]
    W = staged_refs[0].shape[1] if staged_refs is not None else 0
    sk_ref, sv_ref = staged_refs if staged_refs is not None else (None, None)

    def span(s):
        length = len_ref[s]  # CACHE positions (the current token arrives via ck/cv refs)
        # the most recent ``count`` of them live in the staged block, NOT the pool: the pool's
        # read stops short of them. Clamped at 0: an idle slot (length 0) carries staged garbage
        # the caller discards, and a negative span must not start a negative-offset DMA
        count = count_ref[s] if count_ref is not None else jnp.int32(0)
        pool_len = jnp.maximum(length - count, 0)
        # the current token sits at position `length`; the band is (length - window, length]
        lo = jnp.maximum(length + 1 - window, 0) if window > 0 else jnp.int32(0)
        return count, pool_len, lo, jnp.minimum(lo, pool_len) // chunk, pl.cdiv(pool_len, chunk)

    count, pool_len, lo, c0, c1 = span(s_i)
    before, after = jnp.maximum(s_i - 1, 0), jnp.minimum(s_i + 1, S - 1)
    *_, b0, b1 = span(before)
    *_, a0, a1 = span(after)
    # a fetch passes from a slot to the next iff BOTH read the pool: one predicate, evaluated on
    # both sides from the same scalars, so nothing is left in flight or waited for twice. A slot
    # with nothing to read (idle, or every position still staged) breaks the chain, and the slot
    # after it warms up by itself
    reads = c0 < c1
    handed = (s_i > 0) & (b0 < b1) & reads
    hands_on = (s_i + 1 < S) & (a0 < a1) & reads
    # the buffer this slot's first slab is in: where its predecessor put it, so the parity
    # follows the running count of slabs and not the slot's own
    first = jnp.where(handed, start_ref[0], 0)

    def fetch(buf, s, c):  # one DMA a buffer: the whole [Hkv, chunk, Dh] slab
        return (
            pltpu.make_async_copy(slab(k_hbm, s, c), k_buf.at[buf], sem.at[buf, 0]),
            pltpu.make_async_copy(slab(v_hbm, s, c), v_buf.at[buf], sem.at[buf, 1]),
        )

    @pl.when(reads & jnp.logical_not(handed))
    def _warm_up():
        for d in fetch(first, s_i, c0):
            d.start()

    q = q_ref[0].astype(jnp.float32) * Dh ** -0.5  # [Hkv, n_rep, Dh]

    def step(c, carry):
        m, l, acc = carry
        cur = (first + c - c0) % 2
        more = c + 1 < c1

        # behind the slot's last slab goes the next slot's first, into the buffer this step
        # does not compute from: no slot starts with an empty HBM queue
        @pl.when(more | hands_on)
        def _():
            for d in fetch(1 - cur, jnp.where(more, s_i, after), jnp.where(more, c + 1, a0)):
                d.start()

        for d in fetch(cur, s_i, c):
            d.wait()

        k = k_buf[cur].astype(jnp.float32)            # [Hkv, chunk, Dh]
        v = v_buf[cur].astype(jnp.float32)
        # batched over kv heads: s [Hkv, n_rep, chunk]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        )
        pos = c * chunk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        valid = jnp.logical_and(pos >= lo, pos < pool_len)
        s = jnp.where(valid, s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=2, keepdims=True)
        pv = jax.lax.dot_general(                      # [Hkv, n_rep, Dh]
            p, v, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
        )
        acc = acc * alpha + pv
        return m_new, l, acc

    m0 = jnp.full((Hkv, n_rep, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((Hkv, n_rep, 1), jnp.float32)
    acc0 = jnp.zeros((Hkv, n_rep, Dh), jnp.float32)
    m, l, acc = jax.lax.fori_loop(c0, c1, step, (m0, l0, acc0))

    @pl.when(hands_on)
    def _():
        start_ref[0] = (first + c1 - c0) % 2

    # the chunk's staged rows (positions pool_len .. length-1, VMEM-resident) and the current
    # token (position `length`), one softmax step joined to the slabs' running m, l, acc: the
    # cache stays read-only, and a slot with nothing cached normalises over what is shown here
    H = Hkv * n_rep
    scores, values = _chunk_fold(H, Hkv, W, Dh)
    s2 = scores(q, sk_ref, ck_ref, 0, count, pool_len, lo)
    m_new = jnp.maximum(m, s2.max(axis=1, keepdims=True).reshape(Hkv, n_rep, 1))
    alpha, p2 = jnp.exp(m - m_new), jnp.exp(s2 - m_new.reshape(H, 1))
    l = l * alpha + p2.sum(axis=1, keepdims=True).reshape(Hkv, n_rep, 1)
    acc = acc * alpha + values(p2, sv_ref, cv_ref, 0)
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def _slab_scratch(Hkv, chunk, Dh, dtype):
    """``_kernel``'s scratch: two slab buffers each of K and V, a DMA semaphore each, and the
    buffer a handed-on fetch went to."""
    from jax.experimental.pallas import tpu as pltpu

    return [
        pltpu.VMEM((2, Hkv, chunk, Dh), dtype),
        pltpu.VMEM((2, Hkv, chunk, Dh), dtype),
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.SMEM((1,), jnp.int32),
    ]


@functools.partial(jax.jit, static_argnames=("window", "chunk"))
def ragged_decode_attention(
    q: jax.Array,        # [S, H, Dh] — one new token per slot
    ck: jax.Array,       # [S, Hkv, maxT, Dh] — read-only cache
    cv: jax.Array,
    lengths: jax.Array,  # [S] int32 — CACHE positions (excluding current token)
    *,
    cur_k: jax.Array,    # [S, Hkv, Dh] — current token's K (not yet cached)
    cur_v: jax.Array,
    window: int = 0,
    chunk: int = CHUNK,
) -> jax.Array:
    """Per-slot ragged cache attention; returns o [S, H, Dh].

    Slot s attends cache positions [max(0, len_s + 1 - window), len_s) plus
    the current token (its K/V arrive via ``cur_k``/``cur_v``, folded as the
    last softmax step) — the cache is never written here, so the engine can
    defer the cache write to one small scatter per step. HBM traffic per step
    is Σ_s ceil(len_s/chunk)·chunk positions; slab c of slot s is positions
    ``c * chunk ..`` of the slot's own rows (``_kernel``: the module docstring).

    PRECONDITION: ``lengths[s] < maxT`` for every slot whose output is
    consumed. At ``lengths == maxT`` (only reachable via the engine's
    clamped write position for retired-not-yet-flushed slots) position
    maxT-1 is attended twice — once as stale cache, once as the current
    token — and the result is garbage the caller must discard.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, Dh = q.shape
    Hkv, maxT = ck.shape[1], ck.shape[2]
    n_rep = H // Hkv
    chunk = min(chunk, maxT)
    while chunk > 8 and maxT % chunk:  # shrink to divide (cf. _block_sizes)
        chunk //= 2
    if maxT % chunk:  # floor at 8: a 1-position slab would be a perf cliff
        raise ValueError(f"cache max_len {maxT} has no slab size >= 8 that divides it")
    qg = q.reshape(S, Hkv, n_rep, Dh)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, Hkv, n_rep, Dh), lambda s, L: (s, 0, 0, 0)),
            pl.BlockSpec((1, Hkv, Dh), lambda s, L: (s, 0, 0)),
            pl.BlockSpec((1, Hkv, Dh), lambda s, L: (s, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # ck stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),   # cv stays in HBM
        ],
        out_specs=pl.BlockSpec((1, Hkv, n_rep, Dh), lambda s, L: (s, 0, 0, 0)),
        scratch_shapes=_slab_scratch(Hkv, chunk, Dh, ck.dtype),
    )

    def kern(len_ref, q_ref, ck_ref, cv_ref, k_hbm, v_hbm, o_ref, *scratch):
        _kernel(
            len_ref, None, q_ref, ck_ref, cv_ref, None, k_hbm, v_hbm, o_ref, *scratch,
            slab=lambda ref, s, c: ref.at[s, :, pl.ds(c * chunk, chunk)], chunk=chunk, window=window,
        )

    o = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Hkv, n_rep, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret(),
        cost_estimate=pl.CostEstimate(
            flops=4 * S * H * maxT * Dh,
            bytes_accessed=(ck.size + cv.size) * ck.dtype.itemsize // 4,
            transcendentals=S * H * maxT,
        ),
    )(lengths, qg, cur_k, cur_v, ck, cv)
    return o.reshape(S, H, Dh)


@functools.partial(jax.jit, static_argnames=("window",))
def paged_decode_attention(
    q: jax.Array,           # [S, H, Dh] — one new token per slot
    kp: jax.Array,          # [L, P, Hkv, page_len, Dh] — WHOLE page pool (read-only)
    vp: jax.Array,
    lengths: jax.Array,     # [S] int32 — CACHE positions (excluding current)
    page_table: jax.Array,  # [S, max_pages] int32 — logical page j → physical
    layer: jax.Array,       # [] int32 — which layer's pages to read (may be traced)
    *,
    cur_k: jax.Array,       # [S, Hkv, Dh]
    cur_v: jax.Array,
    window: int = 0,
    staged_k: jax.Array | None = None,  # [S, W, Hkv, Dh] — chunk staging
    staged_v: jax.Array | None = None,
    staged_count: jax.Array | None = None,  # [S] int32 — live staged entries
) -> jax.Array:
    """Ragged decode attention over a PAGED cache; returns o [S, H, Dh].

    ``ragged_decode_attention``'s walk (``_kernel``: an instance a slot, two
    slab buffers, a fetch handed from each slot to the next, online softmax,
    one fold of the staged rows and the current token) with one indirection:
    the DMA slab is a PAGE, and slab c of slot s reads physical page
    ``page_table[s, c]`` of layer ``layer`` of the pool. The operand is the
    WHOLE pool plus a layer index (a scalar-prefetch operand, so the decode
    step's layer scan can hand a traced one), never one layer's slice of it:
    a Mosaic call's operand needs a buffer of its own, so a slice handed in
    is a copy of a layer's pool a call, which once took two fifths of the
    serving step (PERF.md, PR 27). HBM traffic per step is
    Σ_s ceil(len_s/page_len)·page_len positions — the pool's total size
    L × P is irrelevant to step cost: HBM footprint tracks allocated pages,
    not slots × max_len. Entries of ``page_table`` beyond slot s's live pages
    are never read (loop bounds come from ``lengths``; the fetch handed on
    reads the next slot's first LIVE page); SWA slots skip whole pages below
    the window exactly as the dense kernel skips slabs.

    CHUNKED DECODE STAGING: with ``staged_k/v/count``, the most recent
    ``staged_count[s]`` of the ``lengths[s]`` positions live in the staged
    buffer (this decode chunk's not-yet-flushed columns), NOT the pool —
    the pool read stops short of them and they fold in from VMEM with the
    current token, as one softmax step whatever the count (staged row j is
    position ``lengths - staged_count + j``, kept iff inside the window).
    This is what lets the engine write the pool ONCE per chunk and not once
    per token.

    Same PRECONDITION as the dense kernel: consumed slots have
    ``lengths[s] < max_pages * page_len`` and their pages allocated.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, Dh = q.shape
    if kp.ndim != 5:
        raise ValueError(
            f"kp {kp.shape}: the operand is the whole pool [L, P, Hkv, page_len, "
            "Dh] with a layer index, not one layer's slice of it"
        )
    P, Hkv, page_len = kp.shape[1:4]
    n_rep = H // Hkv
    if page_len < 8 or page_len % 8:
        raise ValueError(
            f"page_len {page_len} must be a multiple of 8 (>= 8): the "
            "slab-DMA/sublane layout assumes sublane-aligned pages"
        )
    qg = q.reshape(S, Hkv, n_rep, Dh)
    has_staged = staged_k is not None
    if has_staged and (staged_v is None or staged_count is None):
        raise ValueError("staged_k needs staged_v and staged_count")
    # three scalar-prefetch operands: lengths (and counts), page_table, layer
    meta = (
        jnp.stack([lengths, staged_count], axis=1).astype(jnp.int32)
        if has_staged else lengths[:, None]
    )

    staged_specs = (
        [
            pl.BlockSpec((1,) + staged_k.shape[1:], lambda s, M, PT, LY: (s, 0, 0, 0)),
            pl.BlockSpec((1,) + staged_k.shape[1:], lambda s, M, PT, LY: (s, 0, 0, 0)),
        ]
        if has_staged else []
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # meta [S, 1|2], page_table, layer [1]
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, Hkv, n_rep, Dh), lambda s, M, PT, LY: (s, 0, 0, 0)),
            pl.BlockSpec((1, Hkv, Dh), lambda s, M, PT, LY: (s, 0, 0)),
            pl.BlockSpec((1, Hkv, Dh), lambda s, M, PT, LY: (s, 0, 0)),
            *staged_specs,
            pl.BlockSpec(memory_space=pl.ANY),   # kp stays in HBM, all layers of it
            pl.BlockSpec(memory_space=pl.ANY),   # vp stays in HBM
        ],
        out_specs=pl.BlockSpec((1, Hkv, n_rep, Dh), lambda s, M, PT, LY: (s, 0, 0, 0)),
        scratch_shapes=_slab_scratch(Hkv, page_len, Dh, kp.dtype),
    )

    class _Col:
        """A 1-column view over the packed meta operand."""

        def __init__(self, ref, col):
            self.ref, self.col = ref, col

        def __getitem__(self, s):
            return self.ref[s, self.col]

    def kern(meta_ref, pt_ref, layer_ref, q_ref, ck_ref, cv_ref, *rest):
        staged_refs, rest = (rest[:2], rest[2:]) if has_staged else (None, rest)
        k_hbm, v_hbm, o_ref, *scratch = rest
        _kernel(
            _Col(meta_ref, 0), _Col(meta_ref, 1) if has_staged else None, q_ref, ck_ref, cv_ref, staged_refs,
            k_hbm, v_hbm, o_ref, *scratch,
            # slab c of slot s is physical page page_table[s, c] of the layer: a view of the whole-pool
            # operand, nothing copied
            slab=lambda ref, s, c: ref.at[layer_ref[0], pt_ref[s, c]], chunk=page_len, window=window,
        )

    operands = [meta, page_table, jnp.reshape(layer, (1,)).astype(jnp.int32), qg, cur_k, cur_v]
    if has_staged:
        operands += [staged_k, staged_v]
    operands += [kp, vp]
    o = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Hkv, n_rep, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret(),
        cost_estimate=pl.CostEstimate(
            flops=4 * S * H * page_table.shape[1] * page_len * Dh,
            # one layer's K and V pages that the page table can address, a
            # quarter of them live (the dense kernel's guess): the operand's
            # other layers are never read, and must not weigh in the estimate
            bytes_accessed=(
                2 * min(P, page_table.size) * Hkv * page_len * Dh * kp.dtype.itemsize // 4
            ),
            transcendentals=S * H * page_table.shape[1] * page_len,
        ),
    )(*operands)
    return o.reshape(S, H, Dh)


#: slots of a ring a grid step of ``ring_decode_attention`` reads as one block. Chosen on the
#: chip by the call's device time in a trace at 256 slots x 8 kv heads x 144 rows x 128: blocks
#: of 2 / 4 / 8 / 16 / 32 slots took 203.8 / 204.7 / 206.5 / 210.3 / 217.6 us (PERF.md, PR 35).
#: The HBM bounds the call at any of them (151 MB a call: 184 us at the peak); what a larger
#: block adds is the first block's fetch, which nothing hides.
RING_BLOCK = 2


@functools.partial(jax.jit, static_argnames=("window",))
def ring_decode_attention(
    q: jax.Array,             # [S, H, Dh] — one new token per slot
    rk: jax.Array,            # [Lw, S, Hkv, ring, Dh] — EVERY window layer's rings (read-only)
    rv: jax.Array,
    lengths: jax.Array,       # [S] int32 — CACHE positions (staged ones among them, current excluded)
    layer: jax.Array,         # [] int32 — which layer's rings to read (may be traced)
    *,
    cur_k: jax.Array,         # [S, Hkv, Dh]
    cur_v: jax.Array,
    window: int,
    staged_k: jax.Array,      # [S, W, Hkv, Dh] — the decode chunk's staging
    staged_v: jax.Array,
    staged_count: jax.Array,  # [S] int32
) -> jax.Array:
    """Decode attention of a WINDOW layer over its rings; returns o [S, H, Dh].

    A ring (models/paged_cache.py) keeps position p of slot s at row
    ``p % ring`` of ``rk[layer, s]``: a slot's whole cache is one contiguous
    ``[Hkv, ring, Dh]`` block and the slots' blocks lie one after another, so
    there is nothing to look up. A grid step takes a block of ``RING_BLOCK``
    slots through a plain ``BlockSpec`` whose index map reads the layer from a
    scalar-prefetch operand (the operand is every layer's rings: a layer's
    slice handed to a Mosaic call would be a copy), and Pallas' pipeline
    fetches the next block while this one is computed. Every ring is read
    once, whole, and a row is masked by the position it holds: with
    ``pool_len = max(length - staged_count, 0)`` row r holds the newest
    position below ``pool_len`` that is congruent to r, and is read iff that
    position is at least ``lo = max(length + 1 - window, 0)``. A row of an
    earlier lap or of the slot's last tenant holds a position below ``lo``
    (the ring has ``window + chunk - 1`` rows or more), so it is never read.

    The arithmetic is ``paged_decode_attention``'s: float32 scores, softmax
    and accumulator from the cache's rows, q scaled by ``Dh ** -0.5``, ONE
    softmax over the ring, the staged rows (positions ``pool_len .. length -
    1``, kept iff ``>= lo``) and the current token. The ring is one slab, so
    nothing is rescaled between slabs; the order of summation differs from the
    page walk's, the precision does not. An idle slot (length 0) normalises
    over its current token alone.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, Dh = q.shape
    if rk.ndim != 5 or rk.shape[1] != S:
        raise ValueError(f"rk {rk.shape}: the operand is every window layer's rings [Lw, S={S}, Hkv, ring, Dh]")
    Hkv, ring = rk.shape[2:4]
    n_rep, W = H // Hkv, staged_k.shape[1]
    block = max(b for b in range(1, min(RING_BLOCK, S) + 1) if S % b == 0)
    scale = Dh ** -0.5
    meta = jnp.stack([lengths, staged_count], axis=1).astype(jnp.int32)       # [S, 2]

    def kern(meta_ref, layer_ref, q_ref, ck_ref, cv_ref, sk_ref, sv_ref, k_ref, v_ref, o_ref):
        first = pl.program_id(0) * block
        scores, values = _chunk_fold(H, Hkv, W, Dh)

        def one_slot(b, _):
            length, count = meta_ref[first + b, 0], meta_ref[first + b, 1]
            pool_len = jnp.maximum(length - count, 0)
            lo = jnp.maximum(length + 1 - window, 0)
            # rows 0 .. newest hold the lap of position pool_len - 1, the rows after it the lap before
            last = jnp.maximum(pool_len - 1, 0)
            newest = last % ring
            lap = last - newest
            qf = q_ref[b].astype(jnp.float32) * scale                        # [Hkv, n_rep, Dh]
            k = k_ref[b].astype(jnp.float32)                                 # [Hkv, ring, Dh]
            v = v_ref[b].astype(jnp.float32)
            s = jax.lax.dot_general(qf, k, (((2,), (2,)), ((0,), (0,))),
                                    preferred_element_type=jnp.float32)      # [Hkv, n_rep, ring]
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            pos = lap + row - jnp.where(row > newest, ring, 0)
            s = jnp.where((pos >= lo) & (pool_len > 0), s, -1e30)

            s2 = scores(qf, sk_ref, ck_ref, b, count, pool_len, lo)              # [H, (W + 1) * Hkv]
            m = jnp.maximum(s.max(axis=2, keepdims=True), s2.max(axis=1, keepdims=True).reshape(Hkv, n_rep, 1))
            p, p2 = jnp.exp(s - m), jnp.exp(s2 - m.reshape(H, 1))
            l = p.sum(axis=2, keepdims=True) + p2.sum(axis=1, keepdims=True).reshape(Hkv, n_rep, 1)
            acc = jax.lax.dot_general(p, v, (((2,), (1,)), ((0,), (0,))),
                                      preferred_element_type=jnp.float32)    # [Hkv, n_rep, Dh]
            acc = acc + values(p2, sv_ref, cv_ref, b)
            o_ref[b] = (acc / l).astype(o_ref.dtype)
            return 0

        jax.lax.fori_loop(0, block, one_slot, 0)

    def slots(*rest):  # a block of slots of an operand [S, *rest]
        return pl.BlockSpec((block, *rest), lambda i, M, LY: (i,) + (0,) * len(rest))

    rings = pl.BlockSpec((None, block, Hkv, ring, Dh), lambda i, M, LY: (LY[0], i, 0, 0, 0))
    o = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # meta [S, 2], layer [1]
            grid=(S // block,),
            in_specs=[slots(Hkv, n_rep, Dh), slots(Hkv, Dh), slots(Hkv, Dh), slots(W, Hkv, Dh), slots(W, Hkv, Dh),
                      rings, rings],
            out_specs=slots(Hkv, n_rep, Dh),
        ),
        out_shape=jax.ShapeDtypeStruct((S, Hkv, n_rep, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret(),
        name="ring_decode_attention",
        cost_estimate=pl.CostEstimate(
            flops=4 * S * H * (ring + W + 1) * Dh,
            bytes_accessed=2 * S * Hkv * ring * Dh * rk.dtype.itemsize,      # one layer's rings, K and V, once
            transcendentals=S * H * (ring + W + 1),
        ),
    )(meta, jnp.reshape(layer, (1,)).astype(jnp.int32), q.reshape(S, Hkv, n_rep, Dh), cur_k, cur_v,
      staged_k, staged_v, rk, rv)
    return o.reshape(S, H, Dh)


#: pages fetched together by the sparse kernel: one wait covers 2 x this many
#: copies in flight, so a page of 64 positions (16 KB a head) does not pay a
#: DMA's latency each
SPARSE_BATCH = 8


@jax.jit
def sparse_paged_decode_attention(
    q: jax.Array,             # [S, H, Dh] — one new token per slot
    kp: jax.Array,            # [L, P, Hkv, page_len, Dh] — WHOLE page pool (read-only)
    vp: jax.Array,
    layer: jax.Array,         # [] int32 — which layer's pages to read
    pages: jax.Array,         # [S, Hkv, N] int32 — PHYSICAL pages to read, the first counts[s, h] live
    logical: jax.Array,       # [S, Hkv, N] int32 — the logical page each stands for (its positions)
    full: jax.Array,          # [S, Hkv, N] bool/int — every position of the page is visible (else: from win_lo on)
    counts: jax.Array,        # [S, Hkv] int32
    lengths: jax.Array,       # [S] int32 — CACHE positions (staged ones among them, current excluded)
    win_lo: jax.Array,        # [S] int32 — first position the local window shows
    *,
    cur_k: jax.Array,         # [S, Hkv, Dh]
    cur_v: jax.Array,
    staged_k: jax.Array,      # [S, W, Hkv, Dh] — the decode chunk's staging
    staged_v: jax.Array,
    staged_count: jax.Array,  # [S] int32
) -> jax.Array:
    """Decode attention over a CHOSEN part of a paged cache; returns o [S, H, Dh].

    ``paged_decode_attention`` reads every page of a slot's table up to its
    length. Here the caller names the pages, a list for each slot and kv head
    (a learned score chose them: ops/sparse_attention.py), and nothing else of
    the pool is touched: page ``pages[s, h, c]`` holds the positions of logical
    page ``logical[s, h, c]``, all of them visible where ``full`` says so and
    otherwise from ``win_lo[s]`` on (the first page of the local window is cut
    by position, not by page). Positions at or past the pool's part of the
    slot (``lengths - staged_count``) are masked; the staged window and the
    current token fold in as explicit online-softmax steps, a row at a time
    (this call's own loop). One grid instance a (slot, kv head): the group's query heads share
    one list, so they share one read. Pages arrive ``SPARSE_BATCH`` at a time,
    double-buffered, each head's ``[page_len, Dh]`` slab a copy of its own.
    HBM traffic a call is Σ counts x page_len positions, whatever the context.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, Dh = q.shape
    if kp.ndim != 5:
        raise ValueError(f"kp {kp.shape}: the operand is the whole pool [L, P, Hkv, page_len, Dh]")
    Hkv, page_len = kp.shape[2:4]
    n_rep, G = H // Hkv, SPARSE_BATCH
    N = pages.shape[2]
    pad = (-N) % G
    rows = S * Hkv

    def flat(a):  # [S, Hkv, N] -> [S*Hkv, N padded to whole batches]
        return jnp.pad(a.astype(jnp.int32).reshape(rows, N), ((0, 0), (0, pad)))

    meta = flat(logical) * 2 + flat(full)
    info = jnp.stack([lengths, staged_count, win_lo], axis=1).astype(jnp.int32)   # [S, 3]
    W = staged_k.shape[1]
    scale = Dh ** -0.5

    def kern(pages_ref, meta_ref, counts_ref, info_ref, layer_ref,
             q_ref, ck_ref, cv_ref, sk_ref, sv_ref, k_hbm, v_hbm, o_ref):
        s_i, h_i = pl.program_id(0), pl.program_id(1)
        row = s_i * Hkv + h_i
        length, staged, lo = info_ref[s_i, 0], info_ref[s_i, 1], info_ref[s_i, 2]
        pool_len = jnp.maximum(length - staged, 0)
        count = counts_ref[row, 0]
        nb = pl.cdiv(count, G)
        kl, vl = k_hbm.at[layer_ref[0]], v_hbm.at[layer_ref[0]]

        def body(k_buf, v_buf, sem):
            qf = q_ref[0, 0].astype(jnp.float32) * scale            # [n_rep, Dh]

            def dma(slot, b):
                out = []
                for g in range(G):
                    # past the list's end: fetch its last page again (masked below)
                    page = pages_ref[row, jnp.minimum(b * G + g, count - 1)]
                    out.append(pltpu.make_async_copy(kl.at[page, h_i], k_buf.at[slot, g], sem.at[slot, 0]))
                    out.append(pltpu.make_async_copy(vl.at[page, h_i], v_buf.at[slot, g], sem.at[slot, 1]))
                return out

            @pl.when(nb > 0)
            def _warmup():
                for d in dma(0, 0):
                    d.start()

            col = jax.lax.broadcasted_iota(jnp.int32, (1, G * page_len), 1)
            gidx, off = col // page_len, col % page_len

            def step(b, carry):
                m, l, acc = carry
                cur, nxt = b % 2, (b + 1) % 2

                @pl.when(b + 1 < nb)
                def _():
                    for d in dma(nxt, b + 1):
                        d.start()

                for d in dma(cur, b):
                    d.wait()
                k = k_buf[cur].reshape(G * page_len, Dh).astype(jnp.float32)
                v = v_buf[cur].reshape(G * page_len, Dh).astype(jnp.float32)
                s = jax.lax.dot_general(qf, k, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)     # [n_rep, G*page_len]
                pos = jnp.zeros_like(col)
                shown = jnp.zeros_like(col)
                for g in range(G):
                    mg = meta_ref[row, b * G + g]
                    live = (b * G + g < count).astype(jnp.int32)
                    pos = jnp.where(gidx == g, (mg >> 1) * page_len + off, pos)
                    # 2: every position; 1: the window's; 0: not in the list
                    shown = jnp.where(gidx == g, live * (1 + (mg & 1)), shown)
                valid = (pos < pool_len) & ((shown == 2) | ((shown == 1) & (pos >= lo)))
                s = jnp.where(valid, s, -1e30)
                m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
                p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
                alpha = jnp.exp(m - m_new)
                l = l * alpha + p.sum(axis=1, keepdims=True)
                acc = acc * alpha + jax.lax.dot_general(
                    p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
                return m_new, l, acc

            m0 = jnp.full((n_rep, 1), -1e30, jnp.float32)
            l0 = jnp.zeros((n_rep, 1), jnp.float32)
            acc0 = jnp.zeros((n_rep, Dh), jnp.float32)
            m, l, acc = jax.lax.fori_loop(0, nb, step, (m0, l0, acc0))

            def fold_one(k1, v1, carry):
                m, l, acc = carry
                s1 = jnp.sum(qf * k1, axis=1, keepdims=True)         # [n_rep, 1]
                m_new = jnp.maximum(m, s1)
                alpha, p1 = jnp.exp(m - m_new), jnp.exp(s1 - m_new)
                return m_new, l * alpha + p1, acc * alpha + p1 * v1

            # the chunk's earlier tokens (positions pool_len .. length-1): always
            # inside the local window
            def staged_step(j, carry):
                return fold_one(sk_ref[0, 0, j].astype(jnp.float32), sv_ref[0, 0, j].astype(jnp.float32), carry)

            m, l, acc = jax.lax.fori_loop(0, staged, staged_step, (m, l, acc))
            m, l, acc = fold_one(ck_ref[0, 0, 0].astype(jnp.float32), cv_ref[0, 0, 0].astype(jnp.float32), (m, l, acc))
            o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

        pl.run_scoped(
            body,
            k_buf=pltpu.VMEM((2, G, page_len, Dh), kp.dtype),
            v_buf=pltpu.VMEM((2, G, page_len, Dh), vp.dtype),
            sem=pltpu.SemaphoreType.DMA((2, 2)),
        )

    def per_head(width):
        return pl.BlockSpec((1, 1, width, Dh), lambda s, h, *_: (s, h, 0, 0))

    def per_head_rows(width):  # a row an index of a leading dimension: [S, Hkv, width, 1, Dh]
        return pl.BlockSpec((1, 1, width, 1, Dh), lambda s, h, *_: (s, h, 0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,  # pages, meta, counts, info, layer
        grid=(S, Hkv),
        in_specs=[
            per_head(n_rep), per_head_rows(1), per_head_rows(1), per_head_rows(W), per_head_rows(W),
            pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=per_head(n_rep),
    )
    read = S * Hkv * N * page_len
    o = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Hkv, n_rep, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret(),
        cost_estimate=pl.CostEstimate(
            flops=4 * n_rep * read * Dh, bytes_accessed=2 * read * Dh * kp.dtype.itemsize,
            transcendentals=n_rep * read),
    )(
        flat(pages), meta, counts.astype(jnp.int32).reshape(rows, 1), info,
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        q.reshape(S, Hkv, n_rep, Dh), cur_k[:, :, None, None], cur_v[:, :, None, None],
        staged_k.transpose(0, 2, 1, 3)[:, :, :, None], staged_v.transpose(0, 2, 1, 3)[:, :, :, None], kp, vp,
    )
    return o.reshape(S, H, Dh)


# -- differential attention at decode: both maps in one read of the pairs ----------------------------------
#
# ops/attention.py says why: over a cache of kv-head PAIRS a head's two softmax maps are plain grouped-query
# attention of queries widened with zeros, so a decode step's read of a page (or of a ring) is ONE call of the
# one-map kernel that moves every key and value once. The output goes to `attention.differential_combine`.

def differential_paged_decode_attention(q, kp, vp, lengths, page_table, layer, **rest):
    """`paged_decode_attention` for both maps: q [S, H, dh]; kp, vp a pool of pairs [L, P, Hkv / 2, page_len, 2 dh];
    cur_k, cur_v [S, Hkv / 2, 2 dh] and the staged rows [S, W, Hkv / 2, 2 dh] pairs too. Returns [S, H, 2 dh]."""
    from tony_tpu.ops.attention import differential_queries

    return paged_decode_attention(differential_queries(q, kp.dtype), kp, vp, lengths, page_table, layer, **rest)


def differential_ring_decode_attention(q, rk, rv, lengths, layer, **rest):
    """`ring_decode_attention` for both maps over rings of pairs [Lw, S, Hkv / 2, ring, 2 dh]. Returns [S, H, 2 dh]."""
    from tony_tpu.ops.attention import differential_queries

    return ring_decode_attention(differential_queries(q, rk.dtype), rk, rv, lengths, layer, **rest)
