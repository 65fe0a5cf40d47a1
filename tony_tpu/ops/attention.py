"""Attention kernels: XLA reference + Pallas TPU flash attention.

The compute hot path the reference never owned (it lived inside TF/torch —
SURVEY.md §2.4): here multi-head attention is a first-class op with
- ``attention_reference``: einsum+softmax through XLA (runs everywhere; XLA
  already fuses mask+softmax into the matmuls well on TPU),
- ``flash_attention``: blockwise-online-softmax Pallas kernel keeping the
  score matrix in VMEM tiles (O(T) memory), for long sequences on TPU,
- ``mha``: the dispatcher models call (impl='auto' picks per backend).

GQA/MQA is kernel-native: k/v keep their [B, Hkv, T, D] shape and the
kernels alias q heads onto kv heads through BlockSpec index maps
(head h reads kv head h // n_rep), so K/V HBM traffic stays at Hkv size.
Only the XLA reference path broadcasts (``repeat_kv``).

``chunk_prefill_attention`` is the serving prefill's call (key tiles on the
grid over a request's staged keys). Differential attention (two softmax maps
a head pair, subtracted) has no kernel of its own: ``differential_queries``
widens the queries so that a cache of kv-head PAIRS, a whole tile of 128
lanes where a head of 64 is half of one, gives both maps in ONE call of a
one-map kernel (the end of this file; the decode calls are at the end of
ops/decode_attention.py).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.extend.core import Literal, jaxpr_as_fun

from tony_tpu.ops.interpret import interpret

NEG_INF = -1e30

# Per-row stats (logsumexp, delta) are carried with a trailing lane dim of
# this size: TPU Pallas requires >=2-D tiles whose last dim is 128-divisible
# OR equal to the full array dim — a small full-width lane dim keeps the
# HBM cost of the stats negligible while satisfying the tiling rule.
_STAT_LANES = 8



def repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """[B, Hkv, T, D] → [B, Hkv*n_rep, T, D] (GQA head broadcast)."""
    if n_rep == 1:
        return k
    B, H, T, D = k.shape
    return jnp.broadcast_to(k[:, :, None], (B, H, n_rep, T, D)).reshape(B, H * n_rep, T, D)


def attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    segment_ids: jax.Array | None = None,
    window: int = 0,
) -> jax.Array:
    """Plain attention; q/k/v: [B, H, T, D] (KV already head-broadcast).

    ``segment_ids`` [B, T] (packed sequences): attention is confined within
    each segment — position i attends j only when seg[i] == seg[j].
    ``window`` > 0: sliding-window (Mistral/Mixtral-style) — position i
    attends only the last ``window`` positions (i−window, i].
    """
    D = q.shape[-1]
    scale = scale if scale is not None else D ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    Tq, Tk = s.shape[-2], s.shape[-1]
    q_pos = jnp.arange(Tq)[:, None] + (Tk - Tq)
    k_pos = jnp.arange(Tk)[None, :]
    if causal:
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    if window > 0:
        s = jnp.where(q_pos - k_pos < window, s, NEG_INF)
    if segment_ids is not None:
        same = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        s = jnp.where(same, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def _seg_arrays(segment_ids: jax.Array, B: int, T: int) -> tuple[jax.Array, jax.Array]:
    """Lane-/sublane-replicated segment-id layouts the kernels can tile:
    q-side [B, T, _STAT_LANES] (rows) and k-side [B, _STAT_LANES, T] (cols)."""
    s = segment_ids.astype(jnp.int32)
    segq = jnp.broadcast_to(s[:, :, None], (B, T, _STAT_LANES))
    segk = jnp.broadcast_to(s[:, None, :], (B, _STAT_LANES, T))
    return segq, segk


# ---------------------------------------------------------------------------
# Pallas flash attention (TPU)
# ---------------------------------------------------------------------------

def _imin(a, b):
    """min over Python ints (the block counts) and traced scalars (a kernel's
    loop bounds) alike."""
    return min(a, b) if isinstance(a, int) and isinstance(b, int) else jnp.minimum(a, b)


def _imax(a, b):
    return max(a, b) if isinstance(a, int) and isinstance(b, int) else jnp.maximum(a, b)


def _k_runs(qb, block_q: int, block_k: int, nk: int, causal: bool, window: int):
    """The k blocks q block ``qb`` visits, as bounds s <= e1 <= e2 <= e over
    ABSOLUTE positions (query i sees keys <= i, and > i - window): [s, e1) is
    crossed by the window's lower edge, [e1, e2) is interior (every pair
    visible: min q_pos >= max k_pos and max q_pos - min k_pos < window),
    [e2, e) is crossed by the diagonal. Blocks outside [s, e) are hidden: no
    pair visible, not visited. An empty visit has e <= s and e1 == e2 == e."""
    q_lo, q_hi = qb * block_q, qb * block_q + block_q - 1
    end = _imin(nk, q_hi // block_k + 1) if causal else nk
    start = _imax(0, (q_lo - window + 1) // block_k) if window > 0 else 0
    int_end = (q_lo + 1) // block_k if causal else nk
    int_start = (q_hi - window) // block_k + 1 if window > 0 else 0
    e1 = _imin(_imax(int_start, start), end)
    e2 = _imin(_imax(int_end, e1), end)
    return start, e1, e2, end


def _wide_band(block_q: int, block_k: int, window: int) -> bool:
    """A band at least block_q + block_k - 2 wide keeps its two edges in
    different blocks: an edge block then owes the mask of one edge only."""
    return window >= block_q + block_k - 2


def _edge_masks(block_q: int, block_k: int, causal: bool, window: int):
    """(causal, window) masks each of the three runs of ``_k_runs`` owes, as
    ((diagonal run), (interior run), (window-edge run))."""
    wide = _wide_band(block_q, block_k, window)
    return (causal, window > 0 and not wide), (False, False), (causal and not wide, window > 0)


def flash_block_classes(
    Tq: int, Tk: int, block_q: int, block_k: int, causal: bool, window: int,
) -> dict[str, int]:
    """How many of one head's (q block, k block) pairs are ``interior`` (every
    pair visible: the kernels run them with no iota, compare or select),
    ``edge`` (crossed by the diagonal or the window's edge: masked) and
    ``hidden`` (no visible pair: never visited). Static, so a function and not
    a counter; from the same bounds the kernels loop over."""
    nq, nk = Tq // block_q, Tk // block_k
    interior = edge = 0
    for qb in range(nq):
        s, e1, e2, e = _k_runs(qb, block_q, block_k, nk, causal, window)
        interior += max(e2 - e1, 0)
        edge += max(e1 - s, 0) + max(e - e2, 0)
    return {"interior": interior, "edge": edge, "hidden": nq * nk - interior - edge}


def _visible(s, q_pos, k_pos, mask_causal: bool, window: int, sq=None, sk=None):
    """Scores with the pairs a block's class says may be hidden set to
    NEG_INF: one select over the tile, none where nothing is owed. ``window``
    is 0 where the window's edge cannot cross the block."""
    ok = None
    if mask_causal:
        ok = q_pos >= k_pos
    if window > 0:
        w = k_pos > q_pos - window
        ok = w if ok is None else ok & w
    if sq is not None:
        same = sq == sk
        ok = same if ok is None else ok & same
    return s if ok is None else jnp.where(ok, s, NEG_INF)


def _k_pos(kb, block_k: int):
    return kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)


def _q_pos(qb, block_q: int):
    return qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)


# tiles a step of the interior loop takes, longest first: a `fori_loop` of
# dynamic trip count is one tile a basic block, so nothing of tile i + 1 runs
# under tile i and each pays the MXU's fill and drain alone. Two tiles a step,
# then the odd one: at the training cells' shape the forward 7.19 -> 7.00 ms
# and dq 8.51 -> 8.36 a call, four a step no better (7.01, 8.35: PERF.md
# section 6, PR 40); it is all the unrolling a shape with no band gets
_INTERIOR_UNROLL = (2, 1)


def _tiles(lo, hi, tile, carry, unroll=(1,)):
    """``carry = tile(i, carry)`` for i in [lo, hi), ``unroll`` tiles a loop
    step (a cascade: each loop takes what the one before left over)."""
    for u in unroll:
        steps = jnp.maximum(hi - lo, 0) // u

        def step(i, c, lo=lo, u=u):
            for t in range(u):
                c = tile(lo + i * u + t, c)
            return c

        carry = jax.lax.fori_loop(0, steps, step, carry)
        lo = lo + steps * u
    return carry


# the longest run of tiles a q block deep in the band gets as one basic block
_STEADY_MAX_TILES = 12


def _steady_runs(Tq: int, Tk: int, block_q: int, block_k: int, causal: bool, window: int):
    """(window-edge, interior, diagonal) tile counts of a q block deep in the
    band — the last one's, where the band's lower edge lies inside the
    sequence — or None where the shape has no such block. Under a band every
    q block past the first ``window`` rows visits that same short sequence of
    blocks, so the kernels run it as one basic block of static length."""
    start, e1, e2, end = _k_runs(Tq // block_q - 1, block_q, block_k, Tk // block_k, causal, window)
    if not (causal and window > 0 and 0 < start < end <= start + _STEADY_MAX_TILES):
        return None
    return e1 - start, e2 - e1, end - e2


def _band_loop(qb, block_q: int, block_k: int, nk: int, causal: bool, window: int, steady,
               make_tile, carry):
    """``carry`` through every k block q block ``qb`` visits (``_k_runs``),
    each by the tile of its class: ``make_tile(mask_causal, mask_window)``
    gives ``tile(kb, carry)``. A q block whose runs have the ``steady``
    lengths (``_steady_runs``) takes them as one unrolled basic block, in
    which the scheduler runs one tile's vector work under the next tile's
    matmuls; every other q block loops a run at a time."""
    start, e1, e2, end = _k_runs(qb, block_q, block_k, nk, causal, window)
    diagonal, interior, window_edge = _edge_masks(block_q, block_k, causal, window)

    def by_runs(carry):
        if window > 0:
            carry = _tiles(start, e1, make_tile(*window_edge), carry)
        carry = _tiles(e1, e2, make_tile(*interior), carry, _INTERIOR_UNROLL)
        if causal:
            carry = _tiles(e2, end, make_tile(*diagonal), carry)
        return carry

    if steady is None:
        return by_runs(carry)
    n_edge, n_interior, n_diagonal = steady

    def unrolled(carry):
        classes = [window_edge] * n_edge + [interior] * n_interior + [diagonal] * n_diagonal
        for t, masks in enumerate(classes):
            carry = make_tile(*masks)(start + t, carry)
        return carry

    is_steady = (e1 - start == n_edge) & (e2 - e1 == n_interior) & (end - e2 == n_diagonal)
    return jax.lax.cond(is_steady, unrolled, by_runs, carry)


_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _scaled(q_blk, scale: float):
    """A q block times the softmax scale, once for every tile it meets and in
    the type it arrived in (the MXU takes bfloat16 as it is; float32 stays
    float32)."""
    return (q_blk.astype(jnp.float32) * scale).astype(q_blk.dtype)


def _flash_kernel(
    q_ref, k_ref, v_ref, *rest,
    block_k: int, causal: bool, has_seg: bool, window: int, scale: float, steady,
):
    """Grid: (B*H, Tq//block_q). Online softmax over KV blocks in VMEM.

    Also emits the per-row logsumexp (scaled-score space) so the Pallas
    backward can recompute probabilities blockwise without the T×T matrix.
    The key loop is cut by block class (``_k_runs``): blocks the band hides
    are not visited (no DMA, no flops), blocks it covers whole run with no
    iota, compare or select, and only the blocks the window's edge or the
    diagonal crosses pay for that one mask. With ``has_seg``, two extra refs
    carry packed-sequence segment ids (q-side rows, k-side cols) and every
    visited block also pays the segment compare (segment ids are data).
    q, k, v reach the MXU in their own type; m, l, o stay float32.
    """
    from jax.experimental import pallas as pl

    if has_seg:
        segq_ref, segk_ref, o_ref, lse_ref = rest
    else:
        o_ref, lse_ref = rest
    block_q, D = q_ref.shape
    Tk = k_ref.shape[0]
    q_blk_idx = pl.program_id(1)
    q = _scaled(q_ref[:], scale)
    q_pos = _q_pos(q_blk_idx, block_q)
    sq = segq_ref[:][:, :1] if has_seg else None  # [block_q, 1]

    def make_tile(mask_causal: bool, mask_window: bool):
        def tile(kb, carry):
            o, m, l = carry
            ks = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
            k_blk, v_blk = k_ref[ks, :], v_ref[ks, :]
            s = jax.lax.dot_general(q, k_blk, _NT, preferred_element_type=jnp.float32)
            s = _visible(s, q_pos, _k_pos(kb, block_k), mask_causal, window if mask_window else 0,
                         sq, segk_ref[:1, ks] if has_seg else None)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            o_new = o * alpha + jax.lax.dot_general(
                p.astype(v_blk.dtype), v_blk, _NN, preferred_element_type=jnp.float32
            )
            return o_new, m_new, l_new

        return tile

    carry = (
        jnp.zeros((block_q, D), jnp.float32),
        jnp.full((block_q, 1), NEG_INF, jnp.float32),
        jnp.zeros((block_q, 1), jnp.float32),
    )
    carry = _band_loop(q_blk_idx, block_q, block_k, pl.cdiv(Tk, block_k), causal, window, steady,
                       make_tile, carry)
    o, m, l = carry
    l = jnp.maximum(l, 1e-20)
    o_ref[:] = (o / l).astype(o_ref.dtype)
    lse_ref[:] = jnp.broadcast_to(m + jnp.log(l), (block_q, _STAT_LANES))


def _flash_fwd_impl(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool, block_q: int, block_k: int,
    segment_ids: jax.Array | None = None, window: int = 0,
) -> tuple[jax.Array, jax.Array]:
    """Shared forward: ([B,H,Tq,D], lse [B,H,Tq]) — shapes pre-validated."""
    out, lse_lanes = _flash_fwd_lanes(q, k, v, causal, block_q, block_k, segment_ids, window)
    return out, lse_lanes[:, :, :, 0]


def _flash_fwd_lanes(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool, block_q: int, block_k: int,
    segment_ids: jax.Array | None = None, window: int = 0,
) -> tuple[jax.Array, jax.Array]:
    """Forward returning the lane-replicated lse [B,H,Tq,_STAT_LANES] so the
    backward can feed it to the Pallas kernels without a re-broadcast.

    GQA is kernel-native: k/v arrive as [B, Hkv, Tk, D] and the q-head grid
    aliases onto kv heads through the BlockSpec index map (head h reads kv
    head h // n_rep) — no head broadcast, so K/V HBM traffic stays at the
    Hkv size. Consecutive q heads map to the same kv block, which Pallas
    recognizes as a revisit and keeps resident in VMEM.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    n_rep = H // Hkv
    scale = D ** -0.5
    qf = q.reshape(B * H, Tq, D)
    kf = k.reshape(B * Hkv, Tk, D)
    vf = v.reshape(B * Hkv, Tk, D)

    has_seg = segment_ids is not None
    kernel = functools.partial(
        _flash_kernel, block_k=block_k, causal=causal, has_seg=has_seg,
        window=window, scale=scale, steady=_steady_runs(Tq, Tk, block_q, block_k, causal, window),
    )
    in_specs = [
        pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
        pl.BlockSpec((None, Tk, D), lambda b, i: (b // n_rep, 0, 0)),
        pl.BlockSpec((None, Tk, D), lambda b, i: (b // n_rep, 0, 0)),
    ]
    operands = [qf, kf, vf]
    if has_seg:
        segq, segk = _seg_arrays(segment_ids, B, Tq)
        in_specs += [
            pl.BlockSpec((None, block_q, _STAT_LANES), lambda b, i: (b // H, i, 0)),
            pl.BlockSpec((None, _STAT_LANES, Tk), lambda b, i: (b // H, 0, 0)),
        ]
        operands += [segq, segk]
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, Tq // block_q),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, _STAT_LANES), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, Tq, _STAT_LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret(),
        name="flash_fwd",
        cost_estimate=pl.CostEstimate(
            flops=4 * B * H * Tq * Tk * D,
            bytes_accessed=2 * (qf.size + kf.size + vf.size) * q.dtype.itemsize,
            transcendentals=B * H * Tq * Tk,
        ),
    )(*operands)
    return out.reshape(B, H, Tq, D), lse.reshape(B, H, Tq, _STAT_LANES)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "window"))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int | None = None,
    block_k: int | None = None,
    segment_ids: jax.Array | None = None,
    window: int = 0,
) -> jax.Array:
    """Pallas TPU flash attention; q: [B, H, T, D], k/v: [B, Hkv, T, D] with
    H % Hkv == 0 (GQA handled inside the kernel), T % block == 0.
    ``segment_ids`` [B, T] confines attention within packed segments
    (training-shape only: Tq == Tk). ``window`` > 0: sliding-window band —
    out-of-band k blocks are skipped entirely (no DMA, no flops).
    ``block_q``/``block_k`` default to the module constants, shrunk
    to divide the sequence lengths (``_block_sizes``)."""
    B, H, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    if H % Hkv:
        raise ValueError(f"n_heads {H} must be divisible by n_kv_heads {Hkv}")
    if segment_ids is not None and Tq != Tk:
        raise ValueError(f"segment_ids requires Tq == Tk, got {Tq} vs {Tk}")
    auto_bq, auto_bk = _block_sizes(Tq, Tk)
    block_q = auto_bq if block_q is None else min(block_q, Tq)
    block_k = auto_bk if block_k is None else min(block_k, Tk)
    # awkward lengths (e.g. 257) make _block_sizes halve to degenerate
    # blocks — take the XLA reference path rather than a laneless grid.
    # Non-8-multiple blocks (a 300-long seq reaching the kernel as one
    # block) are a Mosaic sublane-alignment lowering risk the interpreter
    # won't catch — route them to the reference path too.
    if (block_q < min(8, Tq) or block_k < min(128, Tk)
            or block_q % 8 or block_k % 8):
        return attention_reference(
            q, repeat_kv(k, H // Hkv), repeat_kv(v, H // Hkv),
            causal=causal, segment_ids=segment_ids, window=window,
        )
    if Tq % block_q or Tk % block_k:
        return attention_reference(
            q, repeat_kv(k, H // Hkv), repeat_kv(v, H // Hkv),
            causal=causal, segment_ids=segment_ids, window=window,
        )
    return _flash_fwd_impl(q, k, v, causal, block_q, block_k, segment_ids, window)[0]


def _flash_bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    block_k: int, num_q_blocks: int, causal: bool, has_seg: bool, window: int, scale: float, steady,
):
    """Grid: (B*Hkv, n_rep * Tq//block_q): one q block of one query head of a
    kv head's group a step, that kv head's k and v whole in VMEM (as the
    forward holds them) and its dk and dv beside them as float32 output blocks
    that stay resident until the group is done. A (q block, k block) pair forms
    s, p = exp(s - lse), dp and ds ONCE and feeds all three gradients from them:
    dv[kb] += p^T do, dk[kb] += ds^T (scale q), dq += ds k[kb]: five products
    and one pass of exponentials a pair. The k loop is the forward's
    (``_band_loop``): hidden blocks are not visited, interior blocks are not
    masked, an edge block pays its one edge. The group's query heads are folded
    into the q rows (layout [B*Hkv, n_rep*Tq, ...]), so a kv head's dk and dv
    sum over them in place; a k block no q block sees keeps its zeros."""
    from jax.experimental import pallas as pl

    if has_seg:
        segq_ref, segk_ref, dq_ref, dk_ref, dv_ref = rest
    else:
        dq_ref, dk_ref, dv_ref = rest
    block_q, D = q_ref.shape
    Tk = k_ref.shape[0]
    step = pl.program_id(1)
    q_blk_idx = step % num_q_blocks  # the q block within its own head (positions)

    @pl.when(step == 0)
    def _init():
        dk_ref[:] = jnp.zeros_like(dk_ref)
        dv_ref[:] = jnp.zeros_like(dv_ref)

    q = _scaled(q_ref[:], scale)
    do = do_ref[:]
    lse = lse_ref[:][:, :1]            # [block_q, 1] (lanes identical)
    delta = delta_ref[:][:, :1]        # [block_q, 1]
    q_pos = _q_pos(q_blk_idx, block_q)
    sq = segq_ref[:][:, :1] if has_seg else None

    def make_tile(mask_causal: bool, mask_window: bool):
        def tile(kb, dq):
            ks = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
            k_blk, v_blk = k_ref[ks, :], v_ref[ks, :]
            s = jax.lax.dot_general(q, k_blk, _NT, preferred_element_type=jnp.float32)
            s = _visible(s, q_pos, _k_pos(kb, block_k), mask_causal, window if mask_window else 0,
                         sq, segk_ref[:1, ks] if has_seg else None)
            p = jnp.exp(s - lse)                                   # [block_q, block_k]
            dp = jax.lax.dot_general(do, v_blk, _NT, preferred_element_type=jnp.float32)
            # p and ds meet the MXU in the type of the operand they meet
            ds = (p * (dp - delta)).astype(q.dtype)
            dv_ref[ks, :] += jax.lax.dot_general(                  # p^T @ do
                p.astype(do.dtype), do, _TN, preferred_element_type=jnp.float32)
            dk_ref[ks, :] += jax.lax.dot_general(                  # ds^T @ (scale · q)
                ds, q, _TN, preferred_element_type=jnp.float32)
            return dq + jax.lax.dot_general(ds, k_blk, _NN, preferred_element_type=jnp.float32)

        return tile

    dq = _band_loop(q_blk_idx, block_q, block_k, pl.cdiv(Tk, block_k), causal, window, steady,
                    make_tile, jnp.zeros((block_q, D), jnp.float32))
    dq_ref[:] = (scale * dq).astype(dq_ref.dtype)


# what the backward asks of the chip's 128 MiB of VMEM: a kv head's k and v and
# its float32 dk and dv stay resident (double-buffered, 25 MB at 8192 x 128 in
# bfloat16) beside a steady q block's tiles, past the default scoped 16 MiB
_BWD_VMEM_LIMIT = 100 * 1024 * 1024


def _flash_bwd_impl(
    q, k, v, o, lse, do, causal: bool, block_q: int, block_k: int,
    segment_ids: jax.Array | None = None, window: int = 0,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Pallas flash backward, one call: p recomputed blockwise from (q, k, lse),
    once a (q block, k block) pair, for dq, dk and dv together."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    n_rep = H // Hkv
    scale = D ** -0.5
    num_q_blocks = Tq // block_q
    # the GQA group folded into the q rows: [B*Hkv, n_rep*Tq, ...]
    qg = q.reshape(B * Hkv, n_rep * Tq, D)
    dog = do.reshape(B * Hkv, n_rep * Tq, D)
    kf = k.reshape(B * Hkv, Tk, D)
    vf = v.reshape(B * Hkv, Tk, D)
    lseg = lse.reshape(B * Hkv, n_rep * Tq, _STAT_LANES)  # lane-replicated from the fwd
    # delta[i] = rowsum(do ⊙ o): the softmax-normalization term of ds
    delta = jnp.sum(dog.astype(jnp.float32) * o.reshape(qg.shape).astype(jnp.float32), axis=-1)
    deltag = jnp.broadcast_to(delta[:, :, None], lseg.shape)

    blk_q = pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0))
    row_q = pl.BlockSpec((None, block_q, _STAT_LANES), lambda b, i: (b, i, 0))
    full_k = pl.BlockSpec((None, Tk, D), lambda b, i: (b, 0, 0))
    in_specs = [blk_q, full_k, full_k, blk_q, row_q, row_q]
    operands = [qg, kf, vf, dog, lseg, deltag]
    has_seg = segment_ids is not None
    if has_seg:
        # seg arrays are [B, ...] per head (not group-folded): batch = b // Hkv,
        # q block within its head = i % num_q_blocks
        in_specs += [
            pl.BlockSpec((None, block_q, _STAT_LANES), lambda b, i: (b // Hkv, i % num_q_blocks, 0)),
            pl.BlockSpec((None, _STAT_LANES, Tk), lambda b, i: (b // Hkv, 0, 0)),
        ]
        operands += list(_seg_arrays(segment_ids, B, Tq))  # Tq == Tk (validated)
    # the pairs the walk visits, of the dense grid's: what the five products cost
    classes = flash_block_classes(Tq, Tk, block_q, block_k, causal, window)
    visited = B * H * (classes["interior"] + classes["edge"]) * block_q * block_k
    itemsize = q.dtype.itemsize
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_kernel, block_k=block_k, num_q_blocks=num_q_blocks, causal=causal,
            has_seg=has_seg, window=window, scale=scale,
            steady=_steady_runs(Tq, Tk, block_q, block_k, causal, window),
        ),
        grid=(B * Hkv, n_rep * num_q_blocks),
        in_specs=in_specs,
        out_specs=[blk_q, full_k, full_k],
        out_shape=[
            jax.ShapeDtypeStruct(qg.shape, q.dtype),
            jax.ShapeDtypeStruct(kf.shape, jnp.float32),
            jax.ShapeDtypeStruct(vf.shape, jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_BWD_VMEM_LIMIT),
        interpret=interpret(),
        name="flash_bwd",
        cost_estimate=pl.CostEstimate(
            flops=5 * 2 * visited * D,
            bytes_accessed=(3 * qg.size + 2 * kf.size) * itemsize + 2 * kf.size * 4,
            transcendentals=visited,
        ),
    )(*operands)
    return (
        dq.reshape(B, H, Tq, D),
        dk.reshape(B, Hkv, Tk, D).astype(k.dtype),
        dv.reshape(B, Hkv, Tk, D).astype(v.dtype),
    )


# -- trainable flash attention: pallas forward + pallas backward -------------
# pallas_call has no JVP rule (pallas guide §20: production kernels define a
# custom VJP). The backward is the FlashAttention-2 scheme: forward saves the
# per-row logsumexp; backward recomputes probabilities blockwise in VMEM (one
# kernel over q blocks: a tile's p and ds feed dq, dk and dv) — no T×T
# materialization.

# bq 256 / bk 512, chosen on a v5e at the training cells' shape ([2·32, 8192,
# 128] / 8 kv heads, bfloat16, band 4096; device time of a call from a trace,
# PERF.md section 6, PR 40). Forward: 7.01 ms at 256 x 512, 7.57 at 256 x 1024,
# 11.52 at 256 x 256; 512 x 512 no longer fits VMEM once a steady q block's
# nine tiles are one basic block. The grid computes 28.3 M pairs a head (1.12 x
# the band's); what a smaller k block saves in pairs it loses twice over in
# loop steps.
_BLOCK_Q = 256
_BLOCK_K = 512
# the one backward call's own, at the same shape (PERF.md section 6, PR 52):
# 12.97 ms at 512 x 512, 13.97 at 256 x 512, 15.27 at 512 x 256, 14.43 at
# 256 x 1024, 14.31 at 512 x 1024, 16.27 at 1024 x 256; a q block of 1024 rows
# spills (24.0 ms at 1024 x 512, 28.3 at 1024 x 1024). A tile's float32
# read-modify-write of dk and dv is by the tile, so the larger q block wins
# where the forward's does not fit; 512 x 512 computes the forward's 28.3 M
# pairs a head, its five products at 91% of the MXU's peak.
_BWD_BLOCK_Q = 512
_BWD_BLOCK_K = 512


def _block_sizes(Tq: int, Tk: int, block_q: int = _BLOCK_Q, block_k: int = _BLOCK_K) -> tuple[int, int]:
    """Largest blocks ≤ ``block_q`` / ``block_k`` (the forward's, by default)
    that DIVIDE the sequence lengths (halving until they do). With bq ≠ bk
    defaults, a length like 768 divides 256 but not 512 — every kernel entry
    point must agree on this rule or the grid reads padded garbage past the
    last block."""
    bq, bk = min(block_q, Tq), min(block_k, Tk)
    while bq > 1 and Tq % bq:
        bq //= 2
    while bk > 1 and Tk % bk:
        bk //= 2
    # Mosaic sublane alignment: a non-8-multiple block (Tq=132 → bq=132
    # divides but can't lower cleanly) is a hardware lowering risk. Degrade
    # it to 1 so EVERY caller's small-block fallback gate — including the
    # custom_vjp training entry points, which don't re-check alignment —
    # routes such shapes to the XLA reference path.
    if bq % 8:
        bq = 1
    if bk % 8:
        bk = 1
    return bq, bk


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_trainable(q, k, v, causal, window=0):
    return flash_attention(q, k, v, causal=causal, window=window)


def _flash_fwd(q, k, v, causal, window):
    from jax.ad_checkpoint import checkpoint_name

    Tq, Tk = q.shape[2], k.shape[2]
    bq, bk = _block_sizes(Tq, Tk)
    o, lse = _flash_fwd_lanes(q, k, v, causal, bq, bk, None, window)
    # Named so a remat policy can pin JUST the kernel outputs
    # (save_only_these_names("flash_o", "flash_lse")): the backward then
    # recomputes the cheap qkv matmuls but not the O(T²) flash forward.
    # The lse kept for the backward is one lane of the kernel's: a minor
    # dimension of _STAT_LANES floats is padded to 128 in HBM (268 MB a layer
    # at 2 x 32 x 8192 rows for 2 MB of numbers: compile-only, PR 47).
    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse[..., 0], "flash_lse")
    return o, (q, k, v, o, lse)


def _lse_lanes(lse: jax.Array) -> jax.Array:
    """[B, H, Tq] back to the lane-replicated form the backward kernels read."""
    return jnp.broadcast_to(lse[..., None], (*lse.shape, _STAT_LANES))


def _flash_bwd(causal, window, res, g):
    q, k, v, o, lse = res
    bq, bk = _block_sizes(q.shape[2], k.shape[2], _BWD_BLOCK_Q, _BWD_BLOCK_K)
    return _flash_bwd_impl(q, k, v, o, _lse_lanes(lse), g, causal, bq, bk, None, window)


_flash_trainable.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_trainable_seg(q, k, v, seg, causal, window=0):
    """Packed-sequence variant: seg [B, T] int; cotangent for seg is float0."""
    bq, bk = _block_sizes(q.shape[2], k.shape[2])
    return _flash_fwd_impl(q, k, v, causal, bq, bk, seg, window)[0]


def _flash_seg_fwd(q, k, v, seg, causal, window):
    from jax.ad_checkpoint import checkpoint_name

    Tq, Tk = q.shape[2], k.shape[2]
    bq, bk = _block_sizes(Tq, Tk)
    o, lse = _flash_fwd_lanes(q, k, v, causal, bq, bk, seg, window)
    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse[..., 0], "flash_lse")
    return o, (q, k, v, seg, o, lse)


def _flash_seg_bwd(causal, window, res, g):
    import numpy as np

    q, k, v, seg, o, lse = res
    bq, bk = _block_sizes(q.shape[2], k.shape[2], _BWD_BLOCK_Q, _BWD_BLOCK_K)
    dq, dk, dv = _flash_bwd_impl(q, k, v, o, _lse_lanes(lse), g, causal, bq, bk, seg, window)
    return dq, dk, dv, np.zeros(seg.shape, jax.dtypes.float0)


_flash_trainable_seg.defvjp(_flash_seg_fwd, _flash_seg_bwd)


#: What a scanned decoder block may save for its backward: a ladder of named
#: activations, cumulative, in order of time bought a byte. Rung 0 saves
#: nothing (every layer's forward runs again inside its backward); rung 1 is
#: this module's kernel outputs, with the MoE routing outputs ("moe_route",
#: parallel/expert.py: tiny tensors whose replay re-runs the whole vector-bound
#: gating pipeline) and the fused expert-MLP output ("moe_gemm",
#: ops/moe_gemm.py: the one activation whose replay runs three grouped GEMMs);
#: then the residual after attention, q/k/v after rope (models/llama.py:_block)
#: and the two [B, T, F] products of the FFN (ops/layers.py:swiglu). A name
#: the traced block does not hold saves nothing. train/trainer.py chooses the
#: rung where the policy is "auto" and the loop runs on a device that reports
#: its memory, and which of HOST_NAMES (below: what was measured) wait in the
#: host's memory instead.
_LADDER_STEPS = (
    ("flash_o", "flash_lse", "moe_route", "moe_gemm"),
    ("attn_res",), ("attn_qkv",), ("ffn_gate",), ("ffn_up",),
)
REMAT_LADDER = tuple(
    sum(_LADDER_STEPS[:i], ()) for i in range(len(_LADDER_STEPS) + 1))
#: The names that may wait in the host's pinned memory between a layer's
#: forward and its backward instead of being saved or replayed
#: (``scan_blocks``; train/trainer.remat_candidates). A property of the name,
#: not a setting: where in a layer's forward the value is formed, against
#: what is left of that forward to send it behind. q, k and v after rope are
#: formed in a layer's first eighth and leave under the rest of it; the FFN's
#: two products are formed when a fifth of the layer is left, and their 29 ms
#: of copy each (0.47 GB at 2 x 8192 x 14336) stand exposed for more than
#: their replay costs, whichever turn of the loop sends them (PERF.md
#: section 6, PR 56); "attn_res" buys 4 ms a GB and flash's outputs are small.
HOST_NAMES = ("attn_qkv",)
#: the policies that are rungs by another name ("auto" outside the train loop
#: IS "full": only the loop knows a device's memory)
_NAMED_RUNGS = {"full": REMAT_LADDER[0], "auto": REMAT_LADDER[0], "flash": REMAT_LADDER[1]}


class Rung(NamedTuple):
    """A rung with a host part: what the train loop hands a model as
    ``remat_policy`` once it has chosen (train/loop.py). ``saved`` are the
    names a plain tuple would hold; ``host`` names wait in the host's pinned
    memory between a layer's forward and its backward (``scan_blocks``)."""
    saved: tuple[str, ...]
    host: tuple[str, ...] = ()


def remat_block(block_fn, remat: bool, policy: str | tuple[str, ...] = "full"):
    """Wrap a scanned decoder block in the configured remat policy.

    Lives here because the ladder starts at THIS module's checkpoint names
    (flash_o / flash_lse from _flash_fwd) — models must not hardcode them.
    ``policy`` is a rung (a tuple of names: those values are saved, the rest
    of the block is recomputed), one of the rungs by name ("full": nothing
    saved; "flash": the kernel outputs, so the backward never replays the
    O(T²) forward kernel; "auto": see REMAT_LADDER), or "dots" (XLA's own
    policy: save matmul outputs).
    """
    if not remat:
        return block_fn
    if policy == "dots":
        return jax.checkpoint(
            block_fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    if isinstance(policy, str):
        if policy not in _NAMED_RUNGS:
            raise ValueError(
                f"remat_policy must be auto|full|dots|flash or a tuple of names, got {policy!r}")
        policy = _NAMED_RUNGS[policy]
    if not policy:
        return jax.checkpoint(block_fn)
    return jax.checkpoint(
        block_fn, policy=jax.checkpoint_policies.save_only_these_names(*policy))


def scan_blocks(block_fn, carry, layers, remat: bool, policy: str | tuple[str, ...] | Rung = "full"):
    """The stacked ``layers`` through ``remat_block(block_fn, remat, policy)``:
    ``jax.lax.scan``'s ``(carry, ys)``. A ``Rung`` with a host part takes
    ``_scan_host_kept``; every other policy is the one ``lax.scan`` it was."""
    if isinstance(policy, Rung):
        if remat and policy.host:
            return _scan_host_kept(block_fn, carry, layers, policy)
        policy = policy.saved
    return jax.lax.scan(remat_block(block_fn, remat, policy), carry, layers)


def _names_made_of(jaxpr, given: list[set]) -> list[set]:
    """The checkpoint names each output of ``jaxpr`` is made of (None stands
    for an argument or a constant): a value the policy saved is the output of
    the ``name`` equation that named it, and can reach the pullback through a
    ``reduce_precision`` or a nested jit."""
    src = dict(zip(jaxpr.invars, given))
    src.update((v, {None}) for v in jaxpr.constvars)

    def of(v):
        return set() if isinstance(v, Literal) else src[v]

    for eqn in jaxpr.eqns:
        ins = [of(v) for v in eqn.invars]
        inner = list(jax.core.jaxprs_in_params(eqn.params))
        if eqn.primitive.name == "name":
            outs = [{eqn.params["name"]}]
        elif len(inner) == 1 and len(inner[0].invars) == len(ins) and not inner[0].constvars:
            outs = _names_made_of(inner[0], ins)
        else:
            outs = [set().union(*ins)] * len(eqn.outvars)
        src.update(zip(eqn.outvars, outs))
    return [of(v) for v in jaxpr.outvars]


def _scan_host_kept(block_fn, carry, layers, rung: Rung):
    """``lax.scan`` of the block under ``save_only_these_names(saved + host)``
    whose ``host`` values wait in the host's pinned memory, with the loop's
    backward written out so that a layer's values come back WHILE THE LAYER
    ABOVE IT runs its backward: the body of the backward's loop starts the
    copy of layer i-1's values and hands them to the next turn in the carry.
    ``save_and_offload_only_these_names`` inside a plain scan asks for a
    layer's values at the top of that layer's own backward, and the compiler
    waits for them there: 28.6 ms a layer for one FFN product at 2 x 8192 x
    14336, against 7.8 ms to replay it (my chip runs, PR 56). The device
    holds two layers' host values at a time (here and on its way), whatever
    the depth. Values, types and the backward's order of operations are the
    saved rung's: a value read back is the value that was written."""
    block = jax.checkpoint(
        block_fn, policy=jax.checkpoint_policies.save_only_these_names(*rung.saved, *rung.host))
    n = jax.tree.leaves(layers)[0].shape[0]

    # the block and its pullback, traced once: the outputs, then the
    # pullback's leaves (what the backward wants of the forward)
    trees = []

    def outputs_and_kept(c, layer):
        out, pullback = jax.vjp(block, c, layer)
        flat_out, out_tree = jax.tree.flatten(out)
        kept, kept_tree = jax.tree.flatten(pullback)
        trees[:] = [out_tree, kept_tree, len(flat_out)]
        return flat_out + kept

    traced = jax.make_jaxpr(outputs_and_kept)(
        carry, jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), layers))
    out_tree, kept_tree, n_out = trees
    block_and_kept = jaxpr_as_fun(traced)
    kept_vars = traced.jaxpr.outvars[n_out:]
    made_of = _names_made_of(traced.jaxpr, [{None}] * len(traced.jaxpr.invars))[n_out:]
    # a leaf that is an argument or a constant of the trace is not kept: the
    # backward has the argument; one made of host names alone waits on the
    # host; any other is a value the policy saved, and stays on the device
    given = {v: ("argument", k) for k, v in enumerate(traced.jaxpr.invars)}
    given.update((v, ("constant", k)) for k, v in enumerate(traced.jaxpr.constvars))
    on_host = [j for j, (v, names) in enumerate(zip(kept_vars, made_of))
               if not isinstance(v, Literal) and v not in given and names and names <= set(rung.host)]
    on_device = [j for j, v in enumerate(kept_vars)
                 if not isinstance(v, Literal) and v not in given and j not in on_host]
    host_avals = [kept_vars[j].aval for j in on_host]
    to_host = jax.memory.Space.Host
    to_device = jax.memory.Space.Device

    # one array a width and type: two copies in flight at once wait for one
    # another (my chip runs, PR 56), so the values travel as one
    groups: dict[tuple, list[int]] = {}
    for k, a in enumerate(host_avals):
        groups.setdefault((a.shape[-1], a.dtype), []).append(k)

    def pack(values):
        return [jnp.concatenate([values[k].reshape(-1, width) for k in ks])
                for (width, _), ks in groups.items()]

    def unpack(packed):
        out = {}
        for ks, rows in zip(groups.values(), packed):
            at = 0
            for k in ks:
                n_rows = host_avals[k].size // host_avals[k].shape[-1]
                out[k] = rows[at:at + n_rows].reshape(host_avals[k].shape)
                at += n_rows
        return [out[k] for k in range(len(host_avals))]

    @jax.custom_vjp
    def run(carry, layers):
        return jax.lax.scan(block, carry, layers)

    def forward(carry, layers):
        def body(c, layer):
            flat = block_and_kept(*jax.tree.leaves((c, layer)))
            (c_out, y), kept = jax.tree.unflatten(out_tree, flat[:n_out]), flat[n_out:]
            return c_out, (y, c, [kept[j] for j in on_device],
                           [jax.device_put(v, to_host) for v in pack([kept[j] for j in on_host])])
        carry_out, (ys, carries, device, host) = jax.lax.scan(body, carry, layers)
        return (carry_out, ys), (carries, device, host, layers)

    def backward(kept, ct):
        carries, device, host, layers = kept

        def fetch(i):
            return [jax.device_put(jax.lax.dynamic_index_in_dim(h, i, keepdims=False), to_device)
                    for h in host]

        def body(state, xs):
            ct_c, here = state
            i, c, layer, device_i, ct_y = xs
            ahead = fetch(jnp.maximum(i - 1, 0))
            arguments = {"argument": jax.tree.leaves((c, layer)), "constant": traced.consts}
            leaves = [v.val if isinstance(v, Literal) else arguments[given[v][0]][given[v][1]] if v in given
                      else None for v in kept_vars]
            for j, v in zip(on_device + on_host, device_i + unpack(here)):
                leaves[j] = v
            d_c, d_layer = jax.tree.unflatten(kept_tree, leaves)((ct_c, ct_y))
            return (d_c, ahead), d_layer

        (d_carry, _), d_layers = jax.lax.scan(
            body, (ct[0], fetch(n - 1)), (jnp.arange(n), carries, layers, device, ct[1]), reverse=True)
        return d_carry, d_layers

    run.defvjp(forward, backward)
    return run(carry, layers)


def named_bytes(fn, *args, has_aux: bool = False) -> dict[str, int]:
    """Bytes of the values ``fn`` names with ``checkpoint_name``, by name, at
    the shapes of ``args`` (arrays or ShapeDtypeStructs): what a rung of
    REMAT_LADDER keeps alive from the forward to the backward. One abstract
    trace of the forward as differentiation runs it (a custom_vjp names its
    outputs in its fwd rule, which a plain forward never traces); a scan's
    body counts once an iteration, so a scanned block counts once a layer."""
    jaxpr = jax.make_jaxpr(lambda *a: jax.vjp(fn, *a, has_aux=has_aux)[0])(*args)
    out: dict[str, int] = {}

    def walk(jaxpr, times):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "name":
                aval = eqn.outvars[0].aval
                out[eqn.params["name"]] = (
                    out.get(eqn.params["name"], 0) + times * aval.size * aval.dtype.itemsize)
            inner = times * eqn.params["length"] if eqn.primitive.name == "scan" else times
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, inner)

    walk(jaxpr.jaxpr, 1)
    return out


def _flash_selected(impl: str, Tq: int, Tk: int) -> bool:
    """The one copy of mha's kernel-or-reference rule (see ``mha``)."""
    if impl not in ("auto", "flash", "reference"):
        raise ValueError(f"impl must be auto|flash|reference, got {impl!r}")
    bq, bk = _block_sizes(Tq, Tk)
    # ragged lengths shrink the blocks; below 128 the kernel grid is
    # lane-starved and the XLA reference path wins
    fits = bq >= 128 and bk >= 128 and Tq >= 128
    if impl == "flash" and not fits:
        raise ValueError(
            f"impl='flash' cannot be honoured at Tq={Tq}, Tk={Tk} (blocks "
            f"{bq}x{bk}; the kernel needs >= 128 rows a block): use "
            "impl='auto' or 'reference'")
    return impl == "flash" or (impl == "auto" and fits and jax.default_backend() != "cpu")


def mha_on_mesh(
    q: jax.Array, k: jax.Array, v: jax.Array, *, mesh,
    segment_ids: jax.Array | None = None, **kw,
) -> jax.Array:
    """``mha`` for [B, H, T, D] arrays laid out as every model here lays them
    out on ``mesh``: batch over data x fsdp, heads over model. GSPMD cannot
    partition a Mosaic kernel, so where the flash kernel is selected on a
    mesh of more than one device it runs per shard under a fully manual
    ``shard_map``; the XLA reference partitions by itself and stays bare."""
    if mesh is None or mesh.size == 1 or not _flash_selected(
            kw.get("impl", "auto"), q.shape[2], k.shape[2]):
        return mha(q, k, v, segment_ids=segment_ids, **kw)
    from jax.sharding import PartitionSpec as P

    batch = tuple(a for a in ("data", "fsdp") if a in mesh.shape)
    heads = "model" if "model" in mesh.shape else None
    n_head_shards = mesh.shape.get("model", 1)
    if q.shape[1] % n_head_shards:
        raise ValueError(
            f"n_heads {q.shape[1]} must divide by the 'model' axis ({n_head_shards})")
    if k.shape[1] % n_head_shards:  # GQA narrower than the axis: broadcast first
        n_rep = q.shape[1] // k.shape[1]
        k, v = repeat_kv(k, n_rep), repeat_kv(v, n_rep)
    spec = P(batch, heads, None, None)
    if segment_ids is None:
        fn, args, in_specs = functools.partial(mha, **kw), (q, k, v), (spec, spec, spec)
    else:
        fn = lambda q, k, v, seg: mha(q, k, v, segment_ids=seg, **kw)  # noqa: E731
        args, in_specs = (q, k, v, segment_ids), (spec, spec, spec, P(batch, None))
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=spec,
        axis_names=set(mesh.axis_names), check_vma=False,
    )(*args)


def mha(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    impl: str = "auto",
    segment_ids: jax.Array | None = None,
    window: int = 0,
) -> jax.Array:
    """Dispatcher. ``impl="auto"`` selects by what it can observe: the Pallas
    flash kernel off the CPU where the shape feeds it (>= 128 rows a block),
    the XLA reference otherwise. An explicit ``"flash"`` that cannot be
    honoured raises rather than running the reference in its place.

    k/v may carry fewer heads than q (GQA/MQA): the flash kernels read kv
    heads in place via index-map aliasing; the reference path broadcasts.
    ``segment_ids`` [B, T] confines attention within packed segments.
    ``window`` > 0: sliding-window (Mistral/Mixtral) attention band.
    """
    if q.shape[1] % k.shape[1]:
        raise ValueError(f"n_heads {q.shape[1]} must be divisible by n_kv_heads {k.shape[1]}")
    n_rep = q.shape[1] // k.shape[1]
    Tq, Tk = q.shape[2], k.shape[2]
    if _flash_selected(impl, Tq, Tk):
        if segment_ids is not None:
            if Tq != Tk:
                raise ValueError(f"segment_ids requires Tq == Tk, got {Tq} vs {Tk}")
            return _flash_trainable_seg(q, k, v, segment_ids, causal, window)
        return _flash_trainable(q, k, v, causal, window)
    return attention_reference(
        q, repeat_kv(k, n_rep), repeat_kv(v, n_rep),
        causal=causal, segment_ids=segment_ids, window=window,
    )


# -- a prefill chunk's queries against a request's staged keys (serving) -----------------------------------

def _chunk_prefill_kernel(meta_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *, scale, block_q, block_k):
    """One (head, q block, k tile): the running max, sum and accumulator stay in
    scratch over the k tiles, as ``sparse_attention._flash_masked_kernel`` keeps
    them. meta: [pos0, last tile of q block 0, of q block 1, ..., the layer]."""
    from jax.experimental import pallas as pl

    i, j = pl.program_id(1), pl.program_id(2)
    first_row = meta_ref[0] + i * block_q                                    # the q block's first position

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def tile(masked: bool):
        q, k, v = q_ref[0], k_ref[0], v_ref[0]                               # [bq, d], [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        if masked:
            q_pos = first_row + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_new = jnp.maximum(m_sc[...], s.max(axis=1, keepdims=True))
        p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_sc[...] - m_new)
        l_sc[...] = l_sc[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    live = j <= meta_ref[1 + i]
    diagonal = (j + 1) * block_k - 1 > first_row                             # a key of the tile lies past the block's first row
    pl.when(live & diagonal)(lambda: tile(True))
    pl.when(live & jnp.logical_not(diagonal))(lambda: tile(False))

    @pl.when(j == pl.num_programs(2) - 1)
    def _done():
        o_ref[0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k"))
def chunk_prefill_attention(q, k, v, pos0, n_keys, layer=None, *, block_q: int = 256, block_k: int = 512):
    """Causal attention of a prefill chunk over a request's staged keys. q [H, T,
    d]: the chunk's queries, at positions pos0 .. pos0 + T; k, v [Hkv, Tk, d]: the
    request's keys and values at their true positions, the chunk's own among them
    (Tk the staging's length, whatever has been written of it); pos0, n_keys []
    int32: the chunk's first position and the keys that exist. Returns [H, T, d].
    With ``layer`` ([] int32), k and v are a request's WHOLE staging [L, 1, Hkv, Tk,
    d] and the call reads that layer's tiles of it: one layer's slice handed in is
    a copy of it a call (a Mosaic call's operand needs a buffer of its own).

    Flash attention with the KEY TILES ON THE GRID (head, q block, k tile), where
    ``flash_attention`` holds a head's keys and values whole in VMEM: a staging of
    20k positions and more is read a tile at a time. The causal mask comes from the
    positions (an iota on the tiles the diagonal crosses, none on the tiles wholly
    before it); a tile past a q block's last row, or past ``n_keys``, is neither
    fetched (its index map repeats the last live tile) nor computed. Softmax in
    float32, scale ``d ** -0.5``; q head h reads kv head ``h // (H // Hkv)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H, T, d = q.shape
    Hkv, Tk = k.shape[-3:-1]
    if (k.ndim == 5) != (layer is not None):
        raise ValueError(f"k {k.shape}: a staging [L, 1, Hkv, Tk, d] comes with its layer, keys [Hkv, Tk, d] without one")
    bq, bk = min(block_q, T), min(block_k, Tk)
    if T % bq or Tk % bk or H % Hkv:
        raise ValueError(f"{T} queries / {Tk} keys do not divide into tiles of {bq} / {bk}, or {H} heads into {Hkv}")
    nq, nk, n_rep = T // bq, Tk // bk, H // Hkv
    last_row = pos0 + (jnp.arange(nq, dtype=jnp.int32) + 1) * bq - 1
    last = jnp.minimum(last_row, jnp.maximum(n_keys - 1, 0)) // bk
    meta = jnp.concatenate([jnp.reshape(pos0, (1,)).astype(jnp.int32), last.astype(jnp.int32),
                            jnp.reshape(0 if layer is None else layer, (1,)).astype(jnp.int32)])
    if layer is None:
        kv_spec = pl.BlockSpec((1, bk, d), lambda h, i, j, meta: (h // n_rep, jnp.minimum(j, meta[1 + i]), 0))
    else:
        kv_spec = pl.BlockSpec((None, None, 1, bk, d), lambda h, i, j, meta: (meta[1 + nq], 0, h // n_rep, jnp.minimum(j, meta[1 + i]), 0))
    return pl.pallas_call(
        functools.partial(_chunk_prefill_kernel, scale=d ** -0.5, block_q=bq, block_k=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H, nq, nk),
            in_specs=[pl.BlockSpec((1, bq, d), lambda h, i, j, meta: (h, i, 0)), kv_spec, kv_spec],
            out_specs=pl.BlockSpec((1, bq, d), lambda h, i, j, meta: (h, i, 0)),
            scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32), pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret(),
        name="chunk_prefill_attention",
        cost_estimate=pl.CostEstimate(flops=4 * H * T * Tk * d, transcendentals=H * T * Tk,
                                      bytes_accessed=(2 * H * T * d + 2 * Hkv * nq * Tk * d) * q.dtype.itemsize),
    )(meta, q, k, v)


# -- differential attention (arXiv:2410.05258) over the one-map kernels -------------------------------
#
# A head PAIR subtracts two softmax maps, both applied to the pair's values: with the query heads in two stripes
# (q1 the even heads, q2 the odd) and the kv heads likewise (k1, v1 even; k2, v2 odd), a pair's values are Vp =
# [v1 | v2] (2 dh wide) and
#
#     A1 = softmax(q1 k1^T / sqrt(dh)) Vp     A2 = softmax(q2 k2^T / sqrt(dh)) Vp     o = norm(A1 - lam A2) (1 - lam0)
#
# Kv heads 2p and 2p + 1 lie side by side, so a cache that holds PAIRS, [.., Hkv / 2, positions, 2 dh] with Kp = [k1
# | k2] and Vp = [v1 | v2], is the same bytes under another shape, and a pair of 2 x 64 is a whole tile of 128 lanes
# where a head of 64 is half of one. A query widened to the pair with zeros where the OTHER stripe's key lies, [q1 |
# 0] and [0 | q2], has q1 . k1 and q2 . k2 as its scores against Kp: both maps are then plain grouped-query
# attention at heads of 2 dh over Hkv / 2 kv heads, ONE call of a one-map kernel that reads every key and value once
# (the four calls q1k1v1, q1k1v2, q2k2v1, q2k2v2 at heads of dh read each twice). The kernels scale by (2 dh) ** -0.5,
# so the widened queries carry sqrt(2). Nothing of the kernels knows: their one-map calls are the programs they were.

def differential_queries(q: jax.Array, dtype=None) -> jax.Array:
    """q [..., H, dh] (float32 where the caller can: the sqrt(2) is then rounded once, with the cast) -> the
    widened queries [..., H, 2 dh] in `dtype`: an even head [q sqrt(2) | 0], an odd head [0 | q sqrt(2)]."""
    H, dh = q.shape[-2:]
    scaled = q.astype(jnp.float32) * 2.0 ** 0.5
    even = (jnp.arange(H) % 2 == 0)[:, None]
    wide = jnp.concatenate([jnp.where(even, scaled, 0.0), jnp.where(even, 0.0, scaled)], axis=-1)
    return wide.astype(q.dtype if dtype is None else dtype)


def differential_combine(o: jax.Array, lam, lam0, weight: jax.Array, eps: float) -> jax.Array:
    """o [..., H, 2 dh], the one-map kernel's output for the widened queries (head 2a is A1 of pair-row a, head 2a + 1
    its A2) -> RMSNorm over 2 dh of (A1 - lam A2), times `weight` and (1 - lam0): [..., H / 2, 2 dh] float32; row a
    reads back as heads 2a, 2a + 1 of dh."""
    *lead, H, wide = o.shape
    maps = o.astype(jnp.float32).reshape(*lead, H // 2, 2, wide)
    d = maps[..., 0, :] - lam * maps[..., 1, :]
    return d * jax.lax.rsqrt(jnp.mean(d * d, axis=-1, keepdims=True) + eps) * weight.astype(jnp.float32) * (1.0 - lam0)


def differential_chunk_prefill_attention(q, k, v, pos0, n_keys, layer=None):
    """Both maps of a prefill chunk over a request's staged PAIRS, one call of `chunk_prefill_attention`: q [T, H, dh]
    (float32 or the cache's type) at positions pos0 .. pos0 + T; k, v the staging of pairs [L, 1, Hkv / 2, Tk, 2 dh]
    with its `layer` (or [Hkv / 2, Tk, 2 dh] without). Returns [T, H, 2 dh] for `differential_combine`."""
    wide = differential_queries(q, k.dtype).transpose(1, 0, 2)
    return chunk_prefill_attention(wide, k, v, pos0, n_keys, layer).transpose(1, 0, 2)


def differential_window_prefill_attention(q, k, v, tail_k, tail_v, pos0, window: int):
    """Both maps of a prefill chunk of a WINDOW layer, one call of `flash_attention` in its band: q [T, H, dh]; k, v [T,
    Hkv / 2, 2 dh] the chunk's own pairs; tail_k, tail_v [Hkv / 2, window, 2 dh] the pairs of positions pos0 - window
    .. pos0 - 1 (those below 0 do not exist: a segment of their own). Returns ([T, H, 2 dh], the keys [Hkv / 2, window
    + T, 2 dh] and the values the call saw, tail first, for the caller to keep its next tail from)."""
    T, H = q.shape[:2]
    ek = jnp.concatenate([tail_k, k.transpose(1, 0, 2).astype(tail_k.dtype)], axis=1)
    ev = jnp.concatenate([tail_v, v.transpose(1, 0, 2).astype(tail_v.dtype)], axis=1)
    wide = differential_queries(q, ek.dtype).transpose(1, 0, 2)
    eq = jnp.concatenate([jnp.zeros((H, window, wide.shape[2]), wide.dtype), wide], axis=1)   # flash wants as many queries as keys
    seg = jnp.concatenate([pos0 - window + jnp.arange(window) >= 0, jnp.ones((T,), bool)]).astype(jnp.int32)[None]
    o = flash_attention(eq[None], ek[None], ev[None], causal=True, window=window, segment_ids=seg)[0, :, window:]
    return o.transpose(1, 0, 2), ek, ev
