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
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

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

def _flash_kernel(
    q_ref, k_ref, v_ref, *rest,
    block_k: int, causal: bool, has_seg: bool, window: int, scale: float,
):
    """Grid: (B*H, Tq//block_q). Online softmax over KV blocks in VMEM.

    Also emits the per-row logsumexp (scaled-score space) so the Pallas
    backward can recompute probabilities blockwise without the T×T matrix.
    With ``has_seg``, two extra refs carry packed-sequence segment ids
    (q-side rows, k-side cols) and scores cross segments are masked.
    ``window`` > 0 adds the sliding-window band: k blocks wholly before the
    window are skipped (no DMA, no flops), partial blocks are masked.
    """
    from jax.experimental import pallas as pl

    if has_seg:
        segq_ref, segk_ref, o_ref, lse_ref = rest
    else:
        o_ref, lse_ref = rest
    block_q, D = q_ref.shape
    Tk = k_ref.shape[0]
    q_blk_idx = pl.program_id(1)
    q = q_ref[:] .astype(jnp.float32) * scale
    q_pos = q_blk_idx * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    sq = segq_ref[:][:, :1] if has_seg else None  # [block_q, 1]

    num_k_blocks = pl.cdiv(Tk, block_k)
    kb_start = 0
    if causal:
        # only blocks at or below the diagonal contribute
        num_k_blocks = jnp.minimum(num_k_blocks, (q_blk_idx + 1) * block_q // block_k + 1)
    if window > 0:
        # first k position any row of this q block can see: q_first−window+1
        kb_start = jnp.maximum(0, (q_blk_idx * block_q - window + 1) // block_k)

    def body(kb, carry):
        o, m, l = carry
        k_blk = k_ref[pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [block_q, block_k]
        k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        if causal:
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if window > 0:
            s = jnp.where(q_pos - k_pos < window, s, NEG_INF)
        if has_seg:
            sk = segk_ref[:1, pl.ds(kb * block_k, block_k)]  # [1, block_k]
            s = jnp.where(sq == sk, s, NEG_INF)
        m_b = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_b)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        o_new = o * alpha + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return o_new, m_new, l_new

    o0 = jnp.zeros((block_q, D), jnp.float32)
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    o, m, l = jax.lax.fori_loop(kb_start, num_k_blocks, body, (o0, m0, l0))
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
        window=window, scale=scale,
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
    ``block_q``/``block_k`` default to the tuned module constants, shrunk
    to divide the sequence lengths (``_block_sizes``)."""
    B, H, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    if H % Hkv:
        raise ValueError(f"n_heads {H} must be divisible by n_kv_heads {Hkv}")
    if segment_ids is not None and Tq != Tk:
        raise ValueError(f"segment_ids requires Tq == Tk, got {Tq} vs {Tk}")
    auto_bq, auto_bk = _tuned_blocks("flash_fwd", q, Hkv, Tk)
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


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    block_k: int, causal: bool, has_seg: bool, window: int, scale: float,
):
    """Grid: (B*H, Tq//block_q). dq[i] = scale · Σ_kb ds[i,kb] @ k[kb]."""
    from jax.experimental import pallas as pl

    if has_seg:
        segq_ref, segk_ref, dq_ref = rest
    else:
        (dq_ref,) = rest
    block_q, D = q_ref.shape
    Tk = k_ref.shape[0]
    q_blk_idx = pl.program_id(1)
    q = q_ref[:].astype(jnp.float32)
    do = do_ref[:].astype(jnp.float32)
    lse = lse_ref[:][:, :1]            # [block_q, 1] (lanes identical)
    delta = delta_ref[:][:, :1]        # [block_q, 1]
    q_pos = q_blk_idx * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    sq = segq_ref[:][:, :1] if has_seg else None

    num_k_blocks = pl.cdiv(Tk, block_k)
    kb_start = 0
    if causal:
        num_k_blocks = jnp.minimum(num_k_blocks, (q_blk_idx + 1) * block_q // block_k + 1)
    if window > 0:
        kb_start = jnp.maximum(0, (q_blk_idx * block_q - window + 1) // block_k)

    def body(kb, dq):
        k_blk = k_ref[pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = scale * jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        if causal:
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if window > 0:
            s = jnp.where(q_pos - k_pos < window, s, NEG_INF)
        if has_seg:
            sk = segk_ref[:1, pl.ds(kb * block_k, block_k)]
            s = jnp.where(sq == sk, s, NEG_INF)
        p = jnp.exp(s - lse)                                   # [block_q, block_k]
        dp = jax.lax.dot_general(                              # do @ v^T
            do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        dq = dq + jax.lax.dot_general(                         # ds @ k
            ds, k_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return dq

    dq = jax.lax.fori_loop(kb_start, num_k_blocks, body, jnp.zeros((block_q, D), jnp.float32))
    dq_ref[:] = (scale * dq).astype(dq_ref.dtype)


def _dkv_block_contrib(
    q_blk, do_blk, lse_blk, delta_blk, k, v, q_pos, k_pos, causal, scale,
    sq=None, sk=None, window: int = 0,
):
    """One q-block's contribution to (dk, dv) for one k block — the shared
    gradient math of both dkv variants (they differ only in data staging).
    Returns dk WITHOUT the final `scale` factor (callers apply it)."""
    s = scale * jax.lax.dot_general(
        q_blk, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [block_q, block_k]
    if causal:
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    if window > 0:
        s = jnp.where(q_pos - k_pos < window, s, NEG_INF)
    if sq is not None:
        s = jnp.where(sq == sk, s, NEG_INF)
    p = jnp.exp(s - lse_blk)
    dv_c = jax.lax.dot_general(                    # p^T @ do
        p, do_blk, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    dp = jax.lax.dot_general(                      # do @ v^T
        do_blk, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta_blk)
    dk_c = jax.lax.dot_general(                    # ds^T @ q
        ds, q_blk, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    return dk_c, dv_c


def _flash_bwd_dkv_kernel_resident(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    block_q: int, n_rep: int, causal: bool, has_seg: bool, window: int, scale: float,
):
    """Grid: (B*Hkv, Tk//block_k) with the whole [n_rep·Tq, D] q/do staged in
    VMEM — the fast variant for moderate sequence lengths: causally-skipped
    q blocks cost neither DMA nor flops (the fori_loop starts at the
    diagonal). Selected when the staged operands fit the VMEM budget."""
    from jax.experimental import pallas as pl

    if has_seg:
        segq_ref, segk_ref, dk_ref, dv_ref = rest
    else:
        dk_ref, dv_ref = rest
    block_k, D = k_ref.shape
    Tq = q_ref.shape[0] // n_rep
    k_blk_idx = pl.program_id(1)
    k = k_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)
    k_pos = k_blk_idx * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    sk = segk_ref[:1, :] if has_seg else None  # [1, block_k] (this k block)

    num_q_blocks = pl.cdiv(Tq, block_q)
    qb_start = (k_blk_idx * block_k) // block_q if causal else 0
    qb_end = num_q_blocks
    if window > 0:
        # rows beyond the window of this k block's LAST position contribute 0
        last_k = k_blk_idx * block_k + block_k - 1
        qb_end = jnp.minimum(num_q_blocks, (last_k + window - 1) // block_q + 1)

    def make_body(g_off: int):
        def body(qb, carry):
            dk, dv = carry
            q_blk = q_ref[pl.ds(g_off + qb * block_q, block_q), :].astype(jnp.float32)
            do_blk = do_ref[pl.ds(g_off + qb * block_q, block_q), :].astype(jnp.float32)
            lse_blk = lse_ref[pl.ds(g_off + qb * block_q, block_q), :][:, :1]
            delta_blk = delta_ref[pl.ds(g_off + qb * block_q, block_q), :][:, :1]
            q_pos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
            # seg rows are PER HEAD (not group-folded): index by qb directly
            sq = segq_ref[pl.ds(qb * block_q, block_q), :][:, :1] if has_seg else None
            dk_c, dv_c = _dkv_block_contrib(
                q_blk, do_blk, lse_blk, delta_blk, k, v, q_pos, k_pos, causal, scale,
                sq, sk, window,
            )
            return dk + dk_c, dv + dv_c

        return body

    zeros = jnp.zeros((block_k, D), jnp.float32)
    dk, dv = zeros, zeros
    for g in range(n_rep):  # static group unroll
        dk, dv = jax.lax.fori_loop(qb_start, qb_end, make_body(g * Tq), (dk, dv))
    dk_ref[:] = (scale * dk).astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


# staged q/do bytes (bf16, double-buffered) beyond which the resident dkv
# variant would exceed the ~16M scoped-VMEM budget → use the streaming grid
_DKV_RESIDENT_MAX_QROWS = 4096


def _flash_bwd_dkv_kernel(
    kb_ref, qrow_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    num_q_blocks: int, causal: bool, has_seg: bool, window: int, scale: float,
):
    """Grid: (B*Hkv, n_pairs) — one causally-contributing (k block, q block)
    pair per step, streamed via scalar-prefetched index arrays.

    Only one q block is staged in VMEM per step (long sequences would blow
    the VMEM budget if the whole [n_rep·Tq, D] q were staged, as an earlier
    design did), and — unlike a dense (k block × q block) grid — pairs above
    the causal diagonal are never enumerated, so they cost neither DMA nor a
    grid step. dk/dv output blocks are revisited across consecutive pairs of
    the same k block (pairs are sorted by k block), accumulating in f32 in
    VMEM; GQA group members are folded into the q dim (layout
    [B*Hkv, n_rep*Tq, …]), so each pair's q-block index within its own head
    (for position masking) is ``qrow % num_q_blocks``.
    """
    from jax.experimental import pallas as pl

    if has_seg:
        segq_ref, segk_ref, dk_ref, dv_ref = rest
    else:
        dk_ref, dv_ref = rest
    block_q = q_ref.shape[0]
    block_k = k_ref.shape[0]
    j = pl.program_id(1)
    k_blk_idx = kb_ref[j]
    qb = qrow_ref[j] % num_q_blocks  # q-block index within this member's head
    first = jnp.logical_or(j == 0, k_blk_idx != kb_ref[jnp.maximum(j - 1, 0)])

    @pl.when(first)
    def _init():
        dk_ref[:] = jnp.zeros_like(dk_ref)
        dv_ref[:] = jnp.zeros_like(dv_ref)

    k = k_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)
    k_pos = k_blk_idx * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    q_blk = q_ref[:].astype(jnp.float32)
    do_blk = do_ref[:].astype(jnp.float32)
    lse_blk = lse_ref[:][:, :1]
    delta_blk = delta_ref[:][:, :1]
    q_pos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    sq = segq_ref[:][:, :1] if has_seg else None
    sk = segk_ref[:1, :] if has_seg else None
    dk_c, dv_c = _dkv_block_contrib(
        q_blk, do_blk, lse_blk, delta_blk, k, v, q_pos, k_pos, causal, scale, sq, sk, window
    )
    dk_ref[:] += scale * dk_c
    dv_ref[:] += dv_c


def _flash_bwd_impl(
    q, k, v, o, lse, do, causal: bool, block_q: int, block_k: int,
    segment_ids: jax.Array | None = None, window: int = 0,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Pallas flash backward: recompute p blockwise from (q, k, lse)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    n_rep = H // Hkv
    scale = D ** -0.5
    qf = q.reshape(B * H, Tq, D)
    kf = k.reshape(B * Hkv, Tk, D)
    vf = v.reshape(B * Hkv, Tk, D)
    dof = do.reshape(B * H, Tq, D)
    lsef = lse.reshape(B * H, Tq, _STAT_LANES)  # lane-replicated from the fwd
    # delta[i] = rowsum(do ⊙ o): the softmax-normalization term of ds
    delta = jnp.sum(
        dof.astype(jnp.float32) * o.reshape(B * H, Tq, D).astype(jnp.float32), axis=-1
    )
    delta = jnp.broadcast_to(delta[:, :, None], (B * H, Tq, _STAT_LANES))

    full_k = pl.BlockSpec((None, Tk, D), lambda b, i: (b // n_rep, 0, 0))
    blk_q = pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0))
    blk_k = pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0))
    row_q = pl.BlockSpec((None, block_q, _STAT_LANES), lambda b, i: (b, i, 0))

    has_seg = segment_ids is not None
    if has_seg:
        segq, segk = _seg_arrays(segment_ids, B, Tq)  # Tq == Tk (validated)

    dq_specs = [blk_q, full_k, full_k, blk_q, row_q, row_q]
    dq_operands = [qf, kf, vf, dof, lsef, delta]
    if has_seg:
        dq_specs += [
            pl.BlockSpec((None, block_q, _STAT_LANES), lambda b, i: (b // H, i, 0)),
            pl.BlockSpec((None, _STAT_LANES, Tk), lambda b, i: (b // H, 0, 0)),
        ]
        dq_operands += [segq, segk]
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, block_k=block_k, causal=causal, has_seg=has_seg,
            window=window, scale=scale,
        ),
        grid=(B * H, Tq // block_q),
        in_specs=dq_specs,
        out_specs=blk_q,
        out_shape=jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret(),
        cost_estimate=pl.CostEstimate(
            flops=6 * B * H * Tq * Tk * D,
            bytes_accessed=3 * (qf.size + kf.size) * q.dtype.itemsize,
            transcendentals=B * H * Tq * Tk,
        ),
    )(*dq_operands)

    # dk/dv: grid over (kv head, k block, group-member × q block); the GQA
    # group is folded into the q dim (layout [B*Hkv, n_rep*Tq, …]) and the
    # innermost grid dim walks one q block at a time — O(block) VMEM at any
    # sequence length, with dk/dv blocks revisited and accumulated in f32.
    num_q_blocks = Tq // block_q
    qg = qf.reshape(B * Hkv, n_rep * Tq, D)
    dog = dof.reshape(B * Hkv, n_rep * Tq, D)
    lseg = lsef.reshape(B * Hkv, n_rep * Tq, _STAT_LANES)
    deltag = delta.reshape(B * Hkv, n_rep * Tq, _STAT_LANES)
    blk_kv2 = pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0))
    cost = pl.CostEstimate(
        flops=8 * B * H * Tq * Tk * D,
        bytes_accessed=3 * (qf.size + kf.size) * q.dtype.itemsize,
        transcendentals=B * H * Tq * Tk,
    )

    if n_rep * Tq <= _DKV_RESIDENT_MAX_QROWS:
        full_qg = pl.BlockSpec((None, n_rep * Tq, D), lambda b, i: (b, 0, 0))
        row_full_g = pl.BlockSpec((None, n_rep * Tq, _STAT_LANES), lambda b, i: (b, 0, 0))
        dkv_specs = [full_qg, blk_kv2, blk_kv2, full_qg, row_full_g, row_full_g]
        dkv_operands = [qg, kf, vf, dog, lseg, deltag]
        if has_seg:
            dkv_specs += [
                # per-head q rows (NOT group-folded; kernel indexes by qb)
                pl.BlockSpec((None, Tq, _STAT_LANES), lambda b, i: (b // Hkv, 0, 0)),
                pl.BlockSpec((None, _STAT_LANES, block_k), lambda b, i: (b // Hkv, 0, i)),
            ]
            dkv_operands += [segq, segk]
        dk, dv = pl.pallas_call(
            functools.partial(
                _flash_bwd_dkv_kernel_resident,
                block_q=block_q, n_rep=n_rep, causal=causal, has_seg=has_seg,
                window=window, scale=scale,
            ),
            grid=(B * Hkv, Tk // block_k),
            in_specs=dkv_specs,
            out_specs=[blk_kv2, blk_kv2],
            out_shape=[
                jax.ShapeDtypeStruct((B * Hkv, Tk, D), k.dtype),
                jax.ShapeDtypeStruct((B * Hkv, Tk, D), v.dtype),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")
            ),
            interpret=interpret(),
            cost_estimate=cost,
        )(*dkv_operands)
    else:
        # streaming grid: enumerate only the causally-contributing
        # (k block, group member, q block) pairs, sorted by k block, and
        # scalar-prefetch the index arrays so BlockSpec index maps (and the
        # DMA pipeline) follow the sparse walk — q blocks above the diagonal
        # are never fetched, halving DMA traffic and grid steps for causal.
        kb_l, qrow_l = [], []
        for i in range(Tk // block_k):
            # fully-masked k blocks (possible when Tk > Tq) still emit ONE
            # q block per group member: its contribution is exactly zero
            # through the mask, but the visit zero-initializes the output
            # block, which would otherwise be returned uninitialized
            qb0 = min((i * block_k) // block_q, num_q_blocks - 1) if causal else 0
            qb1 = num_q_blocks
            if window > 0:
                # q rows past this k block's window band contribute nothing
                last_k = i * block_k + block_k - 1
                qb1 = max(min(num_q_blocks, (last_k + window - 1) // block_q + 1), qb0 + 1)
            for g in range(n_rep):
                for qb in range(qb0, qb1):
                    kb_l.append(i)
                    qrow_l.append(g * num_q_blocks + qb)
        kb = jnp.array(kb_l, dtype=jnp.int32)
        qrow = jnp.array(qrow_l, dtype=jnp.int32)
        n_pairs = len(kb_l)
        # the sparse walk does `frac` of the dense grid's work (~1/2 causal)
        frac = n_pairs / ((Tk // block_k) * n_rep * num_q_blocks)
        cost = pl.CostEstimate(
            flops=int(cost.flops * frac),
            bytes_accessed=int(cost.bytes_accessed * frac),
            transcendentals=int(cost.transcendentals * frac),
        )

        def q_map(b, j, kb_r, qrow_r):
            return (b, qrow_r[j], 0)

        def kv_map(b, j, kb_r, qrow_r):
            return (b, kb_r[j], 0)

        stream_specs = [
            pl.BlockSpec((None, block_q, D), q_map),
            pl.BlockSpec((None, block_k, D), kv_map),
            pl.BlockSpec((None, block_k, D), kv_map),
            pl.BlockSpec((None, block_q, D), q_map),
            pl.BlockSpec((None, block_q, _STAT_LANES), q_map),
            pl.BlockSpec((None, block_q, _STAT_LANES), q_map),
        ]
        stream_operands = [qg, kf, vf, dog, lseg, deltag]
        if has_seg:
            # seg arrays are [B, ...] per-head (not group-folded): batch =
            # b // Hkv, q block within head = qrow % num_q_blocks
            stream_specs += [
                pl.BlockSpec(
                    (None, block_q, _STAT_LANES),
                    lambda b, j, kb_r, qrow_r: (b // Hkv, qrow_r[j] % num_q_blocks, 0),
                ),
                pl.BlockSpec(
                    (None, _STAT_LANES, block_k),
                    lambda b, j, kb_r, qrow_r: (b // Hkv, 0, kb_r[j]),
                ),
            ]
            stream_operands += [segq, segk]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B * Hkv, n_pairs),
            in_specs=stream_specs,
            out_specs=[
                pl.BlockSpec((None, block_k, D), kv_map),
                pl.BlockSpec((None, block_k, D), kv_map),
            ],
        )
        dk, dv = pl.pallas_call(
            functools.partial(
                _flash_bwd_dkv_kernel,
                num_q_blocks=num_q_blocks, causal=causal, has_seg=has_seg,
                window=window, scale=scale,
            ),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((B * Hkv, Tk, D), jnp.float32),
                jax.ShapeDtypeStruct((B * Hkv, Tk, D), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")
            ),
            interpret=interpret(),
            cost_estimate=cost,
        )(kb, qrow, *stream_operands)

    return (
        dq.reshape(B, H, Tq, D),
        dk.reshape(B, Hkv, Tk, D).astype(k.dtype),
        dv.reshape(B, Hkv, Tk, D).astype(v.dtype),
    )


# -- trainable flash attention: pallas forward + pallas backward -------------
# pallas_call has no JVP rule (pallas guide §20: production kernels define a
# custom VJP). The backward is the FlashAttention-2 scheme: forward saves the
# per-row logsumexp; backward recomputes probabilities blockwise in VMEM (two
# kernels: dq over q blocks, dk/dv over k blocks) — no T×T materialization.

# bq 256 / bk 512: the r3 measured optimum on v5e — halving k-block count
# beats 256/256 on EVERY bench preset, same-session A/Bs: llama-0.87B
# 46.5→49.0% MFU, llama 2×8192 38.4→46.1%, moe 35.3→37.0%, BERT 34.5→37.7%.
# (512/512 and bk 1024 fail to compile — VMEM; bq 128 is neutral.)
# (The builders' r3 ladder, older than this code.) Env-overridable for
# per-hardware tuning.
_BLOCK_Q = int(os.environ.get("TONY_FLASH_BQ", "256"))
_BLOCK_K = int(os.environ.get("TONY_FLASH_BK", "512"))
if _BLOCK_Q < 8 or _BLOCK_Q % 8:
    raise ValueError(f"TONY_FLASH_BQ must be a multiple of 8 >= 8, got {_BLOCK_Q}")
if _BLOCK_K < 128 or _BLOCK_K % 128:
    raise ValueError(f"TONY_FLASH_BK must be a multiple of 128 >= 128, got {_BLOCK_K}")


def _block_sizes(Tq: int, Tk: int) -> tuple[int, int]:
    """Largest blocks ≤ the configured defaults that DIVIDE the sequence
    lengths (halving until they do). With bq ≠ bk defaults, a length like
    768 divides 256 but not 512 — every kernel entry point must agree on
    this rule or the grid reads padded garbage past the last block."""
    bq, bk = min(_BLOCK_Q, Tq), min(_BLOCK_K, Tk)
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


def _tuned_blocks(op: str, q: jax.Array, kv_heads: int, Tk: int) -> tuple[int, int]:
    """Autotuner-aware block sizes: an ops/tune.py cache hit for this exact
    (device, geometry, dtype) — validated against the kernels' lowering
    preconditions, so a stale entry degrades to the default instead of a
    Mosaic failure — else the tuned module constants via ``_block_sizes``.
    Trace-time only (the blocks are static kernel parameters)."""
    B, H, Tq, D = (int(d) for d in q.shape)
    if "TONY_FLASH_BQ" in os.environ or "TONY_FLASH_BK" in os.environ:
        # an EXPLICIT env override is the operator's debugging lever — it
        # must beat the tune cache (which otherwise wins silently)
        return _block_sizes(Tq, Tk)
    from tony_tpu.ops import tune

    params = tune.lookup(op, (B, H, int(kv_heads), Tq, int(Tk), D), str(q.dtype))
    if params:
        bq, bk = int(params.get("block_q", 0)), int(params.get("block_k", 0))
        if (bq >= 8 and bk >= 128 and not (bq % 8 or bk % 128)
                and not (Tq % bq or Tk % bk)):
            return bq, bk
    return _block_sizes(Tq, Tk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_trainable(q, k, v, causal, window=0):
    return flash_attention(q, k, v, causal=causal, window=window)


def _flash_fwd(q, k, v, causal, window):
    from jax.ad_checkpoint import checkpoint_name

    Tq, Tk = q.shape[2], k.shape[2]
    bq, bk = _tuned_blocks("flash_fwd", q, k.shape[1], Tk)
    o, lse = _flash_fwd_lanes(q, k, v, causal, bq, bk, None, window)
    # Named so a remat policy can pin JUST the kernel outputs
    # (save_only_these_names("flash_o", "flash_lse")): the backward then
    # recomputes the cheap qkv matmuls but not the O(T²) flash forward.
    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, window, res, g):
    q, k, v, o, lse = res
    Tq, Tk = q.shape[2], k.shape[2]
    bq, bk = _tuned_blocks("flash_bwd", q, k.shape[1], Tk)
    return _flash_bwd_impl(q, k, v, o, lse, g, causal, bq, bk, None, window)


_flash_trainable.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_trainable_seg(q, k, v, seg, causal, window=0):
    """Packed-sequence variant: seg [B, T] int; cotangent for seg is float0."""
    bq, bk = _tuned_blocks("flash_fwd", q, k.shape[1], k.shape[2])
    return _flash_fwd_impl(q, k, v, causal, bq, bk, seg, window)[0]


def _flash_seg_fwd(q, k, v, seg, causal, window):
    from jax.ad_checkpoint import checkpoint_name

    Tq, Tk = q.shape[2], k.shape[2]
    bq, bk = _tuned_blocks("flash_fwd", q, k.shape[1], Tk)
    o, lse = _flash_fwd_lanes(q, k, v, causal, bq, bk, seg, window)
    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, seg, o, lse)


def _flash_seg_bwd(causal, window, res, g):
    import numpy as np

    q, k, v, seg, o, lse = res
    Tq, Tk = q.shape[2], k.shape[2]
    bq, bk = _tuned_blocks("flash_bwd", q, k.shape[1], Tk)
    dq, dk, dv = _flash_bwd_impl(q, k, v, o, lse, g, causal, bq, bk, seg, window)
    return dq, dk, dv, np.zeros(seg.shape, jax.dtypes.float0)


_flash_trainable_seg.defvjp(_flash_seg_fwd, _flash_seg_bwd)


def remat_block(block_fn, remat: bool, policy: str = "full"):
    """Wrap a scanned decoder block in the configured remat policy.

    Lives here because the "flash" policy pins THIS module's checkpoint
    names (flash_o / flash_lse from _flash_fwd) — models must not hardcode
    them. Policies: "full" (recompute everything), "dots" (save matmul
    outputs), "flash" (save only the flash-kernel outputs so the backward
    never replays the O(T²) forward kernel).
    """
    if not remat:
        return block_fn
    if policy == "dots":
        return jax.checkpoint(
            block_fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    if policy == "flash":
        # also pins MoE routing outputs (parallel/expert.py names them
        # "moe_route": tiny tensors whose recompute would re-run the whole
        # vector-bound gating pipeline) and the fused expert-MLP kernel
        # output ("moe_gemm", ops/moe_gemm.py): [N_rows, D] bf16 per layer
        # — the one activation whose replay would re-run three grouped
        # GEMMs (A/B'd +0.8 MFU pt on the moe bench preset: builders' r3
        # run, older than this code).
        # TONY_REMAT_EXTRA_NAMES ("a,b") appends further named activations
        # (e.g. moe_disp / moe_combine) — the measurement ladder's knob for
        # per-shape save-vs-replay tradeoffs without code edits.
        names = ["flash_o", "flash_lse", "moe_route", "moe_gemm"]
        extra = os.environ.get("TONY_REMAT_EXTRA_NAMES", "")
        names += [n.strip() for n in extra.split(",") if n.strip()]
        return jax.checkpoint(
            block_fn,
            policy=jax.checkpoint_policies.save_only_these_names(*names),
        )
    if policy != "full":
        raise ValueError(f"remat_policy must be full|dots|flash, got {policy!r}")
    return jax.checkpoint(block_fn)


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
