"""The delta rule whose state forgets by a gate a CHANNEL (Kimi Delta Attention,
arXiv:2510.26692): ops/delta_rule.py's recurrence with the log-decay a vector over
the key's channels, where that one has a number a head and position.

A head carries a float32 state ``S`` ``[d_k, d_v]``. At a position with query ``q``,
key ``k`` (both L2-normed by the caller, the query scaled), value ``v``, log-decay
``g <= 0`` ``[d_k]`` and write strength ``beta`` in ``[0, 2]``::

    S' = Diag(exp g) S       w = beta (v - S'^T k)
    S  = S' + k w^T          o = S^T q

Three forms of the one recurrence:

- ``kda_scan``: the literal one, a ``lax.scan`` a position. What the other two are
  tested against; no program calls it.
- ``kda_chunk``: T positions in blocks of ``BLOCK``, one Pallas program
  (``kda_chunk``) a group of up to ``delta_rule.CHUNK_HEADS`` heads (``_chunk_heads``)
  with the group's states resident in VMEM from block to block. With ``G_i`` the
  running sum of ``g`` inside the block (a vector) the
  written rows ``W`` solve ``(I + A) W = beta (V - (K * exp G) S_prev)``, then ``o_i =
  (q_i * exp G_i)^T S_prev + sum_{j<=i} P_ij w_j`` and ``S_next = Diag(exp G_C) S_prev
  + sum_j (k_j * exp(G_C - G_j)) w_j^T``, where ``A_ij = beta_i sum_d k_id k_jd
  exp(G_id - G_jd)`` for ``j < i`` and ``P_ij`` the same with ``q_i`` (and ``q_i . k_i``
  on the diagonal). The decay sits INSIDE the sum over channels, so ``A`` is not
  ``k k^T`` times a matrix of decays as in ops/delta_rule.py, and the one-reference
  factoring ``(k_i * exp G_i) . (k_j * exp -G_j)`` overflows as soon as a channel
  forgets strongly (``exp(-G_j)`` with ``G_j`` near -1000). NO EXPONENTIAL OF A
  POSITIVE NUMBER IS TAKEN ANYWHERE: the pairs ``j < i`` of a block are split by
  the highest bit in which ``i`` and ``j`` differ. At level ``h`` (1, 2, 4, ...,
  BLOCK/2) the block is cut in groups of ``2h``; ``i`` lies in a group's upper half
  and ``j`` in its lower, and with ``m`` the first position of the upper half
  ``exp(G_i - G_j) = exp(G_i - G_{m-1}) exp(G_{m-1} - G_j)``: the first factor is the
  decay from the half's start through ``i``, the second from after ``j`` to the lower
  half's end, both at most 1. Every pair is met at exactly one level, so ``A`` and
  ``P`` are the sum over the levels of one masked MXU product each (rows ``k`` and
  ``q`` scaled by the first factor, stacked; columns ``k`` scaled by the second):
  log2(BLOCK) products of ``[2 BLOCK, d_k] x [d_k, BLOCK]`` and ``2 BLOCK d_k``
  exponentials a level, where the pairs taken one by one are ``BLOCK^2 d_k / 2``
  exponentials and as many multiply-adds on the vector unit (at 64 x 128: 98 k
  against 262 k, and the sums over ``d`` on the MXU). The paper's own split
  (sub-blocks of 16 by one reference each, the pairs inside a sub-block one by one)
  is the top two levels of this one. The sums of ``g`` the factors need are built
  with the levels, a group's total from its halves' (``_chunk_kernel``): sums of terms of
  one sign, never a difference of two running sums (a channel may forget by -20 at
  one position and by -0.01 at the next). ``(I + A)^-1`` is forward substitution, a
  row at a time in float32, as in ops/delta_rule.py and for its reasons; a head's
  63 rows are one chain, so a program carries several heads and their chains run
  side by side (``delta_rule._solve_rows``). The level masks do not know the head:
  they are formed once a program.
- ``kda_step``: one position a slot (decode), a Pallas program (``kda_step``) that
  reads and writes the state of every slot once, in place. The key-side vectors
  (the decay, ``k``, ``beta k``, ``q``) reach the kernel as COLUMNS, ``HEADS`` heads
  side by side along the lanes (``[d_k, 4 x HEADS]``): a ``[..., d_k, 1]`` operand
  would be padded to 128 lanes in HBM, 128 slots' x 64 heads' columns to 0.5 GB an
  operand.

The state, ``g``, ``beta`` and the triangular solve are float32; the level products
take their operands in the activations' type with float32 accumulation, and every
product with the state as an operand is float32 at full precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tony_tpu.ops.delta_rule import _chunk_heads, _dot, _heads_block, _solve_rows
from tony_tpu.ops.interpret import interpret

_HI = jax.lax.Precision.HIGHEST
#: positions a block of ``kda_chunk``: a head's solve is BLOCK sequential rows, the pairs take log2(BLOCK) level
#: products, the state is read and written once a block (32 and 128 are both slower: PERF.md section 6, PR 58)
BLOCK = 64
#: heads a program of ``kda_step`` holds the state of: their four key-side vectors fill 128 lanes at 32
HEADS = 32


def kda_scan(q, k, v, g, beta, state):
    """The recurrence a position at a time. q, k, g [H, T, dk]; v [H, T, dv]; beta
    [H, T]; state [H, dk, dv] float32. Returns (o [H, T, dv] float32, the state
    after T positions)."""

    def position(S, x):
        qt, kt, vt, gt, bt = x
        S = jnp.exp(gt)[:, :, None] * S
        w = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt, precision=_HI))
        S = S + kt[:, :, None] * w[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt, precision=_HI)

    xs = tuple(a.astype(jnp.float32).swapaxes(0, 1) for a in (q, k, v, g, beta))
    state, o = jax.lax.scan(position, state.astype(jnp.float32), xs)
    return o.swapaxes(0, 1), state


def _chunk_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s0_ref, o_ref, s_ref):
    """One block of hb heads. q, k [hb, C, dk]; v [hb, C, dv]; g [hb, C, dk] float32, the
    log-decays; b [hb, C, 1], beta as a column; the states [hb, dk, dv] stay in the
    output block from the heads' first block to their last. A head's arithmetic is
    what it is alone (hb = 1): the heads share the masks and the solve's loop over
    rows, nothing else."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    @pl.when(pl.program_id(1) == 0)
    def _start():
        s_ref[...] = s0_ref[...]

    hb, C, dk = g_ref.shape
    heads, dtype = range(hb), k_ref.dtype
    kf, qf = [k_ref[n].astype(jnp.float32) for n in heads], [q_ref[n].astype(jnp.float32) for n in heads]
    at = jax.lax.broadcasted_iota(jnp.int32, (C, dk), 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    # Sums of g over the groups of h positions the block divides into, h = 1, 2, 4, ...: `total` a group's whole
    # sum (at each of its rows), `upto` the sum from the group's first position through the row's own, `after`
    # the sum from the position after the row's to the group's last. A group of 2h is its two halves: sums of
    # terms of one sign all the way up, no difference of running sums anywhere.
    total = [g_ref[n] for n in heads]
    upto, after = list(total), [jnp.zeros_like(t) for t in total]
    pairs_k = [jnp.zeros((C, C), jnp.float32) for _ in heads]                # sum_d k_id k_jd exp(G_id - G_jd), j < i
    pairs_q = list(pairs_k)                                                  # the same with q_i
    h = 1
    while h < C:
        upper = (at & h) != 0                                                # the row lies in the upper half of its group of 2h
        met = (((row ^ col) & ~(2 * h - 1)) == 0) & ((row & h) != 0) & ((col & h) == 0)
        for n in heads:
            first = jnp.exp(upto[n])                                         # from the half's start through i: at most 1
            second = jnp.where(upper, 0.0, jnp.exp(after[n]))                # from after j to the lower half's end
            rows = jnp.concatenate([(kf[n] * first).astype(dtype), (qf[n] * first).astype(dtype)], axis=0)
            level = _dot(rows, (kf[n] * second).astype(dtype), ((1,), (1,)))   # [2C, C]
            pairs_k[n] = pairs_k[n] + jnp.where(met, level[:C], 0.0)
            pairs_q[n] = pairs_q[n] + jnp.where(met, level[C:], 0.0)
            below, above = pltpu.roll(total[n], h, 0), pltpu.roll(total[n], C - h, 0)   # the other half's total: total[i - h], total[i + h]
            upto[n] = upto[n] + jnp.where(upper, below, 0.0)
            after[n] = after[n] + jnp.where(upper, 0.0, above)
            total[n] = total[n] + jnp.where(upper, below, above)
        h *= 2
    # upto = G_i from the block's start, after = G_C - G_i, total = G_C at every row
    X = _solve_rows([(b_ref[n] * pairs_k[n]).T for n in heads])
    for n in heads:
        S, beta = s_ref[n], b_ref[n]
        eG = jnp.exp(upto[n])                                                # [C, dk]: exp(G_i), a channel
        R = beta * (v_ref[n].astype(jnp.float32) - _dot(kf[n] * eG, S, ((1,), (0,)), exact=True))
        W = _dot(X[n], R, ((1,), (0,)), exact=True)                          # [C, dv]: the written rows
        P = pairs_q[n] + jnp.where(row == col, jnp.sum(qf[n] * kf[n], axis=1, keepdims=True), 0.0)
        o = _dot(qf[n] * eG, S, ((1,), (0,)), exact=True) + _dot(P, W, ((1,), (0,)), exact=True)
        # the block's whole decay a channel, along the state's lanes: g's columns summed by a product with ones (a
        # [1, dk] row cannot be laid down a column without a transpose)
        whole = jnp.exp(_dot(g_ref[n], jnp.ones((C, S.shape[1]), jnp.float32), ((0,), (0,)), exact=True))
        s_ref[n] = whole * S + _dot(kf[n] * jnp.exp(after[n]), W, ((0,), (0,)), exact=True)
        o_ref[n] = o.astype(o_ref.dtype)


@jax.jit
def kda_chunk(q, k, v, g, beta, state, valid=None):
    """q, k [H, T, dk]; v [H, T, dv]; g [H, T, dk], beta [H, T] float32; state [H, dk,
    dv] float32 (before the chunk's first position); valid [] int32, the positions
    of the chunk that count (default all; a padded last chunk of a prompt).
    Returns (o [H, T, dv] in v's type, the state after ``valid`` positions). A
    position past ``valid`` neither decays nor writes (g = 0, beta = 0); its output
    is not meant to be read. T in whole blocks of ``min(BLOCK, T)``, a power of two."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H, T, dk = q.shape
    dv = v.shape[2]
    C = min(BLOCK, T)
    if T % C or C & (C - 1):
        raise ValueError(f"chunk of {T} positions does not divide into blocks of {C}, a power of two")
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    if valid is not None:
        counts = jnp.arange(T) < valid
        g, beta = jnp.where(counts[:, None], g, 0.0), jnp.where(counts, beta, 0.0)
    hb = _chunk_heads(H, C, dk, dv, q.dtype.itemsize)
    rows = lambda d: pl.BlockSpec((hb, C, d), lambda h, b: (h, b, 0))
    whole = pl.BlockSpec((hb, dk, dv), lambda h, b: (h, 0, 0))
    levels = C.bit_length() - 1
    o, state = pl.pallas_call(
        _chunk_kernel,
        grid=(H // hb, T // C),
        in_specs=[rows(dk), rows(dk), rows(dv), rows(dk), rows(1), whole],
        out_specs=[rows(dv), whole],
        out_shape=[jax.ShapeDtypeStruct((H, T, dv), v.dtype), jax.ShapeDtypeStruct((H, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret(),
        name="kda_chunk",
        cost_estimate=pl.CostEstimate(flops=2 * H * T * (3 * dk * dv + C * (2 * levels * dk + 2 * dv + C + dk)),
                                      transcendentals=H * T * dk * (2 * levels + 3),
                                      bytes_accessed=H * T * (2 * dk + 2 * dv) * q.dtype.itemsize + 4 * H * T * dk + 8 * H * dk * dv),
    )(q, k, v, g, beta.reshape(H, T, 1), state.astype(jnp.float32))
    return o, state


def _step_kernel(c_ref, bv_ref, s_ref, o_ref, so_ref):
    """A block of one slot's heads. c [1, 1, dk, 4 hb]: the heads' key-side vectors as
    columns, four runs of hb lanes (exp g, k, beta k, q); bv (beta v) [1, hb, dv], a
    head a row; the state [1, hb, dk, dv] read and written once."""
    hb = bv_ref.shape[1]
    cols = c_ref[0, 0]
    for h in range(hb):
        a, k, bk, q = (cols[:, j * hb + h:j * hb + h + 1] for j in range(4))   # [dk, 1] each
        S = a * s_ref[0, h]                                                  # [dk, dv]
        w = bv_ref[0, h:h + 1, :] - jnp.sum(S * bk, axis=0, keepdims=True)   # [1, dv]
        S = S + k * w
        so_ref[0, h] = S
        o_ref[0, h:h + 1, :] = jnp.sum(S * q, axis=0, keepdims=True)


@jax.jit
def kda_step(q, k, v, g, beta, state):
    """One position a slot: q, k, g [S, H, dk]; v [S, H, dv]; beta [S, H]; state [S,
    H, dk, dv] float32, updated in place where the caller donates it. Returns (o [S,
    H, dv] float32, the state with this position in it)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, dk = q.shape
    dv = v.shape[2]
    hb = _heads_block(H, HEADS)
    f32 = lambda a: a.astype(jnp.float32)
    beta = f32(beta)[:, :, None]
    # [S, H, dk] x 4 -> [S, H / hb, dk, 4 hb]: a head a lane, the four vectors in runs of hb lanes
    cols = jnp.stack([jnp.exp(f32(g)), f32(k), beta * f32(k), f32(q)], axis=1)           # [S, 4, H, dk]
    cols = cols.reshape(S, 4, H // hb, hb, dk).transpose(0, 2, 4, 1, 3).reshape(S, H // hb, dk, 4 * hb)
    o, state = pl.pallas_call(
        _step_kernel,
        grid=(S, H // hb),
        in_specs=[pl.BlockSpec((1, 1, dk, 4 * hb), lambda s, h: (s, h, 0, 0)), pl.BlockSpec((1, hb, dv), lambda s, h: (s, h, 0)),
                  pl.BlockSpec((1, hb, dk, dv), lambda s, h: (s, h, 0, 0))],
        out_specs=[pl.BlockSpec((1, hb, dv), lambda s, h: (s, h, 0)), pl.BlockSpec((1, hb, dk, dv), lambda s, h: (s, h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((S, H, dv), jnp.float32), jax.ShapeDtypeStruct((S, H, dk, dv), jnp.float32)],
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret(),
        name="kda_step",
        cost_estimate=pl.CostEstimate(flops=7 * S * H * dk * dv, transcendentals=0, bytes_accessed=8 * S * H * dk * dv),
    )(cols, beta * f32(v), f32(state))
    return o, state
