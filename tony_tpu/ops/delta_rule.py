"""The gated delta rule: linear attention whose state forgets by a gate the token
chooses and, before it writes a key's value, takes out what it already says of
that key (Gated DeltaNet, arXiv:2412.06464).

A head carries a float32 state ``S`` ``[d_k, d_v]``. At a position with query
``q``, key ``k`` (both L2-normed by the caller, the query scaled), value ``v``,
log-decay ``g <= 0`` and write strength ``beta`` in ``[0, 2]``::

    S' = exp(g) S            w = beta (v - S'^T k)
    S  = S' + k w^T          o = S^T q

Three forms of the one recurrence:

- ``gated_delta_scan``: the literal one, a ``lax.scan`` a position. What the
  other two are tested against; no program calls it.
- ``gated_delta_chunk``: T positions in blocks of ``BLOCK``, one Pallas program
  (``delta_chunk``) a group of up to ``CHUNK_HEADS`` heads (``_chunk_heads``) with
  the group's states resident in VMEM from block to block. Inside a block the
  written rows ``W`` solve ``(I + A) W = beta (V - exp(G) K S_prev)``, ``A_ij =
  beta_i (k_i . k_j) exp(G_i - G_j)`` for ``j < i`` (``G`` the running sum of ``g``
  inside the block, every difference summed term by term); then ``o_i = exp(G_i)
  S_prev^T q_i + sum_{j<=i} exp(G_i - G_j) (k_j . q_i) w_j`` and ``S_next =
  exp(G_C) S_prev + sum_j exp(G_C - G_j) k_j w_j^T`` (the WY form, section 3 of
  the paper). ``(I + A)^-1`` is taken by FORWARD SUBSTITUTION, a row at a
  time, in float32: ``A`` is nilpotent and ``(I - A)(I + A^2)(I + A^4)...`` would
  be exact in exact arithmetic, but with keys that are all SiLU outputs (positive
  in the mean, so ``k_i . k_j`` near a half) or ``beta`` near 2 the powers of ``A``
  reach 1e8 at a block of 64 before they cancel, and float32 keeps seven digits:
  the row form has no such intermediate (tests/test_olmo_hybrid.py holds both
  regimes to the scan). Every ``exp(G_i - G_j)`` is taken under the ``j <= i``
  mask, where it is at most 1. A head's 63 rows are ONE chain, each waiting for
  the row before, which no layout shortens; a program therefore carries several
  heads, whose chains are independent and stand row by row side by side
  (``_solve_rows``), and a row is summed over the rows above it only.
- ``gated_delta_step``: one position a slot (decode), a Pallas program
  (``delta_step``) that reads and writes the state of every slot once, in place.

``short_conv_chunk`` / ``short_conv_step``: the causal depthwise convolution
over time that precedes the rule (``taps`` inputs a channel, an optional bias,
then SiLU), with the last ``taps - 1`` inputs carried from chunk to chunk and from
step to step. The state-space layers (ops/ssd.py) run the same one, with a bias.

The state, ``g``, ``beta`` and the triangular solve are float32; the block's
products take their operands in the activations' type with float32
accumulation, and every product with the state as an operand is float32 at
full precision.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from tony_tpu.ops.interpret import interpret

_HI = jax.lax.Precision.HIGHEST
#: positions a block of ``gated_delta_chunk``: a head's solve is BLOCK sequential rows, the state is
#: read and written once a block
BLOCK = 64
#: heads a program of a blocked rule (``gated_delta_chunk``, ops/kda.py's ``kda_chunk``) holds at most: their solves are
#: independent chains that run row by row side by side. Chosen on the chip at 30 heads of 96 / 192 and 64 of 128 / 128
#: (PERF.md section 6, PR 58): past 4 a call gains 2-6%, and every start pays more seconds to trace and lower a body that
#: is written out a head
CHUNK_HEADS = 4
#: bytes of VMEM the blocks, states and live values of a chunk program's heads may take together: under the 16 MB
#: a kernel is given on the smallest chip this runs on
CHUNK_VMEM = 12 << 20


def gated_delta_scan(q, k, v, g, beta, state):
    """The recurrence a position at a time. q, k [H, T, dk]; v [H, T, dv]; g,
    beta [H, T]; state [H, dk, dv] float32. Returns (o [H, T, dv] float32, the
    state after T positions)."""

    def position(S, x):
        qt, kt, vt, gt, bt = x
        S = jnp.exp(gt)[:, None, None] * S
        w = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt, precision=_HI))
        S = S + kt[:, :, None] * w[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt, precision=_HI)

    xs = tuple(a.astype(jnp.float32).swapaxes(0, 1) for a in (q, k, v, g, beta))
    state, o = jax.lax.scan(position, state.astype(jnp.float32), xs)
    return o.swapaxes(0, 1), state


def _dot(a, b, dims, exact=False):
    """a . b over `dims`, float32 out; `exact`: both operands float32 at full precision."""
    if exact:
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI if exact else None,
                               preferred_element_type=jnp.float32)


def _chunk_heads(H: int, C: int, dk: int, dv: int, itemsize: int) -> int:
    """Heads a program of a blocked rule holds: the most up to CHUNK_HEADS that divide H and whose blocks (q, k, v, o and
    the float32 gates, in the pipeline's two buffers), states (in and out, two buffers each) and live float32 values
    (about sixteen arrays of a block's rows by the wider of its widths: 1.0 MB a head measured at 64 x 128 / 128)
    fit CHUNK_VMEM. A prime H over CHUNK_HEADS runs a head a program."""
    need = 2 * C * ((2 * dk + 2 * dv) * itemsize + 4 * (dk + 128)) + 16 * dk * dv + 64 * C * max(dk, dv, C)
    return _heads_block(H, max(1, min(CHUNK_HEADS, CHUNK_VMEM // need)))


@jax.jit
def _solve_rows(At):
    """X = (I + A)^-1 of each head of a program by rows, X_i = e_i - A_i X. At: a head a [C, C] float32, A
    transposed (A strictly lower). The rows of X from i on are still those of I and A_ij = 0 there, so row i is summed
    over the tiles of 8 rows that hold a row above it and ONE tile is rewritten. The rows' loop is outermost and the
    heads inside it: a head's rows are a chain, each waiting for the row before, and the heads' chains interleave.
    Jitted so that its 2,000 equations are traced once a process and not once a prefill bucket (the kernel's body is
    traced anew for every grid)."""
    C = At[0].shape[0]
    r = math.gcd(C, 8)                                                       # rows a tile: a float32 register's sublanes
    sub = jax.lax.broadcasted_iota(jnp.int32, (r, C), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (r, C), 1)
    first = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
    X = [[(sub + t == lane).astype(jnp.float32) for t in range(0, C, r)] for _ in At]
    for i in range(1, C):
        above = (i - 1) // r + 1                                             # tiles with a row before i
        e, here = (first == i).astype(jnp.float32), sub == i % r
        for x, at in zip(X, At):
            rows = x[0] if above == 1 else jnp.concatenate(x[:above], axis=0)
            new = e - jnp.sum(at[:above * r, i:i + 1] * rows, axis=0, keepdims=True)   # [1, C]: e_i - A_i X
            x[i // r] = jnp.where(here, new, x[i // r])
    return [jnp.concatenate(x, axis=0) for x in X]


def _chunk_kernel(q_ref, k_ref, v_ref, gc_ref, gr_ref, b_ref, s0_ref, o_ref, s_ref):
    """One block of hb heads. q, k [hb, C, dk]; v [hb, C, dv]; gc, b [hb, C, 1] and gr
    [hb, 1, 1, C]: the block's log-decays as a column and as a row, beta as a column;
    the states [hb, dk, dv] stay in the output block from the heads' first block to
    their last. A head's arithmetic is what it is alone (hb = 1): the heads share the
    masks and the solve's loop over rows, nothing else."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _start():
        s_ref[...] = s0_ref[...]

    hb, C = q_ref.shape[:2]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    # G_i - G_j = sum of g_m over j < m <= i, summed term by term (a product with a 0/1 matrix) and not as a
    # difference of running sums: a token's log-decay may be -100 and its neighbour's -0.01, and the
    # difference of two running sums near -1000 keeps three digits of the small one
    upto = (col <= row).astype(jnp.float32)                                  # [i, m]: m <= i
    decay, At = [], []
    for n in range(hb):
        k, g, beta = k_ref[n], gc_ref[n], b_ref[n]                           # [C, dk], [C, 1], [C, 1]
        between = _dot(upto, jnp.where(row > col, g, 0.0), ((1,), (0,)), exact=True)     # [i, j]
        decay.append(jnp.where(col <= row, jnp.exp(jnp.minimum(between, 0.0)), 0.0))     # exp(G_i - G_j), j <= i
        At.append(jnp.where(col < row, beta * _dot(k, k, ((1,), (1,))) * decay[n], 0.0).T)
    X = _solve_rows(At)
    for n in range(hb):
        q, k, v, S = q_ref[n], k_ref[n], v_ref[n], s_ref[n]
        gr, beta = gr_ref[n, 0], b_ref[n]                                    # [1, C], [C, 1]
        eG = jnp.exp(jnp.sum(upto * gr, axis=1, keepdims=True))              # [C, 1]: exp(G_i), from the block's start
        R = beta * (v.astype(jnp.float32) - eG * _dot(k, S, ((1,), (0,)), exact=True))
        W = _dot(X[n], R, ((1,), (0,)), exact=True)                          # [C, dv]: the written rows
        o = eG * _dot(q, S, ((1,), (0,)), exact=True) + _dot(_dot(q, k, ((1,), (1,))) * decay[n], W, ((1,), (0,)), exact=True)
        to_end = jnp.exp(jnp.sum(jnp.where(col > row, gr, 0.0), axis=1, keepdims=True))   # [C, 1]: exp(G_C - G_i)
        # the block's whole decay along the lanes first, then down the rows: Mosaic has no broadcast of [1, 1] in both at once
        whole = jnp.exp(jnp.broadcast_to(jnp.sum(gr, axis=1, keepdims=True), (1, S.shape[1])))
        s_ref[n] = whole * S + _dot(k.astype(jnp.float32) * to_end, W, ((0,), (0,)), exact=True)
        o_ref[n] = o.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block",))
def gated_delta_chunk(q, k, v, g, beta, state, valid=None, block: int = BLOCK):
    """q, k [H, T, dk]; v [H, T, dv]; g, beta [H, T] float32; state [H, dk, dv]
    float32 (before the chunk's first position); valid [] int32, the positions of
    the chunk that count (default all; a padded last chunk of a prompt). Returns
    (o [H, T, dv] in v's type, the state after ``valid`` positions). A position
    past ``valid`` neither decays nor writes (g = 0, beta = 0); its output is
    not meant to be read. T in whole blocks of ``min(block, T)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H, T, dk = q.shape
    dv = v.shape[2]
    C = min(block, T)
    if T % C:
        raise ValueError(f"chunk of {T} positions does not divide into blocks of {C}")
    nb, hb = T // C, _chunk_heads(H, C, dk, dv, q.dtype.itemsize)
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    if valid is not None:
        counts = jnp.arange(T) < valid
        g, beta = jnp.where(counts, g, 0.0), jnp.where(counts, beta, 0.0)
    rows = lambda d: pl.BlockSpec((hb, C, d), lambda h, b: (h, b, 0))
    whole = pl.BlockSpec((hb, dk, dv), lambda h, b: (h, 0, 0))
    o, state = pl.pallas_call(
        _chunk_kernel,
        grid=(H // hb, nb),
        in_specs=[rows(dk), rows(dk), rows(dv), rows(1), pl.BlockSpec((hb, 1, 1, C), lambda h, b: (h, b, 0, 0)), rows(1), whole],
        out_specs=[rows(dv), whole],
        out_shape=[jax.ShapeDtypeStruct((H, T, dv), v.dtype), jax.ShapeDtypeStruct((H, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret(),
        name="delta_chunk",
        cost_estimate=pl.CostEstimate(flops=2 * H * T * (3 * dk * dv + C * (2 * dk + 2 * dv + C)), transcendentals=H * T * (C + 2),
                                      bytes_accessed=H * T * (2 * dk + 2 * dv) * q.dtype.itemsize + 8 * H * dk * dv),
    )(q, k, v, g.reshape(H, T, 1), g.reshape(H, nb, 1, C), beta.reshape(H, T, 1), state.astype(jnp.float32))
    return o, state


def _step_kernel(q_ref, k_ref, bv_ref, a_ref, b_ref, s_ref, o_ref, so_ref):
    """A block of one slot's heads: q, k [1, hb, dk, 1] columns; bv (beta v) [1, hb,
    1, dv]; a (exp g), b (beta) [1, hb, 1, 1]; the state [1, hb, dk, dv] read and
    written once."""
    S = a_ref[0] * s_ref[0]                                                  # [hb, dk, dv]
    k, q = k_ref[0], q_ref[0]                                                # [hb, dk, 1]
    w = bv_ref[0] - b_ref[0] * jnp.sum(S * k, axis=1, keepdims=True)         # [hb, 1, dv]
    S = S + k * w
    so_ref[0] = S
    o_ref[0] = jnp.sum(S * q, axis=1, keepdims=True)


def _heads_block(H: int, most: int = 8) -> int:
    return max(h for h in range(1, min(H, most) + 1) if H % h == 0)


@jax.jit
def gated_delta_step(q, k, v, g, beta, state):
    """One position a slot: q, k [S, H, dk]; v [S, H, dv]; g, beta [S, H]; state
    [S, H, dk, dv] float32, updated in place where the caller donates it. Returns
    (o [S, H, dv] float32, the state with this position in it)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, dk = q.shape
    dv = v.shape[2]
    hb = _heads_block(H)
    f32 = lambda a: a.astype(jnp.float32)
    beta = f32(beta)[:, :, None, None]
    spec = lambda a, b: pl.BlockSpec((1, hb, a, b), lambda s, h: (s, h, 0, 0))
    o, state = pl.pallas_call(
        _step_kernel,
        grid=(S, H // hb),
        in_specs=[spec(dk, 1), spec(dk, 1), spec(1, dv), spec(1, 1), spec(1, 1), spec(dk, dv)],
        out_specs=[spec(1, dv), spec(dk, dv)],
        out_shape=[jax.ShapeDtypeStruct((S, H, 1, dv), jnp.float32), jax.ShapeDtypeStruct((S, H, dk, dv), jnp.float32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret(),
        name="delta_step",
        cost_estimate=pl.CostEstimate(flops=7 * S * H * dk * dv, transcendentals=0, bytes_accessed=8 * S * H * dk * dv),
    )(f32(q)[..., None], f32(k)[..., None], beta * f32(v)[:, :, None, :], jnp.exp(f32(g))[:, :, None, None], beta,
      f32(state))
    return o[:, :, 0], state


# -- the convolution before the rule -----------------------------------------------------------------

def _conv_kernel(u_ref, prev_ref, tail_ref, w_ref, *rest, taps):
    """A tile of rows x channels: u [tb, cb]; prev [8, cb], the 8 rows of u before
    the tile (its last taps - 1 are read); tail [8, cb], the same for the chunk's
    first tile; w [taps, cb]; then, where the convolution has one, its bias [1, cb]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    *bias_ref, o_ref = rest

    before = jnp.where(pl.program_id(0) == 0, tail_ref[...], prev_ref[...]).astype(jnp.float32)
    x = jnp.concatenate([before, u_ref[...].astype(jnp.float32)], axis=0)    # [8 + tb, cb]
    n, tb = x.shape[0], u_ref.shape[0]
    w = w_ref[...].astype(jnp.float32)
    acc = jnp.zeros((tb, x.shape[1]), jnp.float32)
    for j in range(taps):                                                    # y_t = sum_j w_j x_{t - (taps-1) + j}
        lag = taps - 1 - j
        acc = acc + w[j:j + 1] * pltpu.roll(x, (n - (8 - lag)) % n, 0)[:tb]
    if bias_ref:
        acc = acc + bias_ref[0][...].astype(jnp.float32)
    o_ref[...] = (acc * jax.nn.sigmoid(acc)).astype(o_ref.dtype)


def _conv_channels(C: int) -> int:
    wide = [c for c in range(128, min(C, 1280) + 1, 128) if C % c == 0]
    return max(wide) if wide else C


@jax.jit
def short_conv_chunk(u, tail, w, valid=None, bias=None):
    """u [T, C]: the chunk's inputs; tail [taps - 1, C]: the inputs before it,
    oldest first (zeros at a sequence's start); w [taps, C], the newest input's
    weight last; bias [C] or None, added before the SiLU. Returns (silu(conv + bias)
    [T, C] in u's type, the last taps - 1 inputs before position ``valid`` of the
    chunk (default its end))."""
    from jax.experimental import pallas as pl

    T, C = u.shape
    taps = w.shape[0]
    if not 1 < taps <= 9 or tail.shape != (taps - 1, C):
        raise ValueError(f"{taps} taps (2 to 9 are carried in one tile of 8 rows) with a tail of {tail.shape}")
    tb, cb = min(256, T), _conv_channels(C)
    if T % tb or tb % 8:
        raise ValueError(f"chunk of {T} rows does not divide into tiles of {tb} rows, a multiple of 8")
    tail8 = jnp.pad(tail.astype(u.dtype), ((8 - (taps - 1), 0), (0, 0)))
    with_bias = ([pl.BlockSpec((1, cb), lambda t, c: (0, c))], [bias.reshape(1, C)]) if bias is not None else ([], [])
    y = pl.pallas_call(
        functools.partial(_conv_kernel, taps=taps),
        grid=(T // tb, C // cb),
        in_specs=[pl.BlockSpec((tb, cb), lambda t, c: (t, c)),
                  pl.BlockSpec((8, cb), lambda t, c: (jnp.maximum(t * (tb // 8) - 1, 0), c)),
                  pl.BlockSpec((8, cb), lambda t, c: (0, c)),
                  pl.BlockSpec((taps, cb), lambda t, c: (0, c)), *with_bias[0]],
        out_specs=pl.BlockSpec((tb, cb), lambda t, c: (t, c)),
        out_shape=jax.ShapeDtypeStruct((T, C), u.dtype),
        interpret=interpret(),
        name="short_conv",
    )(u, u, tail8, w, *with_bias[1])
    at = (T if valid is None else valid) - (taps - 1) + jnp.arange(taps - 1)   # rows of u; below 0: rows of the tail
    kept = jnp.where((at >= 0)[:, None], u[jnp.clip(at, 0, T - 1)], tail.astype(u.dtype)[jnp.clip(at + taps - 1, 0, taps - 2)])
    return y, kept


def short_conv_step(u, tail, w, bias=None):
    """One position a slot: u [S, C]; tail [S, taps - 1, C]; bias [C] or None.
    Returns (silu(conv + bias) [S, C] in u's type, the tail with this input in it)."""
    x = jnp.concatenate([tail.astype(jnp.float32), u.astype(jnp.float32)[:, None]], axis=1)   # [S, taps, C]
    acc = jnp.sum(x * w.astype(jnp.float32)[None], axis=1)
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    return (acc * jax.nn.sigmoid(acc)).astype(u.dtype), x[:, 1:].astype(tail.dtype)
