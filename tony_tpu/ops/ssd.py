"""The state-space dual form of Mamba-2 (SSD, arXiv:2405.21060): a linear recurrence
whose decay and step size the token chooses, over a state a head that the heads
of a GROUP read through one pair of projections.

A head carries a float32 state ``h`` ``[P, N]`` (``P`` the head's width, ``N`` the
state's). At a position with input ``x`` ``[P]``, step ``dt > 0``, log-decay ``g =
-exp(A_log) dt <= 0`` and the position's ``B``, ``C`` ``[N]`` of the head's group::

    h = exp(g) h + dt x B^T          y = h C + D x

``B`` and ``C`` come as ``[T, N]`` (one pair for all heads: one group) or as ``[T,
G, N]`` (``G`` groups: head ``h`` of ``H`` reads pair ``h // (H / G)``). The shape
says which; the one-group call is the program it was before there were groups.

Three forms of the one recurrence:

- ``ssd_scan``: the literal one, a ``lax.scan`` a position. What the other two
  are tested against; no program calls it.
- ``ssd_chunk``: T positions in blocks of ``BLOCK``, one Pallas program
  (``ssd_chunk``) a group of ``HEADS`` heads, the group's state resident in VMEM
  from block to block. With ``G`` the running sum of ``g`` inside a block,
  ``Y = (L o (C B^T)) (dt x) + exp(G) C h_prev`` where ``L_ij = exp(G_i - G_j)``
  for ``j <= i`` and 0 above, and ``h_next = exp(G_T) h_prev + sum_j exp(G_T - G_j)
  dt_j x_j B_j^T``. Every ``exp(G_i - G_j)`` is taken under the ``j <= i`` mask,
  where it is at most 1 (no ``exp(-G_j)`` is ever formed). ``C B^T`` does not
  know the head: it is formed once a block and program and serves the program's
  heads, which all lie in ONE B/C group (a program's heads divide a group's);
  the read-out of the old state and the state's update are one product each
  for all of them (``C`` against the program's ``[N, HEADS x P]`` state, ``B``
  against the weighted inputs). Only ``L`` is a head's own.
- ``ssd_step``: one position a slot (decode), a Pallas program (``ssd_step``)
  that reads and writes a slot's state once, in place, a span of lanes at a
  time; a span lies in one B/C group and takes that group's columns.

THE KERNELS' STATE IS ``[N, H x P]`` (``lanes``): the state's index down the rows,
(head, channel) along the lanes, which is where ``x`` and ``y`` lie already. A
step is then elementwise on whole tiles (a lane's decay and ``dt x`` broadcast
down the rows, ``B`` and ``C`` along the lanes) and the read-out's sum over ``N``
adds rows, where ``[H, P, N]`` reduced every tile along its lanes and needed ``dt
x`` transposed (64 slots' step, the call alone in the serving cell: 0.81-0.86 ms,
626-660 GB/s, against 0.91; PERF.md section 6, PR 53). The chunk form's two state
products need no transposed operand either.

The state, ``dt``, the decays and the two products that read or write the state
are float32 at full precision; ``C B^T`` and the masked product take their
operands in the activations' type with float32 accumulation. ``D x`` is added
outside the kernels (an elementwise pass the caller's gate fuses with).

``BLOCK``, ``HEADS`` and ``SPAN`` are this kernel's own constants, chosen on the
chip at 128 heads of 64 over a state of 128 and chunks of 256 to 2048 rows
(PERF.md section 6, PR 53). At 32 heads of 128 over a state of 256 in two groups
they give a program of either kernel 2 MB of state where granite's has 0.5 and
1 MB; that fits a v5e's scoped VMEM, and half of it (8 heads, spans of 1024)
was no faster on the chip (PERF.md section 6, PR 59).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tony_tpu.ops.interpret import interpret

_HI = jax.lax.Precision.HIGHEST
#: positions a block of ``ssd_chunk``: the masked product is BLOCK x BLOCK a head, the state is read
#: and written once a block
BLOCK = 128
#: heads a program of ``ssd_chunk`` holds the state of (their inputs are HEADS x P lanes wide)
HEADS = 16
#: lanes of a slot's state a program of ``ssd_step`` reads and writes (N rows of them: a block of N x SPAN x 4 bytes)
SPAN = 2048


def ssd_scan(x, dt, g, B, C, D, state):
    """The recurrence a position at a time. x [T, H, P]; dt, g [T, H]; B, C [T,
    N] or [T, G, N]; D [H]; state [H, P, N] float32. Returns (y [T, H, P]
    float32, the state after T positions)."""
    H = x.shape[1]
    B, C = _by_head(B, H), _by_head(C, H)

    def position(h, inputs):
        xt, dtt, gt, bt, ct = inputs
        h = jnp.exp(gt)[:, None, None] * h + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        return h, jnp.einsum("hpn,hn->hp", h, ct, precision=_HI) + D.astype(jnp.float32)[:, None] * xt

    state, y = jax.lax.scan(position, state.astype(jnp.float32), tuple(a.astype(jnp.float32) for a in (x, dt, g, B, C)))
    return y, state


def _by_head(a, H: int):
    """B or C, [T, N] or [T, G, N], as every head reads it: [T, H, N], head h its group's (h // (H / G))."""
    a = a[:, None] if a.ndim == 2 else a
    return jnp.repeat(a, H // a.shape[1], axis=1)


def _groups(B, H: int) -> int:
    """The B/C groups a call's shapes say: 1 for [T, N], G for [T, G, N] (G divides the heads)."""
    G = 1 if B.ndim == 2 else B.shape[1]
    if H % G:
        raise ValueError(f"{G} groups of B and C do not divide {H} heads")
    return G


def _dot(a, b, dims, exact=False):
    """a . b over `dims`, float32 out; `exact`: both operands float32 at full precision."""
    if exact:
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI if exact else None,
                               preferred_element_type=jnp.float32)


def _chunk_kernel(x_ref, b_ref, c_ref, col_ref, row_ref, whole_ref, s0_ref, y_ref, s_ref, *, heads, width):
    """One block of one group of heads. x [C, heads x P]; b, c [C, N]; col [1, C, 3
    x heads]: exp(G_i), G_i and the update's weight exp(G_C - G_i) dt_i of each
    head, as columns; row [1, 2 x heads, C]: G_j and dt_j as rows; whole [1, 1,
    heads x P]: the block's whole decay exp(G_C) of each lane's head; the group's
    state [N, heads x P] stays in the output block from the group's first block
    to its last."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _start():
        s_ref[...] = s0_ref[...]

    x, Bm, Cm, S = x_ref[...], b_ref[...], c_ref[...], s_ref[...]
    col, rows = col_ref[0], row_ref[0]
    C, P = x.shape[0], width
    tri = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1) <= jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    cb = _dot(Cm, Bm, ((1,), (1,)))                                          # [i, j]: C_i . B_j, the same for every head
    old = _dot(Cm, S, ((1,), (0,)), exact=True)                              # [C, heads x P]: C_i h_prev, every head at once
    lanes = 2 * P if heads % 2 == 0 and 2 * P <= 128 else P                  # two heads side by side fill a tile of lanes (a head of 128 fills its own)
    first = jax.lax.broadcasted_iota(jnp.int32, (C, lanes), 1) < P
    weighted = []
    for at in range(0, heads, lanes // P):
        xa = x[:, at * P:at * P + lanes]
        own = range(at, at + lanes // P)
        ys = []
        for k in own:
            decay = jnp.where(tri, jnp.exp(jnp.minimum(col[:, heads + k:heads + k + 1] - rows[k:k + 1], 0.0)), 0.0)
            m = decay * cb * rows[heads + k:heads + k + 1]                   # L_ij (C_i . B_j) dt_j
            ys.append(_dot(m.astype(x.dtype), xa, ((1,), (0,))))             # over BOTH heads' lanes: the other's are dropped
        pick = (lambda a: jnp.where(first, a[0], a[1])) if len(ys) == 2 else (lambda a: a[0])
        y = pick(ys) + pick([col[:, k:k + 1] for k in own]) * old[:, at * P:at * P + lanes]
        y_ref[:, at * P:at * P + lanes] = y.astype(y_ref.dtype)
        weighted.append(xa.astype(jnp.float32) * pick([col[:, 2 * heads + k:2 * heads + k + 1] for k in own]))
    s_ref[...] = whole_ref[0] * S + _dot(Bm, jnp.concatenate(weighted, axis=1), ((0,), (0,)), exact=True)


def _group(H: int, P: int, most: int) -> int:
    """Heads a program holds: the most up to `most` that divide H and fill whole tiles of 128 lanes (or all of them)."""
    fits = [h for h in range(1, min(H, most) + 1) if H % h == 0 and (h * P) % 128 == 0]
    return max(fits) if fits else H


@functools.partial(jax.jit, static_argnames=("block",))
def ssd_chunk(x, dt, g, B, C, D, state, valid=None, block: int = BLOCK):
    """x [T, H, P]; dt, g [T, H] float32 (the step and the log-decay -exp(A_log)
    dt); B, C [T, N] or [T, G, N]; D [H]; state [N, H x P] float32 (before the chunk's first
    position; `lanes`); valid [] int32, the positions of the chunk that count
    (default all; a padded last chunk of a prompt). Returns (y [T, H, P] in x's
    type, the state after ``valid`` positions). A position past ``valid`` neither
    decays nor writes (g = 0, dt = 0); its output is not meant to be read. T in
    whole blocks of ``min(block, T)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, H, P = x.shape
    N, groups = B.shape[-1], _groups(B, H)
    Cb = min(block, T)
    if T % Cb:
        raise ValueError(f"chunk of {T} positions does not divide into blocks of {Cb}")
    nb, hb = T // Cb, _group(H // groups, P, HEADS)                          # a program's heads lie in one B/C group
    ng = H // hb
    per = ng // groups                                                       # programs a B/C group
    dt, g = dt.astype(jnp.float32), g.astype(jnp.float32)
    if valid is not None:
        counts = (jnp.arange(T) < valid)[:, None]
        dt, g = jnp.where(counts, dt, 0.0), jnp.where(counts, g, 0.0)
    G = jnp.cumsum(g.reshape(nb, Cb, H), axis=1)                             # the running sum inside each block
    to_end = jnp.exp(G[:, -1:] - G) * dt.reshape(nb, Cb, H)                  # exp(G_C - G_i) dt_i: what position i leaves in the state
    by_group = lambda a: a.reshape(T, ng, hb).transpose(1, 0, 2)             # [T, H] or [blocks, Cb, H] -> [groups, T, heads]
    col = jnp.concatenate([by_group(a) for a in (jnp.exp(G), G, to_end)], axis=2)
    row = jnp.concatenate([by_group(a) for a in (G, dt)], axis=2).transpose(0, 2, 1)
    flat = pl.BlockSpec((Cb, hb * P), lambda h, b: (b, h))
    shared = pl.BlockSpec((Cb, N), (lambda h, b: (b, 0)) if groups == 1 else (lambda h, b: (b, h // per)))
    whole = pl.BlockSpec((N, hb * P), lambda h, b: (0, h))
    y, state = pl.pallas_call(
        functools.partial(_chunk_kernel, heads=hb, width=P),
        grid=(ng, nb),
        in_specs=[flat, shared, shared, pl.BlockSpec((1, Cb, 3 * hb), lambda h, b: (h, b, 0)),
                  pl.BlockSpec((1, 2 * hb, Cb), lambda h, b: (h, 0, b)), pl.BlockSpec((1, 1, hb * P), lambda h, b: (b, 0, h)), whole],
        out_specs=[flat, whole],
        out_shape=[jax.ShapeDtypeStruct((T, H * P), x.dtype), jax.ShapeDtypeStruct((N, H * P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret(),
        name="ssd_chunk",
        cost_estimate=pl.CostEstimate(flops=2 * T * (ng * Cb * N + H * P * (Cb + 2 * N)), transcendentals=T * H * (Cb + 2),
                                      bytes_accessed=2 * T * H * P * x.dtype.itemsize + 2 * ng * T * N * B.dtype.itemsize + 8 * H * P * N),
    )(x.reshape(T, H * P), B.reshape(T, groups * N), C.reshape(T, groups * N), col, row, jnp.repeat(jnp.exp(G[:, -1:]), P, axis=2),
      state.astype(jnp.float32))
    y = y.reshape(T, H, P)
    return (y.astype(jnp.float32) + D.astype(jnp.float32)[None, :, None] * x.astype(jnp.float32)).astype(x.dtype), state


def _step_kernel(x_ref, a_ref, b_ref, c_ref, s_ref, o_ref, so_ref):
    """One slot, one span of lanes. x [1, 1, L]: dt x, a (head, channel) a lane; a
    [1, 1, L]: exp(g) of each lane's head; b, c [1, N, 1]: columns; the span of the
    slot's state [1, N, L], read and written once. The state's index lies down the
    rows, so the read-out's sum over it adds rows and no lanes."""
    N, L = s_ref.shape[1:]
    tile = min(128, L)
    b, c = jnp.broadcast_to(b_ref[0], (N, tile)), jnp.broadcast_to(c_ref[0], (N, tile))
    for at in range(0, L, tile):
        new = a_ref[0, :, at:at + tile] * s_ref[0, :, at:at + tile] + b * x_ref[0, :, at:at + tile]    # [N, tile]
        so_ref[0, :, at:at + tile] = new
        o_ref[0, :, at:at + tile] = jnp.sum(new * c, axis=0, keepdims=True)


def lanes(state):
    """A state as the recurrence writes it, [..., H, P, N], in the kernels' layout [..., N, H x P]: the
    state's index down the rows, (head, channel) along the lanes, where the inputs and outputs lie too."""
    *lead, H, P, N = state.shape
    return jnp.moveaxis(state.reshape(*lead, H * P, N), -1, -2)


@jax.jit
def ssd_step(x, dt, g, B, C, D, state):
    """One position a slot: x [S, H, P]; dt, g [S, H]; B, C [S, N] or [S, G, N]; D
    [H]; state [S, N, H x P] float32 (`lanes`), updated in place where the caller
    donates it. Returns (y [S, H, P] float32, the state with this position in it)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, P = x.shape
    N, HP, groups = B.shape[-1], H * P, _groups(B, H)
    L = next((l for l in (SPAN, 1024, 512, 256, 128) if (HP // groups) % l == 0), HP // groups)    # a span lies in one B/C group
    per = HP // groups // L                                                  # spans a B/C group
    f32 = lambda a: a.astype(jnp.float32)
    xf = f32(x)
    row = pl.BlockSpec((1, 1, L), lambda s, l: (s, 0, l))
    column = pl.BlockSpec((1, N, 1), (lambda s, l: (s, 0, 0)) if groups == 1 else (lambda s, l: (s, l // per, 0)))
    span = pl.BlockSpec((1, N, L), lambda s, l: (s, 0, l))
    o, state = pl.pallas_call(
        _step_kernel,
        grid=(S, HP // L),
        in_specs=[row, row, column, column, span],
        out_specs=[row, span],
        out_shape=[jax.ShapeDtypeStruct((S, 1, HP), jnp.float32), jax.ShapeDtypeStruct((S, N, HP), jnp.float32)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret(),
        name="ssd_step",
        cost_estimate=pl.CostEstimate(flops=5 * S * HP * N, transcendentals=0, bytes_accessed=8 * S * HP * N),
    )((f32(dt)[:, :, None] * xf).reshape(S, 1, HP), jnp.repeat(jnp.exp(f32(g)), P, axis=1)[:, None, :], f32(B).reshape(S, groups * N)[:, :, None],
      f32(C).reshape(S, groups * N)[:, :, None], f32(state))
    return o.reshape(S, H, P) + f32(D)[None, :, None] * xf, state
