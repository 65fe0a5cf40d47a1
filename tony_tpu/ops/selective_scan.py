"""The selective scan of Mamba-1 (arXiv:2312.00752): a linear recurrence whose decay
is a CHANNEL's and a STATE INDEX's, both at once.

A layer carries a float32 state ``h`` ``[E, N]`` (``E`` channels, ``N`` the state's
size). At a position with input ``x`` ``[E]``, step ``dt`` ``[E]`` ``> 0`` and the
position's ``B``, ``C`` ``[N]`` (one pair for all channels)::

    h = exp(dt A) * h + (dt x) B^T          y = h C + D x          A = -exp(A_log) [E, N]

``exp(dt_e A_en)`` differs from channel to channel AND from state index to state
index, so there is no matrix form to fall back on: Mamba-2's dual form
(ops/ssd.py) needs one scalar decay a head, so that ``C B^T`` is shared by the
head's channels. Here every (channel, index) pair decays on its own, and the
recurrence is walked a position at a time. What is avoided is STAGING it: a
``lax.associative_scan`` over ``[T, E, N]`` float32 makes 671 MB a layer at 2,048
rows of 5,120 channels. Three forms of the one recurrence:

- ``selective_scan``: the literal one, a ``lax.scan`` a position. What the other
  two are tested against; no program calls it.
- ``selective_chunk``: T positions, one Pallas program (``selective_chunk``) a span
  of ``LANES`` channels, whose ``[N, LANES]`` float32 state stays in VMEM from block
  to block; the kernel walks a block's positions one by one and nothing of size
  ``T x E x N`` exists anywhere.
- ``selective_step``: one position a slot (decode), a Pallas program
  (``selective_step``) that reads and writes a slot's state once, in place.

THE KERNELS' STATE IS ``[N, E]``, as ``ops/ssd.lanes`` lays a Mamba-2 state: the
state's index down the rows, the channels along the lanes, where ``x``, ``dt`` and
``y`` lie already. A position is then elementwise on whole tiles (a channel's
``dt`` and ``dt x`` broadcast down the rows, ``B`` and ``C`` along the lanes) and the
read-out's sum over ``N`` adds rows. ``A`` arrives as ``[N, E]`` too. The state, the
steps and every product are float32. ``D x`` is added outside the kernels.

A step's state is 4 x N x E bytes a slot and layer (327,680 B at 16 x 5,120, where
a Mamba-2 layer of ops/ssd.py holds 8.39 MB): the step form is bound by its
launches and its lanes, not by the HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tony_tpu.ops.interpret import interpret

#: positions a block of ``selective_chunk`` (its rows are fetched a block at a time)
BLOCK = 256
#: channels a program of either kernel holds the state of: [N, LANES] float32 in registers from position to position
LANES = 512


def selective_scan(x, dt, A, B, C, D, state):
    """The recurrence a position at a time. x, dt [T, E]; A [E, N] (negative); B, C
    [T, N]; D [E]; state [E, N] float32. Returns (y [T, E] float32, the state after
    T positions)."""
    f32 = lambda a: a.astype(jnp.float32)

    def position(h, inputs):
        xt, dtt, bt, ct = inputs
        h = jnp.exp(dtt[:, None] * f32(A)) * h + (dtt * xt)[:, None] * bt[None, :]
        return h, jnp.sum(h * ct[None, :], axis=1) + f32(D) * xt

    state, y = jax.lax.scan(position, f32(state), (f32(x), f32(dt), f32(B), f32(C)))
    return y, state


def _span(E: int) -> int:
    """Channels a program: LANES where they divide E, else the widest power of two of whole tiles that does, else all."""
    return next((l for l in (LANES, 256, 128) if E % l == 0), E)


def _chunk_kernel(dt_ref, dtx_ref, b_ref, c_ref, a_ref, s0_ref, y_ref, s_ref):
    """One block of one span of channels. dt, dtx [Cb, L]: the step and dt x; b, c
    [Cb, N, W]: a position's B and C as columns, repeated along W lanes (a tile);
    a [N, L]; the span's state [N, L] stays in the output block from the chunk's
    first block to its last."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _start():
        s_ref[...] = s0_ref[...]

    Cb, L = dt_ref.shape
    W = b_ref.shape[2]
    A = a_ref[...]
    wide = (lambda col: jnp.concatenate([col] * (L // W), axis=1)) if L > W else (lambda col: col)

    def position(t, h):
        dt, dtx = dt_ref[pl.ds(t, 1), :], dtx_ref[pl.ds(t, 1), :]            # [1, L]
        h = jnp.exp(dt * A) * h + dtx * wide(b_ref[t])
        y_ref[pl.ds(t, 1), :] = jnp.sum(h * wide(c_ref[t]), axis=0, keepdims=True)
        return h

    s_ref[...] = jax.lax.fori_loop(0, Cb, position, s_ref[...])


@functools.partial(jax.jit, static_argnames=("block",))
def selective_chunk(x, dt, A, B, C, D, state, valid=None, block: int = BLOCK):
    """x [T, E]; dt [T, E] float32 (the step, after its softplus); A [N, E] float32
    (-exp(A_log), the state's index down the rows); B, C [T, N]; D [E]; state [N, E]
    float32 (before the chunk's first position); valid [] int32, the positions of
    the chunk that count (default all; a padded last chunk of a prompt). Returns (y
    [T, E] float32 with ``D x`` in it, the state after ``valid`` positions). A
    position past ``valid`` neither decays nor writes (dt = 0); its output is not
    meant to be read. T in whole blocks of ``min(block, T)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, E = x.shape
    N = B.shape[-1]
    Cb, L = min(block, T), _span(E)
    if T % Cb or Cb % 8:
        raise ValueError(f"chunk of {T} positions does not divide into blocks of {Cb}, a multiple of 8")
    W = min(128, L)
    f32 = lambda a: a.astype(jnp.float32)
    xf, dt = f32(x), f32(dt)
    if valid is not None:
        dt = jnp.where((jnp.arange(T) < valid)[:, None], dt, 0.0)
    columns = lambda a: jnp.broadcast_to(f32(a)[:, :, None], (T, N, W))      # a position's B or C down the rows of a tile
    rows = pl.BlockSpec((Cb, L), lambda e, b: (b, e))
    cols = pl.BlockSpec((Cb, N, W), lambda e, b: (b, 0, 0))
    span = pl.BlockSpec((N, L), lambda e, b: (0, e))
    y, state = pl.pallas_call(
        _chunk_kernel,
        grid=(E // L, T // Cb),
        in_specs=[rows, rows, cols, cols, span, span],
        out_specs=[rows, span],
        out_shape=[jax.ShapeDtypeStruct((T, E), jnp.float32), jax.ShapeDtypeStruct((N, E), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret(),
        name="selective_chunk",
        cost_estimate=pl.CostEstimate(flops=6 * T * E * N, transcendentals=T * E * N,
                                      bytes_accessed=12 * T * E + 8 * (E // L) * T * N * W + 12 * E * N),
    )(dt, dt * xf, columns(B), columns(C), f32(A), f32(state))
    return y + f32(D)[None, :] * xf, state


def _step_kernel(dt_ref, dtx_ref, b_ref, c_ref, a_ref, s_ref, o_ref, so_ref):
    """One slot, one span of channels. dt, dtx [1, 1, L]; b, c [1, N, 1]: columns; a
    [N, L]; the span of the slot's state [1, N, L], read and written once."""
    N, L = s_ref.shape[1:]
    tile = min(128, L)
    b, c = jnp.broadcast_to(b_ref[0], (N, tile)), jnp.broadcast_to(c_ref[0], (N, tile))
    for at in range(0, L, tile):
        at_ = slice(at, at + tile)
        new = jnp.exp(dt_ref[0, :, at_] * a_ref[:, at_]) * s_ref[0, :, at_] + b * dtx_ref[0, :, at_]     # [N, tile]
        so_ref[0, :, at_] = new
        o_ref[0, :, at_] = jnp.sum(new * c, axis=0, keepdims=True)


@jax.jit
def selective_step(x, dt, A, B, C, D, state):
    """One position a slot: x, dt [S, E]; A [N, E] float32; B, C [S, N]; D [E]; state
    [S, N, E] float32, updated in place where the caller donates it. Returns (y [S,
    E] float32 with ``D x`` in it, the state with this position in it)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, E = x.shape
    N, L = B.shape[-1], _span(E)
    f32 = lambda a: a.astype(jnp.float32)
    xf, dt = f32(x), f32(dt)
    row = pl.BlockSpec((1, 1, L), lambda s, l: (s, 0, l))
    column = pl.BlockSpec((1, N, 1), lambda s, l: (s, 0, 0))
    span = pl.BlockSpec((1, N, L), lambda s, l: (s, 0, l))
    o, state = pl.pallas_call(
        _step_kernel,
        grid=(S, E // L),
        in_specs=[row, row, column, column, pl.BlockSpec((N, L), lambda s, l: (0, l)), span],
        out_specs=[row, span],
        out_shape=[jax.ShapeDtypeStruct((S, 1, E), jnp.float32), jax.ShapeDtypeStruct((S, N, E), jnp.float32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret(),
        name="selective_step",
        cost_estimate=pl.CostEstimate(flops=6 * S * E * N, transcendentals=S * E * N, bytes_accessed=8 * S * E * N + 4 * E * N),
    )(dt[:, None, :], (dt * xf)[:, None, :], f32(B)[:, :, None], f32(C)[:, :, None], f32(A), f32(state))
    return o[:, 0] + f32(D)[None, :] * xf, state
