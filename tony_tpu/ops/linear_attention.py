"""Causal linear attention with a per-head decay (Lightning Attention).

No softmax: each head carries a ``[d, d]`` state, ``S_t = lambda_h S_{t-1} +
k_t^T v_t``, and reads it with the query, ``o_t = q_t S_t / sqrt(d)``. The
state is float32 whatever the activations are: it is the sum of every
position so far.

Two forms of the one recurrence:

- ``linear_attention_chunk``: C positions at once, in blocks of ``block``
  positions, one Pallas program a head with the head's state resident in VMEM
  from block to block: inside a block ``o = ((Q K^T * D) V + (Q * decay)
  S_prev) / sqrt(d)`` with ``D_ij = lambda^(i-j)`` for ``j <= i``, and
  ``S_next = lambda^n S_prev + (K * decay_to_end)^T V``. ``valid`` positions of
  the chunk count (a padded last chunk of a prompt): the state that comes back
  is the state after ``valid`` positions, whatever lies behind them.
- ``linear_attention_step``: one position a row (decode), the state updated
  and read in place (XLA: two elementwise passes over the state of all slots).

The chunk form is a kernel and not blocked XLA matmuls under a scan for what a
trace can say of it: one named call (``linear_attention_chunk``, 0.21 ms of
device time at C = 2048 x 32 heads x 128, blocks of 256) where XLA leaves a
dozen unnamed fusions among the chunk program's others (PERF.md section 6).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tony_tpu.ops.interpret import interpret

_HI = jax.lax.Precision.HIGHEST


def log_decay(n_heads: int) -> jax.Array:
    """``-log(lambda_h)`` = 2^(-8(h+1)/H), float32 [H]: Lightning Attention's
    slopes, a constant of the head's index (no parameter)."""
    h = jnp.arange(1, n_heads + 1, dtype=jnp.float32)
    return jnp.exp2(-8.0 * h / n_heads)


def _chunk_kernel(slopes_ref, valid_ref, q_ref, k_ref, v_ref, s0_ref, o_ref, s_ref, *, n_heads, scale):
    """One block of one head: q, k, v [1, blk, d]; the state [1, d, d] stays in
    the output block from the head's first block to its last."""
    from jax.experimental import pallas as pl

    b, blk = pl.program_id(1), q_ref.shape[1]

    @pl.when(b == 0)
    def _start():
        s_ref[...] = s0_ref[...]

    s = slopes_ref[pl.program_id(0) % n_heads]
    n = jnp.clip(valid_ref[0] - b * blk, 0, blk).astype(jnp.float32)        # positions that count
    q, k, v, S = q_ref[0], k_ref[0], v_ref[0], s_ref[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (blk, 1), 0).astype(jnp.float32)
    lag = i - jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1).astype(jnp.float32)   # i - j
    intra = jnp.where(lag >= 0, jnp.exp(-s * jnp.maximum(lag, 0.0)), 0.0)
    p = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * intra
    o = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    from_prev = jnp.exp(-s * (i + 1.0))                                     # lambda^(i+1): [blk, 1]
    o = o + jax.lax.dot_general(q.astype(jnp.float32) * from_prev, S, (((1,), (0,)), ((), ())),
                                precision=_HI, preferred_element_type=jnp.float32)
    # what each position still weighs when the block's `n` positions are over
    to_end = jnp.where(i < n, jnp.exp(-s * jnp.maximum(n - 1.0 - i, 0.0)), 0.0)
    s_ref[0] = jnp.exp(jnp.full((1, S.shape[1]), -s * n)) * S + jax.lax.dot_general(
        k.astype(jnp.float32) * to_end, v.astype(jnp.float32), (((0,), (0,)), ((), ())),
        precision=_HI, preferred_element_type=jnp.float32)
    o_ref[0] = (o * scale).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block",))
def linear_attention_chunk(q, k, v, state, slopes, valid=None, block: int = 256):
    """q, k, v: [B, H, C, d]; state: [B, H, d, d] float32 (the state before the
    chunk's first position); slopes: [H] (``log_decay``); valid: [] int32,
    positions of the chunk that count (default all). Returns (o [B, H, C, d] in
    q's type, the state after ``valid`` positions)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, C, d = q.shape
    block = min(block, C)
    if C % block:
        raise ValueError(f"chunk of {C} positions does not divide into blocks of {block}")
    valid = jnp.full((1,), C, jnp.int32) if valid is None else valid.astype(jnp.int32).reshape(1)
    rows = pl.BlockSpec((1, block, d), lambda g, b, *_: (g, b, 0))
    whole = pl.BlockSpec((1, d, d), lambda g, b, *_: (g, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_chunk_kernel, n_heads=H, scale=d ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B * H, C // block),
            in_specs=[rows, rows, rows, whole], out_specs=[rows, whole]),
        out_shape=[jax.ShapeDtypeStruct((B * H, C, d), q.dtype), jax.ShapeDtypeStruct((B * H, d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret(),
        name="linear_attention_chunk",
        cost_estimate=pl.CostEstimate(flops=4 * B * H * C * d * (block + d), transcendentals=B * H * C * block,
                                      bytes_accessed=4 * B * H * C * d * q.dtype.itemsize + 8 * B * H * d * d),
    )(slopes.astype(jnp.float32), valid, q.reshape(B * H, C, d), k.reshape(B * H, C, d), v.reshape(B * H, C, d),
      state.astype(jnp.float32).reshape(B * H, d, d))
    return o.reshape(B, H, C, d), state.reshape(B, H, d, d)


def linear_attention_step(q, k, v, state, slopes):
    """One position a row: q, k, v [S, H, d]; state [S, H, d, d] float32.
    Returns (o [S, H, d] in q's type, the state with this position in it)."""
    d = q.shape[-1]
    lam = jnp.exp(-slopes.astype(jnp.float32))[None, :, None, None]
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    state = lam * state + kf[..., :, None] * vf[..., None, :]
    o = jnp.sum(q.astype(jnp.float32)[..., :, None] * state, axis=-2) * d ** -0.5
    return o.astype(q.dtype), state

