"""Context/sequence parallelism: ring attention over a mesh axis.

Absent from the reference (SURVEY.md §5.7); first-class here. The sequence
dimension is sharded over the ``context`` axis; attention runs as a **ring**:
each rank keeps its query block resident and rotates KV blocks around the
axis ring (``ppermute`` → ICI neighbor exchange), merging partial results
with the flash-attention log-sum-exp recurrence, so the full T×T score matrix
never materializes on any chip and memory stays O(T/N) per device.

The ring is built from XLA collectives (compiler-scheduled overlap).
Ulysses-style all-to-all head sharding is provided as the alternative for
models with many heads.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from tony_tpu.parallel import collectives

NEG_INF = -1e30


def _block_attn(q, k, v, mask, scale):
    """One KV-block attention step → (unnormalized out, row max, row lse).

    q: [B, H, Tq, D]; k/v: [B, H, Tk, D]; mask broadcastable to [B, H, Tq, Tk].
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)
    # max is >= NEG_INF even for fully-masked rows, keeping exp() finite
    m = jnp.max(s, axis=-1, keepdims=True)                      # [B,H,Tq,1]
    p = jnp.exp(s - m)
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)                      # [B,H,Tq,1]
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(p.dtype))
    return o, m, l


def _merge(o1, m1, l1, o2, m2, l2):
    """Flash-attention merge of two partial softmax accumulations."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    return o1 * a1 + o2 * a2, m, l1 * a1 + l2 * a2


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = "context",
    causal: bool = True,
    scale: float | None = None,
) -> jax.Array:
    """Ring attention on sequence-sharded q/k/v.

    Must run inside shard_map with the sequence dim sharded over
    ``axis_name``. Shapes (per shard): q/k/v [B, H, T_local, D] (KV heads
    already broadcast to H). Returns [B, H, T_local, D] in q.dtype.
    """
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    B, H, Tl, D = q.shape
    scale = scale if scale is not None else D ** -0.5
    qf = q.astype(jnp.float32)

    q_pos = my * Tl + jnp.arange(Tl)                            # global query positions

    def mask_for(src_idx):
        if not causal:
            return jnp.ones((1, 1, Tl, Tl), dtype=bool)
        kv_pos = src_idx * Tl + jnp.arange(Tl)
        return (q_pos[:, None] >= kv_pos[None, :])[None, None]

    def step(carry, s):
        o, m, l, k_blk, v_blk = carry
        src = (my - s) % n                                      # whose KV block we hold
        o_b, m_b, l_b = _block_attn(qf, k_blk.astype(jnp.float32), v_blk, mask_for(src), scale)
        o, m, l = _merge(o, m, l, o_b, m_b, l_b)
        # rotate KV to the next rank for the following step (last rotate is
        # redundant but keeps the loop uniform; XLA overlaps it with the
        # merge). On a size-1 ring the rotate is the identity — the guard
        # keeps the single-shard path free of ppermute launches.
        k_blk = collectives.stop_transfer_if_single(collectives.rotate, axis_name, k_blk)
        v_blk = collectives.stop_transfer_if_single(collectives.rotate, axis_name, v_blk)
        return (o, m, l, k_blk, v_blk), None

    o0 = jnp.zeros((B, H, Tl, D), jnp.float32)
    m0 = jnp.full((B, H, Tl, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Tl, 1), jnp.float32)
    (o, m, l, _, _), _ = jax.lax.scan(step, (o0, m0, l0, k, v), jnp.arange(n))
    return (o / jnp.maximum(l, 1e-20)).astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = "context",
    causal: bool = True,
    attn_fn=None,
) -> jax.Array:
    """Ulysses/DeepSpeed-style sequence parallelism: all-to-all converts
    sequence sharding into head sharding, runs full-sequence attention on
    1/N of the heads, then converts back. Needs H % axis_size == 0.

    Inside shard_map; shapes per shard: [B, H, T_local, D] → same.
    """
    n = jax.lax.axis_size(axis_name)
    if attn_fn is None:
        from tony_tpu.ops.attention import attention_reference

        attn_fn = partial(attention_reference, causal=causal)

    def seq_to_heads(x):  # [B,H,Tl,D] → [B,H/n,T,D]
        # size-1 axis: shape-preserving identity — skip the collective
        return collectives.stop_transfer_if_single(
            collectives.all_to_all, axis_name, x, split_axis=1, concat_axis=2
        )

    def heads_to_seq(x):  # [B,H/n,T,D] → [B,H,Tl,D]
        return collectives.stop_transfer_if_single(
            collectives.all_to_all, axis_name, x, split_axis=2, concat_axis=1
        )

    out = attn_fn(seq_to_heads(q), seq_to_heads(k), seq_to_heads(v))
    return heads_to_seq(out)
