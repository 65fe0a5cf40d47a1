"""Collective-communication helpers over mesh axes.

The reference's data plane lived inside user frameworks (NCCL/Gloo/MPI —
SURVEY.md §2.6); here it is XLA collectives over ICI/DCN, chosen by mesh-axis
placement. These wrappers are used inside ``shard_map`` bodies (pipeline,
ring attention, MoE all-to-all); plain ``pjit`` code paths rely on XLA's
sharding propagation instead and never call these directly.
"""

from __future__ import annotations

import jax


def ring_size(axis_name: str) -> int:
    return jax.lax.axis_size(axis_name)


def ring_index(axis_name: str) -> jax.Array:
    return jax.lax.axis_index(axis_name)


def rotate(x: jax.Array, axis_name: str, shift: int = 1) -> jax.Array:
    """Send to the next rank on the axis ring (ppermute); the ICI-neighbor
    pattern every ring collective here is built from."""
    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name, perm)


def all_gather(x: jax.Array, axis_name: str, *, axis: int = 0, tiled: bool = True) -> jax.Array:
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def psum(x, axis_name: str):
    return jax.lax.psum(x, axis_name)


def psum_scatter(x: jax.Array, axis_name: str, *, axis: int = 0) -> jax.Array:
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def all_to_all(x: jax.Array, axis_name: str, *, split_axis: int, concat_axis: int) -> jax.Array:
    return jax.lax.all_to_all(x, axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=True)


def pmean(x, axis_name: str):
    return jax.lax.pmean(x, axis_name)


def ring_all_reduce_sum(x: jax.Array, axis_name: str) -> jax.Array:
    """Explicit reduce-scatter + all-gather ring all-reduce.

    Functionally ``psum``; exists for schedule control when overlapping with
    compute in shard_map bodies.
    """
    n = jax.lax.axis_size(axis_name)
    if x.shape[0] % n:
        return jax.lax.psum(x, axis_name)
    scattered = psum_scatter(x, axis_name, axis=0)
    return all_gather(scattered, axis_name, axis=0)


def moe_all_to_all(tokens: jax.Array, axis_name: str) -> jax.Array:
    """Expert-dispatch all-to-all: [E_local*C, ...] tokens grouped by target
    expert shard → exchanged so each rank holds its experts' tokens."""
    return all_to_all(tokens, axis_name, split_axis=0, concat_axis=0)


def stop_transfer_if_single(transfer, axis_name: str, x: jax.Array, /, *args, **kwargs) -> jax.Array:
    """Apply ``transfer(x, axis_name, ...)`` unless the axis has size 1
    (lets one code path serve all mesh shapes).

    A size-1 ``ppermute``/``all_to_all`` is mathematically the identity but
    still lowers to a real collective — a launch (and on some backends an
    ICI round trip) per call that XLA does not always elide. Skipping it
    here keeps single-shard meshes (the 1-chip bench, CPU tests, a context
    axis collapsed by an elastic shrink) off the collective path entirely.

    The axis size is static under ``shard_map``, so the branch resolves at
    trace time — no ``lax.cond`` in the compiled program.
    """
    if jax.lax.axis_size(axis_name) <= 1:
        return x
    return transfer(x, axis_name, *args, **kwargs)
