"""Weight-only int8 quantization: absmax per-channel + fused dequant matmul.

New capability over the reference (its compute lived in user frameworks —
SURVEY.md §2.4). The serving-side win on TPU is HBM bandwidth: int8 weights
halve the bytes streamed per matmul versus bf16, and the Pallas kernel
fuses the dequant into the MXU epilogue so no bf16 copy of the weight ever
exists in HBM. Training stays bf16; quantize at export time.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from tony_tpu.ops.interpret import interpret

class QTensor(NamedTuple):
    """Per-output-channel absmax int8 quantization of a [..., K, N] weight."""

    q: jax.Array      # int8 [..., K, N]
    scale: jax.Array  # f32  [..., N] (absmax over the K/contraction dim)


def quantize_int8(w: jax.Array) -> QTensor:
    """[..., K, N] float → QTensor with per-N-channel absmax scales.

    Leading dims (e.g. the stacked-layer dim) quantize independently."""
    wf = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(wf), axis=-2) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(wf / scale[..., None, :]), -127, 127).astype(jnp.int8)
    return QTensor(q, scale)


def dequantize(qt: QTensor, dtype=jnp.bfloat16) -> jax.Array:
    return (qt.q.astype(jnp.float32) * qt.scale[..., None, :]).astype(dtype)


def int8_matmul_ref(x: jax.Array, qt: QTensor) -> jax.Array:
    """XLA reference: x [.., K] @ dequant [K, N] → [.., N] in x.dtype."""
    out = jnp.einsum(
        "...k,kn->...n", x.astype(jnp.float32), qt.q.astype(jnp.float32)
    )
    return (out * qt.scale).astype(x.dtype)


def _quant_matmul_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, n_k: int):
    """Grid (M//bm, N//bn, K//bk), K innermost. int8 block is cast to bf16 in
    VMEM (HBM streamed at 1 byte/weight), dot accumulates f32 in scratch, and
    the per-channel scale lands in the epilogue of the last K step."""
    from jax.experimental import pallas as pl

    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x = x_ref[:].astype(jnp.bfloat16)
    w = q_ref[:].astype(jnp.bfloat16)
    acc_ref[:] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(k_idx == n_k - 1)
    def _epilogue():
        o_ref[:] = (acc_ref[:] * s_ref[:][0]).astype(o_ref.dtype)


# the blocks a caller gets who names none (the builders' r3 ladder on a v5e, older than this code)
_BLOCK_M, _BLOCK_N, _BLOCK_K = 256, 256, 512


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k"))
def int8_matmul(
    x: jax.Array,
    qt: QTensor,
    *,
    block_m: int = _BLOCK_M,
    block_n: int = _BLOCK_N,
    block_k: int = _BLOCK_K,
) -> jax.Array:
    """Fused dequant matmul: x [M, K] (or [..., K]) @ QTensor[K, N] → [..., N].

    Falls back to the XLA reference when shapes don't tile evenly.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lead = x.shape[:-1]
    K = x.shape[-1]
    N = qt.q.shape[1]
    xm = x.reshape(-1, K)
    M = xm.shape[0]
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    # TPU minimum-tile alignment (8 sublanes × 128 lanes for f32 blocks) in
    # addition to even tiling — sub-tile blocks would fail Mosaic lowering
    # on hardware even though the interpreter accepts them (batch-1 decode,
    # tiny K, etc. route to XLA, which handles small shapes fine).
    # decode-sized row counts underfill the kernel's M tile: the XLA
    # reference (dequant fused into the einsum) measured faster at M ≤ 32 on
    # BOTH bench geometries (8B-geometry chunk 233→181 ms, 1B 181→171 ms —
    # r3-cont); the kernel is the prefill/training-sized path
    if M < 64 or (M % bm or N % bn or K % bk or bm % 8 or bk % 128 or bn % 128):
        return int8_matmul_ref(x, qt)
    n_k = K // bk

    out = pl.pallas_call(
        functools.partial(_quant_matmul_kernel, n_k=n_k),
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret(),
        cost_estimate=pl.CostEstimate(
            flops=2 * M * N * K,
            bytes_accessed=M * K * x.dtype.itemsize + K * N + M * N * x.dtype.itemsize,
            transcendentals=0,
        ),
    )(xm, qt.q, qt.scale.reshape(1, N))
    return out.reshape(*lead, N)


def quantize_tree(params, min_size: int = 1 << 16):
    """Quantize every >=2-D float leaf with >= min_size elements to QTensor
    (weight-only int8 export; stacked-layer leading dims quantize per layer);
    small/1-D leaves (norms, biases) stay float.

    Returns (tree-with-QTensor-leaves, bytes_before, bytes_after)."""
    before = after = 0
    _SKIP_SUFFIXES = ("norm", "bias", "scale", "ln")

    def visit(path, leaf):
        nonlocal before, after
        sz = leaf.size * leaf.dtype.itemsize
        before += sz
        # two guards against quantizing non-matmul weights:
        # 1. name-based: ANY path segment ending in norm/bias/scale/ln marks
        #    a norm/bias (stacks are [L, D] — 2-D and large at real model
        #    scale, but quantizing them breaks the layer scan and is
        #    numerically wrong; nested layouts like attn_norm/{w,b} put the
        #    telling name on an inner segment). Suffix-of-segment, not
        #    substring, so projections like "upscale_proj" still quantize.
        # 2. shape-based: both trailing dims must look like matmul [K, N].
        segments = [str(getattr(k, "key", k)).lower() for k in path]
        named_skip = any(seg.endswith(s) for seg in segments for s in _SKIP_SUFFIXES)
        is_matmul_like = (
            leaf.ndim >= 2 and leaf.shape[-1] >= 64 and leaf.shape[-2] >= 64
        )
        if (
            not named_skip
            and is_matmul_like
            and leaf.size >= min_size
            and jnp.issubdtype(leaf.dtype, jnp.floating)
        ):
            qt = quantize_int8(leaf)
            after += qt.q.size + qt.scale.size * 4
            return qt
        after += sz
        return leaf

    tree = jax.tree_util.tree_map_with_path(visit, params)
    return tree, before, after
