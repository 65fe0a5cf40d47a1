"""Fused grouped-GEMM SwiGLU kernel for MoE expert compute (Pallas TPU).

The XLA path (``jax.lax.ragged_dot``) runs the three expert GEMMs as
separate megablox custom calls with the [N, F] gate/up activations making
full HBM round-trips between them, and loses ~40% throughput to multi-group
handling even on 512-aligned uniform groups (builders' r3 run, older than this code).
This kernel computes the whole expert MLP — ``silu(x·Wg) ⊙ (x·Wu) · Wd`` —
in ONE VMEM pass per row tile:

- rows arrive sorted by expert (parallel/expert.route_ragged) with group
  sizes padded to the row-tile size, so every tile belongs to exactly one
  expert; a scalar-prefetched ``tile_group`` map drives the weight
  BlockSpecs, and consecutive tiles of the same expert keep the weight
  slab resident in VMEM (Pallas revisit caching);
- the [tile, F] gate/up intermediates live and die in VMEM — no HBM
  round-trips between the three GEMMs;
- the backward is one fused kernel too: recomputes gate/up per tile, then
  produces dx per tile and accumulates dWg/dWu/dWd in VMEM f32 across each
  expert's run of tiles, flushing once per expert (revisited out blocks).

No counterpart in the reference (its MoE support is framework-side; the
equivalent fused kernels live in vendor libraries). VMEM is dominated by
the per-expert weight slabs (3·D·F bf16 ≈ 12.6 MB at D=1024/F=2048,
double-buffered by the pipeline) plus, in the backward, the f32 dW
accumulators (3·D·F·4 ≈ 25 MB); the row-tile buffers scale with TILE_M
(~0.5 MB at the default 128). Measured fine on a v5e's 128 MB at tiles
64–512.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from tony_tpu.ops.interpret import interpret

# fwd row-tile; group sizes are padded to multiples of this. 128 is the r3
# measured optimum on v5e at the bench geometry (same-session ladder:
# 64→36.2%, 96→38.1%, 128→38.4%, 256→36.9%, 512→36.8% active MFU — less
# group-padding waste and tighter pipelining beat bigger GEMM tiles).
# (The builders' r3 ladder, older than this code.)
TILE_M = 128
# bwd row-tile (more VMEM-hungry: f32 dW accumulators); a fwd tile larger than
# this must be a multiple of it (the backward splits fwd tiles into bwd tiles)
TILE_M_BWD = 128
# fwd F-chunking: >0 splits the expert MLP's hidden dim into chunks of this
# size — per chunk: gate/up GEMMs, the silu·mul on the VPU, and a chunked
# down-GEMM accumulating [tile, D] in f32. The monolithic kernel serializes
# MXU(g) → MXU(u) → VPU(h) → MXU(down) per tile; the chunked form lets
# Mosaic overlap the next chunk's MXU work with the current chunk's VPU
# tail. r4 same-session ladder (active MFU, 2 reps): 0 → 38.18/37.92,
# 512 → 38.25/38.25, 1024 → 38.13/38.09 — 512 never loses, ships as
# default; shapes where F % F_CHUNK != 0 fall back to monolithic.
F_CHUNK = 512


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _swiglu_block(x, wg_ref, wu_ref, wd_ref, acc_ref):
    """A block of the expert width for one row tile ``x``: its part of
    ``silu(x Wg) * (x Wu) Wd`` added into ``acc_ref``."""
    fb = wg_ref.shape[1]
    # within a block, F-chunked: overlap the next chunk's gate/up MXU work
    # with the current chunk's VPU silu·mul tail (statically unrolled so
    # Mosaic can software-pipeline the chunk sequence)
    step = F_CHUNK if F_CHUNK and fb % F_CHUNK == 0 and fb > F_CHUNK else fb
    for lo in range(0, fb, step):
        sl = slice(lo, lo + step)
        g = jnp.dot(x, wg_ref[:, sl], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[:, sl], preferred_element_type=jnp.float32)
        h = (_silu(g) * u).astype(x.dtype)
        acc_ref[...] += jnp.dot(h, wd_ref[sl, :], preferred_element_type=jnp.float32)


def _fwd_kernel(tg_ref, meta_ref, xs_ref, wg_ref, wu_ref, wd_ref, ys_ref, acc_ref):
    """One row tile x one block of the expert width: the block's part of
    ``silu(x Wg) * (x Wu) Wd`` added into the tile's float32 accumulator,
    which leaves for HBM with the last block. ``meta_ref[0]`` is the number
    of live row tiles, the groups' own: a tile past them (the rest of the
    static row bound) is skipped, its blocks mapped onto the last live tile's
    so that nothing is fetched or written for it. It may be 0 (no row landed
    on any group): every tile is skipped, all map onto tile 0's last block,
    fetched once, and tile 0's rows come back as unspecified as the rest."""
    from jax.experimental import pallas as pl

    m, c = pl.program_id(0), pl.program_id(1)

    @pl.when(m < meta_ref[0])
    def _tile():
        @pl.when(c == 0)
        def _init():
            acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

        _swiglu_block(xs_ref[...], wg_ref, wu_ref, wd_ref, acc_ref)

        @pl.when(c == pl.num_programs(1) - 1)
        def _flush():
            ys_ref[...] = acc_ref[...].astype(ys_ref.dtype)


def _tokens_kernel(tg_ref, meta_ref, x_ref, tok_ref, gate_ref, wg_ref, wu_ref, wd_ref, y_ref, xs_ref, acc_ref, yacc_ref):
    """``_fwd_kernel`` with the rows gathered and the choices summed in VMEM:
    ``x_ref`` [T, D] is the call's tokens whole, ``tok_ref`` / ``gate_ref``
    [1, tile] the tile's token of each row and its gate (zero on a pad row,
    whose token is 0), ``y_ref`` [T, D] the gated sum over every token's held
    choices. A tile's rows are ``onehot [T, tile]^T x`` (one non-zero term a
    row: exact) at its first block; at its last, the tile's output in the
    activations' type goes into ``yacc_ref`` [T, D] float32 through ``(onehot
    * gate) [T, tile]``: the roundings of the staged form (the output cast,
    the gate cast, products summed wide), a token's choices in tile order.
    ``y`` is zero before the first tile and written after the last grid step,
    live or skipped."""
    from jax.experimental import pallas as pl

    m, c = pl.program_id(0), pl.program_id(1)
    last_block = c == pl.num_programs(1) - 1

    @pl.when((m == 0) & (c == 0))
    def _zero():
        yacc_ref[...] = jnp.zeros(yacc_ref.shape, yacc_ref.dtype)

    @pl.when(m < meta_ref[0])
    def _tile():
        T, tile = x_ref.shape[0], tok_ref.shape[1]
        # [T, tile] float32 (a 32-bit mask selects 32-bit values; the casts to the activations' type follow)
        onehot = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (T, tile), 0) == tok_ref[...], 1.0, 0.0)

        @pl.when(c == 0)
        def _gather():
            acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
            xs_ref[...] = jax.lax.dot_general(
                onehot.astype(x_ref.dtype), x_ref[...], (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
            ).astype(xs_ref.dtype)

        _swiglu_block(xs_ref[...], wg_ref, wu_ref, wd_ref, acc_ref)

        @pl.when(last_block)
        def _combine():
            gates = (onehot * gate_ref[...].astype(y_ref.dtype).astype(jnp.float32)).astype(y_ref.dtype)
            yacc_ref[...] += jnp.dot(gates, acc_ref[...].astype(y_ref.dtype), preferred_element_type=jnp.float32)

    @pl.when((m == pl.num_programs(0) - 1) & last_block)
    def _write():
        y_ref[...] = yacc_ref[...].astype(y_ref.dtype)


#: lanes of a vector register: a row leaves and enters HBM alone as a slab ``[D / LANES, LANES]`` (``row_slabs``)
LANES = 128
#: tokens a step of ``moe_choices_sum`` sums: their choices' rows are fetched together, under the step before
SUM_TOKENS = 64


def row_slabs(x):
    """[T, D] -> [T * D / 128, 128] float32: each row a slab of ``D / 128`` sublanes by 128 lanes, which one DMA
    moves alone (a slice of ``[T, D]`` along T has to be whole tiles of 8 rows; a slab is whole tiles where D
    is a multiple of 1024). 32-bit, so that a strided read of ``[tile * D / 128, 128]`` (sublane j of every
    slab) is columns ``128 j .. 128 j + 127`` of the tile's rows. The values are the activations': what was
    bfloat16 comes back to the bit."""
    T, D = x.shape
    return x.astype(jnp.float32).reshape(T * (D // LANES), LANES)


def _row_copy(src_hbm, src_row, dst_ref, dst_row, sem, per_row):
    """The DMA of one row's slab, ``per_row`` sublanes: ``src_hbm``'s row ``src_row`` to ``dst_ref``'s ``dst_row``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    at = lambda row: pl.ds(row * per_row if isinstance(row, int) else pl.multiple_of(row * per_row, per_row), per_row)
    return pltpu.make_async_copy(src_hbm.at[at(src_row)], dst_ref.at[at(dst_row)], sem)


def _rows_of_slabs(slabs_ref, rows_ref):
    """Rows out of their slabs: ``slabs_ref`` [n * D / 128, 128] -> ``rows_ref`` [n, D], sublane j of every slab
    being columns ``128 j .. 128 j + 127`` of the n rows (a strided read). Written out a block of columns: as a
    loop with the columns at a traced offset the grouped product was 3.73 ms for 3.34 at 2048 x 4096 x 768 of 36
    experts (PERF.md section 6, PR 62); a program's layers share one lowering of it (``moe_swiglu_fetched``)."""
    from jax.experimental import pallas as pl

    n, D = rows_ref.shape
    for j in range(D // LANES):
        rows_ref[:, j * LANES:(j + 1) * LANES] = slabs_ref[pl.ds(j, n, stride=D // LANES), :].astype(rows_ref.dtype)


def _fetch_kernel(tg_ref, meta_ref, tok_ref, real_ref, x_hbm, wg_ref, wu_ref, wd_ref, ys_ref, slabs_ref, xs_ref, acc_ref, sem):
    """``_fwd_kernel`` that fetches a live tile's rows itself: ``x_hbm`` [T * D / 128, 128] float32 is the call's
    tokens as slabs (``row_slabs``), left in HBM; ``tok_ref`` [bound] the token of every sorted row and
    ``real_ref`` [tiles] a tile's real rows (its first ones; a pad row is not fetched), both scalars. At a live
    tile's first block the kernel waits for the tile's slabs (one DMA a real row, started at the tile before's
    first block, so under its products; the first tile starts its own), turns them into the tile's rows
    ``xs_ref`` [tile, D] (``_rows_of_slabs``), and starts the next live tile's fetch into the slabs it
    has just read. A pad row holds zero or an earlier tile's row: finite, computed, never read back. A skipped
    tile fetches nothing. The tile's output leaves as slabs too, ``ys_ref`` [tile * D / 128, 128] float32 of
    values rounded to the activations' type, for ``moe_choices_sum`` to fetch a row at a time."""
    from jax.experimental import pallas as pl

    m, c = pl.program_id(0), pl.program_id(1)
    tile, D = xs_ref.shape
    per_row = D // LANES

    def fetch(t, wait):
        def row(r, carry):
            copy = _row_copy(x_hbm, 0 if wait else tok_ref[t * tile + r], slabs_ref, r, sem.at[0], per_row)
            copy.wait() if wait else copy.start()
            return carry

        jax.lax.fori_loop(0, real_ref[t], row, 0)

    @pl.when(m < meta_ref[0])
    def _tile():
        @pl.when(c == 0)
        def _rows():
            @pl.when(m == 0)
            def _first():
                slabs_ref[...] = jnp.zeros(slabs_ref.shape, slabs_ref.dtype)
                fetch(0, wait=False)

            fetch(m, wait=True)
            _rows_of_slabs(slabs_ref, xs_ref)

            @pl.when(m + 1 < meta_ref[0])
            def _next():
                fetch(m + 1, wait=False)

            acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

        _swiglu_block(xs_ref[...], wg_ref, wu_ref, wd_ref, acc_ref)

        @pl.when(c == pl.num_programs(1) - 1)
        def _flush():
            for j in range(per_row):
                columns = acc_ref[:, j * LANES:(j + 1) * LANES]
                ys_ref[pl.ds(j, tile, stride=per_row), :] = columns.astype(xs_ref.dtype).astype(ys_ref.dtype)


def _sum_kernel(dest_ref, gate_ref, order_ref, count_ref, ys_hbm, y_ref, rows_ref, sums_ref, sem, *, top_k):
    """A step's tokens' gated sum over their choices, from the rows that exist: ``ys_hbm`` [bound * D / 128,
    128] float32 the experts' outputs as slabs, in HBM; scalars: ``dest_ref`` [T * K] a choice's row,
    ``gate_ref`` [T * K] its gate, ``order_ref`` [T * K] a step's choices that have a row, in choice order, as
    places ``token * K + k`` within the step, and ``count_ref`` [steps] how many those are. A step waits for
    its rows (one DMA each, into the choice's own place of ``rows_ref`` [2, tokens * K * D / 128, 128], all
    started at the step before; the first step starts its own), starts the next step's, and adds each row,
    gated, to its token's sum in float32, in choice order: a choice without a row costs nothing, and a place
    no row was fetched into is never read. ``sums_ref`` [tokens * D / 128, 128] becomes ``y_ref`` [tokens, D]
    (``_rows_of_slabs``)."""
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    tokens, D = y_ref.shape
    per_row, places = D // LANES, tokens * top_k
    slot = i % 2

    def start(step, into):
        def choice(j, carry):
            place = order_ref[step * places + j]
            _row_copy(ys_hbm, dest_ref[step * places + place], rows_ref.at[into], place, sem.at[into], per_row).start()
            return carry

        jax.lax.fori_loop(0, count_ref[step], choice, 0)

    @pl.when(i == 0)
    def _first():
        start(0, 0)

    def wait(_, carry):
        _row_copy(ys_hbm, 0, rows_ref.at[slot], 0, sem.at[slot], per_row).wait()
        return carry

    jax.lax.fori_loop(0, count_ref[i], wait, 0)

    @pl.when(i + 1 < pl.num_programs(0))
    def _next():
        start(i + 1, 1 - slot)

    sums_ref[...] = jnp.zeros(sums_ref.shape, sums_ref.dtype)

    def add(j, carry):
        place = order_ref[i * places + j]
        of_token = pl.ds(pl.multiple_of(place // top_k * per_row, per_row), per_row)
        row = rows_ref[slot, pl.ds(pl.multiple_of(place * per_row, per_row), per_row), :]
        sums_ref[of_token, :] += row * gate_ref[i * places + place]
        return carry

    jax.lax.fori_loop(0, count_ref[i], add, 0)
    _rows_of_slabs(sums_ref, y_ref)


def _bwd_kernel(
    tg_ref, xs_ref, dy_ref, wg_ref, wu_ref, wd_ref,
    dxs_ref, dwg_ref, dwu_ref, dwd_ref,
):
    from jax.experimental import pallas as pl

    m = pl.program_id(0)
    prev = tg_ref[jnp.maximum(m - 1, 0)]
    first_of_group = jnp.logical_or(m == 0, tg_ref[m] != prev)

    @pl.when(first_of_group)
    def _init():
        dwg_ref[...] = jnp.zeros(dwg_ref.shape, dwg_ref.dtype)
        dwu_ref[...] = jnp.zeros(dwu_ref.shape, dwu_ref.dtype)
        dwd_ref[...] = jnp.zeros(dwd_ref.shape, dwd_ref.dtype)

    x = xs_ref[...]
    dy = dy_ref[...]
    # recompute the forward intermediates for this tile (remat-in-kernel)
    g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
    s = jax.nn.sigmoid(g)
    silu_g = g * s
    h = (silu_g * u).astype(x.dtype)

    # dh = dy · Wd^T  (contract the D dims — no transposed weight copy)
    dh = jax.lax.dot_general(
        dy, wd_ref[0], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    du = (dh * silu_g).astype(x.dtype)
    dsilu = s * (1.0 + g * (1.0 - s))
    dg = (dh * u * dsilu).astype(x.dtype)

    dxs = jax.lax.dot_general(
        dg, wg_ref[0], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) + jax.lax.dot_general(
        du, wu_ref[0], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    dxs_ref[...] = dxs.astype(dxs_ref.dtype)

    # per-expert weight grads: accumulate f32 in VMEM across the expert's
    # tile run (the out blocks revisit while tile_group stays constant)
    dwg_ref[0] += jax.lax.dot_general(
        x, dg, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    dwu_ref[0] += jax.lax.dot_general(
        x, du, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    dwd_ref[0] += jax.lax.dot_general(
        h, dy, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


#: VMEM the three weight blocks of a grid step may take, double-buffered (of the 100 MB the call asks for)
_WEIGHT_VMEM = 48 * 1024 * 1024


def width_block(D: int, F: int, itemsize: int) -> int:
    """The block of the expert width a grid step holds: all of F where an
    expert's three slabs fit VMEM double-buffered (D=1024, F=2048: one block,
    the slab resident across an expert's tiles), else the largest multiple of
    128 that divides F and fits (D=6144, F=2048 in bf16: 512)."""
    for fb in range(F, 127, -128):
        if F % fb == 0 and fb % 128 == 0 and 3 * D * fb * itemsize * 2 <= _WEIGHT_VMEM:
            return fb
    return F


def _fwd_call(xs, wg, wu, wd, tile_group, tile, live=None, layer=0, name="moe_swiglu_grouped", route=None, fetch=None):
    """wg/wu [L, E, D, F], wd [L, E, F, D] (or without the leading L): the
    banks of every layer that shares them, with the layer's index a scalar
    operand: a layer's slice handed in would be a copy of it a call (a Mosaic
    operand needs a buffer of its own). ``route`` = (sort_tok, gate_sorted),
    each [PN]: ``xs`` is then the tokens ``x`` [T, D] and the result ``y``
    [T, D] (``_tokens_kernel``). ``fetch`` = (sort_tok [PN], real [PN / tile]):
    ``xs`` is then the tokens as slabs (``row_slabs``), which stay in HBM, and
    the result the sorted rows' outputs as slabs (``_fetch_kernel``). The grid,
    the first two scalars and the weight blocks are the same in all three."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if wg.ndim == 3:
        wg, wu, wd = wg[None], wu[None], wd[None]
    D, F = wg.shape[-2:]
    PN = xs.shape[0] if route is None and fetch is None else (route or fetch)[0].shape[0]
    dtype = wg.dtype if fetch is not None else xs.dtype
    fb = width_block(D, F, dtype.itemsize)
    n_tiles, n_blocks = PN // tile, F // fb
    meta = jnp.stack([jnp.asarray(n_tiles if live is None else live, jnp.int32), jnp.asarray(layer, jnp.int32)])
    scalars = (tile_group, meta) + (() if fetch is None else tuple(fetch))

    def tile_of(m, meta):
        return jnp.minimum(m, jnp.maximum(meta[0] - 1, 0))       # a skipped tile: the last live one, or tile 0 with none live

    def rows(m, c, tg, meta, *_):
        return tile_of(m, meta), 0

    def block(m, c, meta):
        return jnp.where(m < meta[0], c, n_blocks - 1)

    def up(m, c, tg, meta, *_):
        return meta[1], tg[tile_of(m, meta)], 0, block(m, c, meta)

    def down(m, c, tg, meta, *_):
        return meta[1], tg[tile_of(m, meta)], block(m, c, meta), 0

    weights = [
        pl.BlockSpec((None, None, D, fb), up),
        pl.BlockSpec((None, None, D, fb), up),
        pl.BlockSpec((None, None, fb, D), down),
    ]
    acc = pltpu.VMEM((tile, D), jnp.float32)
    out_shape = jax.ShapeDtypeStruct(xs.shape, xs.dtype)
    if fetch is not None:
        per_row = D // LANES
        kernel, operands = _fetch_kernel, (xs,)
        ins, out = [pl.BlockSpec(memory_space=pl.ANY)], pl.BlockSpec((tile * per_row, LANES), rows)
        scratch = [pltpu.VMEM((tile * per_row, LANES), xs.dtype), pltpu.VMEM((tile, D), dtype), acc, pltpu.SemaphoreType.DMA((1,))]
        out_shape = jax.ShapeDtypeStruct((PN * per_row, LANES), xs.dtype)
    elif route is None:
        kernel, operands = _fwd_kernel, (xs,)
        ins, out, scratch = [pl.BlockSpec((tile, D), rows)], pl.BlockSpec((tile, D), rows), [acc]
    else:
        T = xs.shape[0]
        whole = pl.BlockSpec((T, D), lambda m, c, tg, meta: (0, 0))                  # one block, fetched once
        of_tile = pl.BlockSpec((None, 1, tile), lambda m, c, tg, meta: (tile_of(m, meta), 0, 0))
        kernel = _tokens_kernel
        operands = (xs, route[0].reshape(n_tiles, 1, tile), route[1].reshape(n_tiles, 1, tile))
        ins, out = [whole, of_tile, of_tile], whole
        scratch = [pltpu.VMEM((tile, D), xs.dtype), acc, pltpu.VMEM((T, D), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(n_tiles, n_blocks),
        in_specs=ins + weights,
        out_specs=out,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),  # revisit caching needs order
            vmem_limit_bytes=100 * 1024 * 1024,  # weight blocks resident (v5e: 128M)
        ),
        interpret=interpret(),
        name=name,
        cost_estimate=pl.CostEstimate(
            flops=2 * PN * D * F * 3,
            bytes_accessed=xs.size * 2 * xs.dtype.itemsize + 3 * wg.shape[1] * D * F * dtype.itemsize,
            transcendentals=PN * F,
        ),
    )(*scalars, *operands, wg, wu, wd)


def moe_swiglu_rows(xs, wg, wu, wd, tile_group, tile, live, layer=0, name="moe_swiglu_grouped"):
    """The forward alone, for serving: ``moe_swiglu_grouped``'s product over the
    first ``live`` row tiles (a traced count, which may be 0); rows of later
    tiles are left unwritten and the caller never reads them. ``wg``/``wu``/
    ``wd`` may carry a leading layer dimension, with ``layer`` the index into
    it. ``name`` is the call's name in a trace."""
    return _fwd_call(xs, wg, wu, wd, tile_group, tile, live, layer, name)


def moe_swiglu_tokens(x, sort_tok, gate_sorted, wg, wu, wd, tile_group, tile, live, layer=0, name="moe_swiglu_grouped"):
    """``moe_swiglu_rows`` over the tokens themselves, for a call whose tokens
    fit VMEM: x [T, D] -> y [T, D] with ``y[t] = sum over the sorted rows j of
    token t of gate_sorted[j] * expert(x[t])``, the rows being ``sort_tok``
    [PN] in expert order (``parallel/expert.route_ragged``; a pad row names
    token 0 with gate 0). Neither the sorted rows nor the experts' outputs
    exist in HBM: the call reads ``x`` once, the live tiles' weights, and
    writes ``y`` (zero where ``live`` is 0). T is padded to whole sublane
    groups of the activations' type here."""
    T = x.shape[0]
    pad = -T % (32 // x.dtype.itemsize)
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return _fwd_call(x, wg, wu, wd, tile_group, tile, live, layer, name, route=(sort_tok, gate_sorted))[:T]


@functools.partial(jax.jit, static_argnames=("tile", "name"))             # a program's layers share ONE lowering of the kernel's body
def moe_swiglu_fetched(x, sort_tok, real, wg, wu, wd, tile_group, tile, live, layer=0, name="moe_swiglu_grouped"):
    """``moe_swiglu_rows`` that fetches its rows from the tokens, for a call whose tokens do not fit VMEM: x
    [T, D] -> the sorted rows' outputs as slabs, [PN * D / 128, 128] float32 of values rounded to x's type,
    the live tiles' written. ``sort_tok`` [PN] is the token of every sorted row and ``real`` [PN / tile] the
    real rows of every tile, a live tile's first ones (``parallel/expert.route_ragged``). ``x[sort_tok]`` is
    never built: a live tile's real rows come by one DMA each from ``row_slabs(x)``, a pass over the tokens,
    and nothing of the static row bound is read or written but the live tiles' outputs."""
    return _fwd_call(row_slabs(x), wg, wu, wd, tile_group, tile, live, layer, name, fetch=(sort_tok, real))


@functools.partial(jax.jit, static_argnames=("top_k", "D", "dtype", "name"))
def moe_choices_sum(ys, dest, gates, top_k, D, dtype, name="moe_choices_sum"):
    """The gated sum over every token's choices, from the rows that exist: ``ys`` [PN * D / 128, 128] float32
    the sorted rows' outputs as slabs (``moe_swiglu_fetched``), ``dest`` [T * K] a choice's row (PN or more
    where the choice has none here), ``gates`` [T * K] its gate -> y [T, D] of ``dtype``, ``y[t] = sum over
    the k that have a row of gates[t, k] * ys[dest[t, k]]`` in float32 in choice order, the gates rounded to
    ``dtype`` first: the roundings of ``jnp.einsum("tkd,tk->td", ys[dest], gates.astype(dtype))``. A row costs
    one DMA and one multiply-add of its slab, a choice without a row nothing: nothing of the static row bound
    is read. T is padded to whole steps of ``SUM_TOKENS`` tokens here, with choices that have no row; a
    step's choices that have one are listed by a stable sort of ``SUM_TOKENS * K`` flags."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    per_row = D // LANES
    T = dest.shape[0] // top_k
    pad = -T % SUM_TOKENS
    steps, places = (T + pad) // SUM_TOKENS, SUM_TOKENS * top_k
    dest = jnp.pad(dest.astype(jnp.int32), (0, pad * top_k), constant_values=ys.shape[0] // per_row)
    gates = jnp.pad(gates.astype(dtype).astype(jnp.float32), (0, pad * top_k))
    has_row = (dest < ys.shape[0] // per_row).reshape(steps, places)
    order = jnp.argsort(~has_row, axis=1, stable=True).astype(jnp.int32).reshape(steps * places)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(steps,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((SUM_TOKENS, D), lambda i, *_: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, places * per_row, LANES), ys.dtype),
            pltpu.VMEM((SUM_TOKENS * per_row, LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    y = pl.pallas_call(
        functools.partial(_sum_kernel, top_k=top_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T + pad, D), dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret(),
        name=name,
    )(dest, gates, order, has_row.sum(1, dtype=jnp.int32), ys)
    return y[:T]


def _bwd_call(xs, dy, wg, wu, wd, tile_group, tile):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    PN, D = xs.shape
    E, _, F = wg.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(PN // tile,),
        in_specs=[
            pl.BlockSpec((tile, D), lambda m, tg: (m, 0)),
            pl.BlockSpec((tile, D), lambda m, tg: (m, 0)),
            pl.BlockSpec((1, D, F), lambda m, tg: (tg[m], 0, 0)),
            pl.BlockSpec((1, D, F), lambda m, tg: (tg[m], 0, 0)),
            pl.BlockSpec((1, F, D), lambda m, tg: (tg[m], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile, D), lambda m, tg: (m, 0)),
            pl.BlockSpec((1, D, F), lambda m, tg: (tg[m], 0, 0)),
            pl.BlockSpec((1, D, F), lambda m, tg: (tg[m], 0, 0)),
            pl.BlockSpec((1, F, D), lambda m, tg: (tg[m], 0, 0)),
        ],
    )
    return pl.pallas_call(
        _bwd_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((PN, D), xs.dtype),
            jax.ShapeDtypeStruct((E, D, F), jnp.float32),
            jax.ShapeDtypeStruct((E, D, F), jnp.float32),
            jax.ShapeDtypeStruct((E, F, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024,  # f32 dW accumulators + weight slabs
        ),
        interpret=interpret(),
        cost_estimate=pl.CostEstimate(
            flops=2 * PN * D * F * 8,
            bytes_accessed=(xs.size * 3 + 2 * (wg.size + wu.size + wd.size))
            * xs.dtype.itemsize,
            transcendentals=PN * F,
        ),
    )(tile_group, xs, dy, wg, wu, wd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def moe_swiglu_grouped(xs, wg, wu, wd, tile_group, tile=TILE_M):
    """Fused grouped SwiGLU: ``ys[i] = silu(xs[i]·Wg[g]) ⊙ (xs[i]·Wu[g]) · Wd[g]``
    where ``g = tile_group[i // tile]``.

    xs: [PN, D] rows sorted by expert, each group's span padded to a
    multiple of ``tile`` (see parallel/expert.route_ragged with tile=...);
    wg/wu: [E, D, F]; wd: [E, F, D]; tile_group: [PN/tile] int32 expert id
    per row tile (must be non-decreasing — weight residency and the
    backward's accumulate-then-flush both rely on it).

    Rows inside a group's padding compute garbage through the expert — the
    caller must never read them (the choice-order combine gathers only real
    rows) and their upstream cotangent must be zero (it is: the combine's
    transpose scatter-adds only real rows).
    """
    return _fwd_call(xs, wg, wu, wd, tile_group, tile)


def _vjp_fwd(xs, wg, wu, wd, tile_group, tile):
    from jax.ad_checkpoint import checkpoint_name

    ys = _fwd_call(xs, wg, wu, wd, tile_group, tile)
    ys = checkpoint_name(ys, "moe_gemm")
    return ys, (xs, wg, wu, wd, tile_group)


def _vjp_bwd(tile, res, dy):
    xs, wg, wu, wd, tile_group = res
    bwd_tile = tile
    if tile > TILE_M_BWD:
        if tile % TILE_M_BWD:  # tile is a call arg
            raise ValueError(
                f"tile={tile} is larger than but not a multiple of "
                f"TILE_M_BWD={TILE_M_BWD}: the backward cannot split "
                "the padded group spans — pick a multiple (or set them equal)"
            )
        # finer backward tiling: same group spans (TILE_M_BWD divides the
        # fwd tile), each fwd tile simply splits into tile/TILE_M_BWD rows
        tile_group = jnp.repeat(tile_group, tile // TILE_M_BWD)
        bwd_tile = TILE_M_BWD
    dxs, dwg, dwu, dwd = _bwd_call(
        xs, dy.astype(xs.dtype), wg, wu, wd, tile_group, bwd_tile
    )
    return (
        dxs,
        dwg.astype(wg.dtype),
        dwu.astype(wu.dtype),
        dwd.astype(wd.dtype),
        np.zeros(tile_group.shape, jax.dtypes.float0),
    )


moe_swiglu_grouped.defvjp(_vjp_fwd, _vjp_bwd)


def tile_group_map(group_sizes_padded: jax.Array, num_tiles: int, tile: int) -> jax.Array:
    """[E] padded group sizes → [num_tiles] expert id per row tile.

    A group of size zero (a held expert no row chose, forward only) has no
    tile: ``side="right"`` steps over it to the next group that has rows.
    Tiles beyond ``sum(group_sizes_padded)`` clamp to the last expert: with
    a backward they compute garbage on pad rows that nothing reads, and
    contribute zero to every gradient (their upstream cotangent rows are
    zero); the forward-only call skips them (``moe_swiglu_rows``'s ``live``).
    """
    bounds = jnp.cumsum(group_sizes_padded)                       # [E]
    starts = jnp.arange(num_tiles, dtype=jnp.int32) * tile
    return jnp.minimum(
        jnp.searchsorted(bounds, starts, side="right").astype(jnp.int32),
        group_sizes_padded.shape[0] - 1,
    )
