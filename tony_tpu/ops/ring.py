"""Pallas remote-DMA ring attention: the hand-overlapped CP data plane.

The XLA implementation (parallel/context.ring_attention) expresses the ring
as ``lax.scan`` + ``ppermute`` and leaves transfer/compute overlap to the
compiler. This module is the same blockwise-softmax schedule written as ONE
Pallas kernel per device: KV shards travel the ``context``-axis ring as
inter-chip RDMA (``make_async_remote_copy`` over ICI) between **HBM-resident
double-buffered slots**, while the kernel overlaps each transfer with the
flash-attention math on the slot it already holds — the TPU analog of the
reference's NCCL-ring data plane, which lived inside user frameworks
(SURVEY.md §2.6), built per the Pallas guide's ring-collective pattern.

VMEM discipline: only tiles pass through VMEM (q/k/v blocks and the f32
softmax state for one q block), so per-device shard size is bounded by HBM,
not VMEM, and KV stays at Hkv width end to end (GQA-native — q heads alias
onto kv heads inside the compute loop, never broadcast).

Differentiable end-to-end in-kernel: the custom VJP's backward is its own
remote-DMA ring kernel (``_ring_bwd_kernel``) — dk/dv partial sums ride the
ring alongside their KV shard, each device adds its local contribution
(recomputing p blockwise from q/k/lse), and a final rotation delivers each
shard's finished gradients home. No XLA-ring fallback anywhere; the kernel
drops into training models via ``LlamaConfig(cp_impl="pallas")``.

Validated in TPU-interpret mode (which emulates RDMA + semaphores across
shard_map devices, with race detection) on a virtual CPU mesh; the real-ICI
path uses the same code with ``interpret=None`` on a physical slice.
"""

from __future__ import annotations

import functools
import os
from typing import Any

import jax
import jax.numpy as jnp

from tony_tpu.ops.attention import NEG_INF, _STAT_LANES
from tony_tpu.ops.interpret import interpret

# Registry of Pallas collective_ids in this program. A collective_id names the
# cross-device barrier-semaphore set; two concurrently-live collective kernels
# sharing an id would alias barrier counts and silently hang. Reserve ids here.
RING_ATTENTION_COLLECTIVE_ID = 7      # forward ring kernel
RING_ATTENTION_BWD_COLLECTIVE_ID = 8  # backward ring kernel (may overlap fwd
                                      # of the next microbatch under pipelining)
# next free id: 9


def default_interpret():
    """InterpretParams when the env asks for emulated kernels, else False
    (same TONY_PALLAS_INTERPRET contract as ops/attention.py)."""
    if interpret():
        from jax.experimental.pallas import tpu as pltpu

        return pltpu.InterpretParams()
    return False


# ring block caps, env-tunable like the flash kernels' TONY_FLASH_BQ/BK.
# The builders' r3 flash ladder (older than this code) measured bk 512 > 256
# on every single-chip preset; the ring's KV block also sets the per-rotation DMA slab, and
# without multi-chip hardware the 256 default stays unvalidated — retune
# TONY_RING_BQ/BK on a real slice.
_RING_BQ = int(os.environ.get("TONY_RING_BQ", "256"))
_RING_BK = int(os.environ.get("TONY_RING_BK", "256"))
for _name, _b in (("TONY_RING_BQ", _RING_BQ), ("TONY_RING_BK", _RING_BK)):
    if _b < 8:  # fail at import, not deep inside a shard_map trace; the value
        # is a CAP on the block search, so any integer ≥ 8 is usable
        raise ValueError(f"{_name}={_b}: ring block caps must be >= 8")


def _pick_block(Tl: int, cap: int = 256) -> int:
    """Largest divisor of the per-device sequence that is a multiple of 8
    and ≤ cap — no hard error for short shards (VERDICT r2 weak #6)."""
    for b in range(min(cap, Tl), 7, -1):
        if Tl % b == 0 and b % 8 == 0:
            return b
    raise ValueError(
        f"per-device sequence {Tl} has no block size (multiple of 8, <= {cap})"
    )


def _ring_fwd_kernel(
    my_ref, q_hbm, k_hbm, v_hbm, *rest,
    n: int, axis_name: str, causal: bool, scale: float,
    n_rep: int, bq: int, bk: int, window: int, has_seg: bool, H: int,
):
    """One device's whole ring pass. Grid: () — the ring loop is in-kernel.

    Per step: (1) neighbor barrier, (2) start the HBM→HBM RDMA of the current
    KV slot to the right neighbor's other slot, (3) stream (q block × kv
    block) tiles through VMEM updating the online-softmax state persisted in
    HBM scratch, (4) wait both RDMA semaphores. Causally-masked tiles are
    skipped before their DMA is issued; a ``window`` adds the symmetric
    below-band skip (SWA), and packed ``segment_ids`` confine attention
    within segments (the GLOBAL segment table rides along replicated — ids
    are tiny next to KV — so no extra ring traffic).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if has_seg:
        segq_hbm, segk_hbm = rest[0], rest[1]
        (o_hbm, lse_hbm, kbuf, vbuf, acc_hbm, m_hbm, l_hbm,
         qt, kt, vt, acct, mt, lt, ot, segqt, segkt,
         csem, send_sem, recv_sem, ready_sem) = rest[2:]
    else:
        segq_hbm = segk_hbm = segqt = segkt = None
        (o_hbm, lse_hbm, kbuf, vbuf, acc_hbm, m_hbm, l_hbm,
         qt, kt, vt, acct, mt, lt, ot,
         csem, send_sem, recv_sem, ready_sem) = rest

    BH, Tl, D = q_hbm.shape
    my = my_ref[0]
    right = jax.lax.rem(my + 1, n)
    left = jax.lax.rem(my + n - 1, n)
    num_qb, num_kb = Tl // bq, Tl // bk

    def copy(src, dst):
        cp = pltpu.make_async_copy(src, dst, csem.at[0])
        cp.start()
        cp.wait()

    # entry rendezvous: both neighbors have entered the kernel (so their
    # ring-slot scratch is live) before any RDMA targets it. Data
    # dependencies bound inter-invocation skew to one kernel, so the global
    # barrier semaphore's counting cannot alias across invocations.
    barrier = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(
        barrier, inc=1, device_id={axis_name: left},
        device_id_type=pltpu.DeviceIdType.MESH,
    )
    pltpu.semaphore_signal(
        barrier, inc=1, device_id={axis_name: right},
        device_id_type=pltpu.DeviceIdType.MESH,
    )
    pltpu.semaphore_wait(barrier, 2)

    # stage the local KV shard into ring slot 0
    copy(k_hbm, kbuf.at[0])
    copy(v_hbm, vbuf.at[0])

    for s in range(n):  # static unroll: n is the mesh-axis size
        cur, nxt = s % 2, (s + 1) % 2
        if s < n - 1:
            if s > 0:
                # the right neighbor freed its slot `nxt` (it finished
                # computing step s-1 on it and said so); a per-neighbor,
                # per-slot semaphore — unlike a counting barrier, a fast
                # LEFT neighbor's signals can never stand in for the right
                # neighbor's (data deps bound neighbor skew to one step, so
                # parity indexing cannot alias across rounds)
                pltpu.semaphore_wait(ready_sem.at[nxt], 1)
            rk = pltpu.make_async_remote_copy(
                src_ref=kbuf.at[cur], dst_ref=kbuf.at[nxt],
                send_sem=send_sem.at[cur, 0], recv_sem=recv_sem.at[nxt, 0],
                device_id={axis_name: right},
                device_id_type=pltpu.DeviceIdType.MESH,
            )
            rv = pltpu.make_async_remote_copy(
                src_ref=vbuf.at[cur], dst_ref=vbuf.at[nxt],
                send_sem=send_sem.at[cur, 1], recv_sem=recv_sem.at[nxt, 1],
                device_id={axis_name: right},
                device_id_type=pltpu.DeviceIdType.MESH,
            )
            rk.start()
            rv.start()

        src = jax.lax.rem(my - s + n, n)  # whose KV shard slot `cur` holds

        def qb_body(bh, qb):
            kvh = bh // n_rep
            copy(q_hbm.at[bh, pl.ds(qb * bq, bq)], qt)
            if has_seg:
                copy(segq_hbm.at[bh // H, pl.ds(qb * bq, bq)], segqt)
            if s == 0:
                acct[:] = jnp.zeros_like(acct)
                mt[:] = jnp.full_like(mt, NEG_INF)
                lt[:] = jnp.zeros_like(lt)
            else:
                copy(acc_hbm.at[bh, pl.ds(qb * bq, bq)], acct)
                copy(m_hbm.at[bh, pl.ds(qb * bq, bq)], mt)
                copy(l_hbm.at[bh, pl.ds(qb * bq, bq)], lt)
            qv = qt[:].astype(jnp.float32) * scale
            q0 = my * Tl + qb * bq  # global position of this q block's row 0

            def kb_body(kb, _):
                k0 = src * Tl + kb * bk

                ok = jnp.bool_(True)
                if causal:
                    ok = jnp.logical_and(ok, k0 <= q0 + bq - 1)
                if window > 0:  # whole tile below the band ⇒ skip its DMA
                    ok = jnp.logical_and(ok, k0 + bk - 1 >= q0 - window + 1)

                @pl.when(ok)
                def _tile():
                    copy(kbuf.at[cur, kvh, pl.ds(kb * bk, bk)], kt)
                    copy(vbuf.at[cur, kvh, pl.ds(kb * bk, bk)], vt)
                    s_blk = jax.lax.dot_general(
                        qv, kt[:].astype(jnp.float32),
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )  # [bq, bk]
                    masked = causal or window > 0 or has_seg
                    if causal or window > 0:
                        q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
                        k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
                        keep = jnp.bool_(True)
                        if causal:
                            keep = jnp.logical_and(keep, q_pos >= k_pos)
                        if window > 0:
                            keep = jnp.logical_and(keep, k_pos > q_pos - window)
                        s_blk = jnp.where(keep, s_blk, NEG_INF)
                    if has_seg:
                        copy(
                            segk_hbm.at[bh // H, :, pl.ds(src * Tl + kb * bk, bk)],
                            segkt,
                        )
                        s_blk = jnp.where(
                            segqt[:][:, :1] == segkt[:][:1, :], s_blk, NEG_INF
                        )
                    m_prev = mt[:][:, :1]
                    l_prev = lt[:][:, :1]
                    m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=-1, keepdims=True))
                    alpha = jnp.exp(m_prev - m_new)
                    p = jnp.exp(s_blk - m_new)
                    if masked:  # fully-masked rows: keep contributions exactly 0
                        p = jnp.where(s_blk <= NEG_INF / 2, 0.0, p)
                    lt[:] = jnp.broadcast_to(
                        l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True), lt.shape
                    )
                    mt[:] = jnp.broadcast_to(m_new, mt.shape)
                    acct[:] = acct[:] * alpha + jax.lax.dot_general(
                        p, vt[:].astype(jnp.float32),
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )

                return 0

            jax.lax.fori_loop(0, num_kb, kb_body, 0)
            if s == n - 1:
                ot[:] = (acct[:] / jnp.maximum(lt[:][:, :1], 1e-20)).astype(ot.dtype)
                copy(ot, o_hbm.at[bh, pl.ds(qb * bq, bq)])
                # lse residual for the ring backward (lane-replicated)
                mt[:] = mt[:] + jnp.log(jnp.maximum(lt[:], 1e-20))
                copy(mt, lse_hbm.at[bh, pl.ds(qb * bq, bq)])
            else:
                copy(acct, acc_hbm.at[bh, pl.ds(qb * bq, bq)])
                copy(mt, m_hbm.at[bh, pl.ds(qb * bq, bq)])
                copy(lt, l_hbm.at[bh, pl.ds(qb * bq, bq)])

        def run_qb_loop():
            jax.lax.fori_loop(
                0, BH * num_qb,
                lambda i, _: (qb_body(i // num_qb, i % num_qb), 0)[1], 0,
            )

        if causal and 0 < s < n - 1:
            # whole KV shard in the future ⇒ skip the entire state round-trip
            # for this step, not just the tile compute (s=0 always has src=my;
            # s=n-1 must run to write o). A window also skips shards wholly
            # BELOW the band (k entirely before my earliest in-window row).
            needed = src <= my
            if window > 0:
                needed = jnp.logical_and(
                    needed, src * Tl + Tl - 1 >= my * Tl - window + 1
                )
            pl.when(needed)(run_qb_loop)
        else:
            run_qb_loop()

        if s < n - 1:
            rk.wait()
            rv.wait()
            # done reading slot `cur` — BOTH as compute input and as the
            # outgoing RDMA source (rk/rv.wait() above confirms the send
            # finished; signaling earlier would let the left neighbor
            # overwrite the buffer mid-send). Tell the LEFT neighbor (whose
            # step-s+1 RDMA targets our `cur`) it may overwrite it. No
            # circular wait: the ready-wait chain grounds out at s=0.
            pltpu.semaphore_signal(
                ready_sem.at[cur], inc=1, device_id={axis_name: left},
                device_id_type=pltpu.DeviceIdType.MESH,
            )

    if n > 1:
        # drain the right neighbor's final free-signal (sent at its step
        # n-2, consumed by no RDMA): semaphores must be zero at kernel exit
        pltpu.semaphore_wait(ready_sem.at[(n - 2) % 2], 1)


def _seg_layouts(segment_ids, axis_name):
    """Local seg [B, Tl] → (segq [B, Tl, LANES] f32 local, segk
    [B, LANES, T_global] f32 — the all-gathered global table; ids are tiny
    next to KV, so replicating beats adding them to the ring payload)."""
    segf = segment_ids.astype(jnp.float32)
    segq = jnp.broadcast_to(segf[:, :, None], (*segf.shape, _STAT_LANES))
    gathered = jax.lax.all_gather(segf, axis_name)            # [n, B, Tl]
    full = jnp.moveaxis(gathered, 0, 1).reshape(segf.shape[0], -1)  # [B, T]
    segk = jnp.broadcast_to(full[:, None, :], (full.shape[0], _STAT_LANES, full.shape[1]))
    return segq, segk


def _refuse_on_tpu() -> None:
    """The ring kernels have only ever run under the interpreter, and the
    chip's compiler refuses them (v5e:2x2, 4-way context mesh,
    [1,16,8192,128], forward and backward): the 8-lane row-statistics layout
    (``_STAT_LANES``) is sliced below the 128-lane tiling. Until that layout
    is repaired (ROADMAP R5b) an explicit ``cp_impl="pallas"`` on a TPU
    backend says so instead of failing somewhere inside Mosaic."""
    if jax.default_backend() == "tpu":
        raise NotImplementedError(
            "cp_impl='pallas' (the remote-DMA ring kernel) does not compile for "
            "the TPU yet: 'Mosaic failed to compile TPU kernel: Slice shape "
            "along dimension 2 must be aligned to tiling (128), but is 8'. Use "
            "cp_impl='xla' or 'ulysses' (ROADMAP R5b)")


def _ring_fwd(q, k, v, axis_name: str, causal: bool, interpret: Any,
              window: int = 0, segment_ids=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _refuse_on_tpu()
    B, H, Tl, D = q.shape
    Hkv = k.shape[1]
    if H % Hkv:
        raise ValueError(f"n_heads {H} must be divisible by n_kv_heads {Hkv}")
    n_rep = H // Hkv
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    scale = D ** -0.5
    bq = _pick_block(Tl, _RING_BQ)
    bk = _pick_block(Tl, _RING_BK)
    has_seg = segment_ids is not None
    qf = q.reshape(B * H, Tl, D)
    kf = k.reshape(B * Hkv, Tl, D)
    vf = v.reshape(B * Hkv, Tl, D)

    kernel = functools.partial(
        _ring_fwd_kernel, n=n, axis_name=axis_name, causal=causal, scale=scale,
        n_rep=n_rep, bq=bq, bk=bk, window=window, has_seg=has_seg, H=H,
    )
    hbm = pltpu.MemorySpace.HBM
    operands = [jnp.full((1,), my, jnp.int32), qf, kf, vf]
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM),
        pl.BlockSpec(memory_space=hbm),
        pl.BlockSpec(memory_space=hbm),
        pl.BlockSpec(memory_space=hbm),
    ]
    seg_tiles = []
    if has_seg:
        segq, segk = _seg_layouts(segment_ids, axis_name)
        operands += [segq, segk]
        in_specs += [pl.BlockSpec(memory_space=hbm), pl.BlockSpec(memory_space=hbm)]
        seg_tiles = [
            pltpu.MemorySpace.VMEM((bq, _STAT_LANES), jnp.float32),
            pltpu.MemorySpace.VMEM((_STAT_LANES, bk), jnp.float32),
        ]
    out, lse = pl.pallas_call(
        kernel,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec(memory_space=hbm), pl.BlockSpec(memory_space=hbm)],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tl, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, Tl, _STAT_LANES), jnp.float32),
        ],
        scratch_shapes=[
            hbm((2, B * Hkv, Tl, D), k.dtype),            # ring KV slots
            hbm((2, B * Hkv, Tl, D), v.dtype),
            hbm((B * H, Tl, D), jnp.float32),             # online-softmax state
            hbm((B * H, Tl, _STAT_LANES), jnp.float32),
            hbm((B * H, Tl, _STAT_LANES), jnp.float32),
            pltpu.MemorySpace.VMEM((bq, D), q.dtype),     # tiles
            pltpu.MemorySpace.VMEM((bk, D), k.dtype),
            pltpu.MemorySpace.VMEM((bk, D), v.dtype),
            pltpu.MemorySpace.VMEM((bq, D), jnp.float32),
            pltpu.MemorySpace.VMEM((bq, _STAT_LANES), jnp.float32),
            pltpu.MemorySpace.VMEM((bq, _STAT_LANES), jnp.float32),
            pltpu.MemorySpace.VMEM((bq, D), q.dtype),
            *seg_tiles,
            pltpu.SemaphoreType.DMA((1,)),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.REGULAR((2,)),    # per-slot "free" acks
        ],
        compiler_params=pltpu.CompilerParams(collective_id=RING_ATTENTION_COLLECTIVE_ID),
        interpret=interpret if interpret is not None else default_interpret(),
    )(*operands)
    return out.reshape(B, H, Tl, D), lse.reshape(B, H, Tl, _STAT_LANES)


def _ring_bwd_kernel(
    my_ref, q_hbm, k_hbm, v_hbm, do_hbm, lse_hbm, delta_hbm, *rest,
    n: int, axis_name: str, causal: bool, scale: float,
    n_rep: int, bq: int, bk: int, window: int, has_seg: bool, H: int,
    slab: int,
):
    """Ring-attention backward as one remote-DMA ring pass per device.

    The rotating payload is (k, v, dk_acc, dv_acc): each KV shard carries its
    f32 dk/dv partial sums around the ring, every device adds its local
    q-block contributions (recomputing p blockwise from q, k, lse — the
    flash-backward trade), dq accumulates locally in HBM, and after the last
    compute step ONE extra rotation delivers each shard's finished dk/dv to
    its home device's output refs. KV shards wholly in this device's causal
    future skip compute (their accumulators still ride the ring).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if has_seg:
        segq_hbm, segk_hbm = rest[0], rest[1]
        (dq_hbm, dk_hbm, dv_hbm,
         kbuf, vbuf, dkbuf, dvbuf,
         qt, kt, vt, dot, lset, deltat, dqt, dks, dvs, segqt, segkt,
         csem, send_sem, recv_sem, ready_sem, fin_sem_s, fin_sem_r) = rest[2:]
    else:
        segq_hbm = segk_hbm = segqt = segkt = None
        (dq_hbm, dk_hbm, dv_hbm,
         kbuf, vbuf, dkbuf, dvbuf,
         qt, kt, vt, dot, lset, deltat, dqt, dks, dvs,
         csem, send_sem, recv_sem, ready_sem, fin_sem_s, fin_sem_r) = rest

    BH, Tl, D = q_hbm.shape
    BHkv = k_hbm.shape[0]
    my = my_ref[0]
    right = jax.lax.rem(my + 1, n)
    left = jax.lax.rem(my + n - 1, n)
    num_qb, num_kb = Tl // bq, Tl // bk

    def copy(src, dst):
        cp = pltpu.make_async_copy(src, dst, csem.at[0])
        cp.start()
        cp.wait()

    barrier = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(
        barrier, inc=1, device_id={axis_name: left},
        device_id_type=pltpu.DeviceIdType.MESH,
    )
    pltpu.semaphore_signal(
        barrier, inc=1, device_id={axis_name: right},
        device_id_type=pltpu.DeviceIdType.MESH,
    )
    pltpu.semaphore_wait(barrier, 2)

    # zero the local dq accumulator
    dqt[:] = jnp.zeros_like(dqt)

    def zero_dq(i, _):
        copy(dqt, dq_hbm.at[i // num_qb, pl.ds((i % num_qb) * bq, bq)])
        return 0

    jax.lax.fori_loop(0, BH * num_qb, zero_dq, 0)

    # stage the local KV shard into ring slot 0; its dk/dv start at zero
    copy(k_hbm, kbuf.at[0])
    copy(v_hbm, vbuf.at[0])
    dks[:] = jnp.zeros_like(dks)
    dvs[:] = jnp.zeros_like(dvs)
    n_sl = Tl // slab

    def zero_dkv(i, _):
        copy(dks, dkbuf.at[0, i // n_sl, pl.ds((i % n_sl) * slab, slab)])
        copy(dvs, dvbuf.at[0, i // n_sl, pl.ds((i % n_sl) * slab, slab)])
        return 0

    jax.lax.fori_loop(0, BHkv * n_sl, zero_dkv, 0)

    for s in range(n):
        cur, nxt = s % 2, (s + 1) % 2
        src = jax.lax.rem(my - s + n, n)  # whose KV shard slot `cur` holds

        # kv is read-only: its RDMA can overlap this step's compute. dk/dv
        # must ship AFTER our contribution is added — started post-compute.
        if s < n - 1:
            if s > 0:
                pltpu.semaphore_wait(ready_sem.at[nxt], 1)
            rk = pltpu.make_async_remote_copy(
                src_ref=kbuf.at[cur], dst_ref=kbuf.at[nxt],
                send_sem=send_sem.at[cur, 0], recv_sem=recv_sem.at[nxt, 0],
                device_id={axis_name: right},
                device_id_type=pltpu.DeviceIdType.MESH,
            )
            rv = pltpu.make_async_remote_copy(
                src_ref=vbuf.at[cur], dst_ref=vbuf.at[nxt],
                send_sem=send_sem.at[cur, 1], recv_sem=recv_sem.at[nxt, 1],
                device_id={axis_name: right},
                device_id_type=pltpu.DeviceIdType.MESH,
            )
            rk.start()
            rv.start()

        num_slabs = Tl // slab
        kb_per_slab = slab // bk

        def slab_body(bh, sl):
            # a SLAB of the riding dk/dv accumulators lives in VMEM
            # (dks/dvs scratch, size bounded by the slab — NOT by Tl, so
            # long shards can't blow the VMEM budget): inner tiles
            # accumulate with ZERO HBM read-modify-writes; dq is
            # loaded/stored once per (q tile, slab) instead of once per
            # (q tile × kv tile) — the r2 "serial dq RMW"
            s_lo = sl * slab
            copy(dkbuf.at[cur, bh, pl.ds(s_lo, slab)], dks)
            copy(dvbuf.at[cur, bh, pl.ds(s_lo, slab)], dvs)

            def qb_body(g, qb):
                qh = bh * n_rep + g
                q0 = my * Tl + qb * bq
                # whole-q-tile skip: nothing in this slab is visible to it
                q_ok = jnp.bool_(True)
                if causal:
                    q_ok = jnp.logical_and(q_ok, src * Tl + s_lo <= q0 + bq - 1)
                if window > 0:
                    q_ok = jnp.logical_and(
                        q_ok, src * Tl + s_lo + slab - 1 >= q0 - window + 1
                    )

                @pl.when(q_ok)
                def _qtile():
                    copy(q_hbm.at[qh, pl.ds(qb * bq, bq)], qt)
                    copy(do_hbm.at[qh, pl.ds(qb * bq, bq)], dot)
                    copy(lse_hbm.at[qh, pl.ds(qb * bq, bq)], lset)
                    copy(delta_hbm.at[qh, pl.ds(qb * bq, bq)], deltat)
                    copy(dq_hbm.at[qh, pl.ds(qb * bq, bq)], dqt)
                    if has_seg:
                        copy(segq_hbm.at[qh // H, pl.ds(qb * bq, bq)], segqt)
                    qv = qt[:].astype(jnp.float32)
                    dov = dot[:].astype(jnp.float32)

                    def kb_body(kb, _):
                        k0 = src * Tl + s_lo + kb * bk
                        ok = jnp.bool_(True)
                        if causal:
                            ok = jnp.logical_and(ok, k0 <= q0 + bq - 1)
                        if window > 0:
                            ok = jnp.logical_and(ok, k0 + bk - 1 >= q0 - window + 1)

                        @pl.when(ok)
                        def _tile():
                            copy(kbuf.at[cur, bh, pl.ds(s_lo + kb * bk, bk)], kt)
                            copy(vbuf.at[cur, bh, pl.ds(s_lo + kb * bk, bk)], vt)
                            if has_seg:
                                copy(
                                    segk_hbm.at[
                                        bh // (BHkv * H // BH), :,
                                        pl.ds(src * Tl + s_lo + kb * bk, bk),
                                    ],
                                    segkt,
                                )
                            kv = kt[:].astype(jnp.float32)
                            vv = vt[:].astype(jnp.float32)
                            k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
                            s_blk = scale * jax.lax.dot_general(
                                qv, kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                            )
                            if causal or window > 0:
                                q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
                                keep = jnp.bool_(True)
                                if causal:
                                    keep = jnp.logical_and(keep, q_pos >= k_pos)
                                if window > 0:
                                    keep = jnp.logical_and(keep, k_pos > q_pos - window)
                                s_blk = jnp.where(keep, s_blk, NEG_INF)
                            if has_seg:
                                s_blk = jnp.where(
                                    segqt[:][:, :1] == segkt[:][:1, :], s_blk, NEG_INF
                                )
                            p = jnp.exp(s_blk - lset[:][:, :1])
                            dp = jax.lax.dot_general(
                                dov, vv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                            )
                            ds = p * (dp - deltat[:][:, :1])
                            dvs[pl.ds(kb * bk, bk)] += jax.lax.dot_general(  # p^T @ do
                                p, dov, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32,
                            )
                            dks[pl.ds(kb * bk, bk)] += scale * jax.lax.dot_general(
                                ds, qv, (((0,), (0,)), ((), ())),            # ds^T @ q
                                preferred_element_type=jnp.float32,
                            )
                            dqt[:] += scale * jax.lax.dot_general(           # ds @ k
                                ds, kv, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32,
                            )

                        return 0

                    jax.lax.fori_loop(0, kb_per_slab, kb_body, 0)
                    copy(dqt, dq_hbm.at[qh, pl.ds(qb * bq, bq)])

                return 0

            jax.lax.fori_loop(
                0, n_rep * num_qb,
                lambda i, _: (qb_body(i // num_qb, i % num_qb), 0)[1], 0,
            )
            copy(dks, dkbuf.at[cur, bh, pl.ds(s_lo, slab)])
            copy(dvs, dvbuf.at[cur, bh, pl.ds(s_lo, slab)])
            return 0

        def run_kb_loop():
            jax.lax.fori_loop(
                0, BHkv * num_slabs,
                lambda i, _: (slab_body(i // num_slabs, i % num_slabs), 0)[1], 0,
            )

        if causal and s > 0:
            # whole shard in this device's causal future ⇒ nothing to add
            # (the accumulators still ride the ring untouched); with a
            # window also skip shards wholly below the band
            needed = src <= my
            if window > 0:
                needed = jnp.logical_and(
                    needed, src * Tl + Tl - 1 >= my * Tl - window + 1
                )
            pl.when(needed)(run_kb_loop)
        else:
            run_kb_loop()

        if s < n - 1:
            # ship the updated dk/dv accumulators after compute
            rdk = pltpu.make_async_remote_copy(
                src_ref=dkbuf.at[cur], dst_ref=dkbuf.at[nxt],
                send_sem=send_sem.at[cur, 2], recv_sem=recv_sem.at[nxt, 2],
                device_id={axis_name: right},
                device_id_type=pltpu.DeviceIdType.MESH,
            )
            rdv = pltpu.make_async_remote_copy(
                src_ref=dvbuf.at[cur], dst_ref=dvbuf.at[nxt],
                send_sem=send_sem.at[cur, 3], recv_sem=recv_sem.at[nxt, 3],
                device_id={axis_name: right},
                device_id_type=pltpu.DeviceIdType.MESH,
            )
            rdk.start()
            rdv.start()
            rk.wait()
            rv.wait()
            rdk.wait()
            rdv.wait()
            pltpu.semaphore_signal(
                ready_sem.at[cur], inc=1, device_id={axis_name: left},
                device_id_type=pltpu.DeviceIdType.MESH,
            )

    if n > 1:
        # drain the right neighbor's final free-signal (same reason as the
        # forward kernel: zero semaphores at exit)
        pltpu.semaphore_wait(ready_sem.at[(n - 2) % 2], 1)

    # final rotation: shard my+1's finished dk/dv sits in our last slot —
    # deliver it straight into the right neighbor's output refs
    last = (n - 1) % 2
    fdk = pltpu.make_async_remote_copy(
        src_ref=dkbuf.at[last], dst_ref=dk_hbm,
        send_sem=fin_sem_s.at[0], recv_sem=fin_sem_r.at[0],
        device_id={axis_name: right},
        device_id_type=pltpu.DeviceIdType.MESH,
    )
    fdv = pltpu.make_async_remote_copy(
        src_ref=dvbuf.at[last], dst_ref=dv_hbm,
        send_sem=fin_sem_s.at[1], recv_sem=fin_sem_r.at[1],
        device_id={axis_name: right},
        device_id_type=pltpu.DeviceIdType.MESH,
    )
    fdk.start()
    fdv.start()
    fdk.wait()
    fdv.wait()


def _ring_bwd(q, k, v, o, lse, do, axis_name: str, causal: bool, interpret: Any,
              window: int = 0, segment_ids=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Tl, D = q.shape
    Hkv = k.shape[1]
    n_rep = H // Hkv
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    scale = D ** -0.5
    bq = _pick_block(Tl, _RING_BQ)
    bk = _pick_block(Tl, _RING_BK)
    has_seg = segment_ids is not None
    qf = q.reshape(B * H, Tl, D)
    kf = k.reshape(B * Hkv, Tl, D)
    vf = v.reshape(B * Hkv, Tl, D)
    dof = do.reshape(B * H, Tl, D)
    lsef = lse.reshape(B * H, Tl, _STAT_LANES)
    delta = jnp.sum(
        dof.astype(jnp.float32) * o.reshape(B * H, Tl, D).astype(jnp.float32), axis=-1
    )
    delta = jnp.broadcast_to(delta[:, :, None], (B * H, Tl, _STAT_LANES))

    # slab: largest bk-multiple divisor of Tl within a ~4 MB f32 budget —
    # the VMEM accumulator footprint is bounded by the slab, not by Tl
    budget_rows = max(bk, (4 * 2 ** 20) // (D * 4) // bk * bk)
    slab = bk
    for s_cand in range(min(Tl, budget_rows), bk - 1, -bk):
        if Tl % s_cand == 0:
            slab = s_cand
            break
    kernel = functools.partial(
        _ring_bwd_kernel, n=n, axis_name=axis_name, causal=causal, scale=scale,
        n_rep=n_rep, bq=bq, bk=bk, window=window, has_seg=has_seg, H=H,
        slab=slab,
    )
    hbm = pltpu.MemorySpace.HBM
    operands = [jnp.full((1,), my, jnp.int32), qf, kf, vf, dof, lsef, delta]
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM),
        pl.BlockSpec(memory_space=hbm),
        pl.BlockSpec(memory_space=hbm),
        pl.BlockSpec(memory_space=hbm),
        pl.BlockSpec(memory_space=hbm),
        pl.BlockSpec(memory_space=hbm),
        pl.BlockSpec(memory_space=hbm),
    ]
    seg_tiles = []
    if has_seg:
        segq, segk = _seg_layouts(segment_ids, axis_name)
        operands += [segq, segk]
        in_specs += [pl.BlockSpec(memory_space=hbm), pl.BlockSpec(memory_space=hbm)]
        seg_tiles = [
            pltpu.MemorySpace.VMEM((bq, _STAT_LANES), jnp.float32),
            pltpu.MemorySpace.VMEM((_STAT_LANES, bk), jnp.float32),
        ]
    dq, dk, dv = pl.pallas_call(
        kernel,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec(memory_space=hbm),
            pl.BlockSpec(memory_space=hbm),
            pl.BlockSpec(memory_space=hbm),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tl, D), jnp.float32),
            jax.ShapeDtypeStruct((B * Hkv, Tl, D), jnp.float32),
            jax.ShapeDtypeStruct((B * Hkv, Tl, D), jnp.float32),
        ],
        scratch_shapes=[
            hbm((2, B * Hkv, Tl, D), k.dtype),     # ring KV slots
            hbm((2, B * Hkv, Tl, D), v.dtype),
            hbm((2, B * Hkv, Tl, D), jnp.float32),  # riding dk/dv accumulators
            hbm((2, B * Hkv, Tl, D), jnp.float32),
            pltpu.MemorySpace.VMEM((bq, D), q.dtype),      # tiles
            pltpu.MemorySpace.VMEM((bk, D), k.dtype),
            pltpu.MemorySpace.VMEM((bk, D), v.dtype),
            pltpu.MemorySpace.VMEM((bq, D), do.dtype),
            pltpu.MemorySpace.VMEM((bq, _STAT_LANES), jnp.float32),
            pltpu.MemorySpace.VMEM((bq, _STAT_LANES), jnp.float32),
            pltpu.MemorySpace.VMEM((bq, D), jnp.float32),
            pltpu.MemorySpace.VMEM((slab, D), jnp.float32),  # slab dk acc
            pltpu.MemorySpace.VMEM((slab, D), jnp.float32),  # slab dv acc
            *seg_tiles,
            pltpu.SemaphoreType.DMA((1,)),
            pltpu.SemaphoreType.DMA((2, 4)),
            pltpu.SemaphoreType.DMA((2, 4)),
            pltpu.SemaphoreType.REGULAR((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(collective_id=RING_ATTENTION_BWD_COLLECTIVE_ID),
        interpret=interpret if interpret is not None else default_interpret(),
    )(*operands)
    return (
        dq.reshape(B, H, Tl, D).astype(q.dtype),
        dk.reshape(B, Hkv, Tl, D).astype(k.dtype),
        dv.reshape(B, Hkv, Tl, D).astype(v.dtype),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def ring_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "context",
    causal: bool = True,
    interpret: Any = None,
    window: int = 0,
) -> jax.Array:
    """Ring attention with the KV rotation as in-kernel remote DMA.

    Must run inside ``shard_map`` with the sequence dim sharded over
    ``axis_name``; per-shard shapes q [B, H, T_local, D], k/v
    [B, Hkv, T_local, D] with H % Hkv == 0 (GQA stays at Hkv width on the
    wire). ``interpret`` accepts ``pltpu.InterpretParams`` for the
    emulated-RDMA CPU path; None defers to ``TONY_PALLAS_INTERPRET``.
    ``window`` > 0 adds the sliding-window band: below-band KV tiles (and
    whole shards) are skipped — no DMA, no grid steps — in fwd AND bwd.

    Block sizes adapt to the per-device sequence (largest ≤256 divisor
    that's a lane multiple), so short shards no longer hard-error.

    Trainable end-to-end in-kernel: the backward is its own remote-DMA ring
    kernel (``_ring_bwd_kernel``) — dk/dv accumulators ride the ring WITH
    their KV shard and a final rotation returns them home. Packed batches
    use ``ring_attention_pallas_seg``.
    """
    return _ring_fwd(q, k, v, axis_name, causal, interpret, window)[0]


def _ring_vjp_fwd(q, k, v, axis_name, causal, interpret, window):
    o, lse = _ring_fwd(q, k, v, axis_name, causal, interpret, window)
    return o, (q, k, v, o, lse)


def _ring_vjp_bwd(axis_name, causal, interpret, window, res, g):
    q, k, v, o, lse = res
    return _ring_bwd(q, k, v, o, lse, g, axis_name, causal, interpret, window)


ring_attention_pallas.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def ring_attention_pallas_seg(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    segment_ids: jax.Array,
    axis_name: str = "context",
    causal: bool = True,
    interpret: Any = None,
    window: int = 0,
) -> jax.Array:
    """Packed-sequence ring attention: ``segment_ids`` is the PER-DEVICE
    [B, T_local] slice of the packed layout (data.pack_sequences ids are
    global per row, so shard-local slices stay globally consistent); the
    kernel all-gathers the tiny id table over the ring axis and confines
    attention within segments on every shard's tiles. Composes with
    ``window`` and GQA; seg cotangent is float0.
    """
    return _ring_fwd(q, k, v, axis_name, causal, interpret, window, segment_ids)[0]


def _ring_seg_vjp_fwd(q, k, v, seg, axis_name, causal, interpret, window):
    o, lse = _ring_fwd(q, k, v, axis_name, causal, interpret, window, seg)
    return o, (q, k, v, seg, o, lse)


def _ring_seg_vjp_bwd(axis_name, causal, interpret, window, res, g):
    import numpy as np

    q, k, v, seg, o, lse = res
    dq, dk, dv = _ring_bwd(
        q, k, v, o, lse, g, axis_name, causal, interpret, window, seg
    )
    return dq, dk, dv, np.zeros(seg.shape, jax.dtypes.float0)


ring_attention_pallas_seg.defvjp(_ring_seg_vjp_fwd, _ring_seg_vjp_bwd)
