"""Pipeline parallelism over the ``stage`` mesh axis, inside shard_map with
``ppermute`` activation hand-off.

Absent from the reference (SURVEY.md §2.5). Two schedules:

- ``spmd_pipeline_1f1b`` — the PRODUCTION path (all training flows route
  here via make_pp_train_step): hand-scheduled one-forward-one-backward
  with O(S) live activations, owning-stage-gated embed/head units, sharded
  microbatch batch dim, bf16 wire.
- ``spmd_pipeline`` — the TEACHING/REFERENCE schedule: GPipe forward under
  ordinary autodiff. Kept because its 40 lines + jax.grad make it the
  verifiable spec the 1F1B parity tests lean on, and the shape every
  pipelining tutorial starts from. Known teaching-path costs, by design:
  the output bank psum-broadcasts to every stage, microbatches enter
  replicated (no DP composition), and the wire must widen to f32 off-TPU.
  Don't train real models with it.

The schedule is SPMD: every stage runs the same program; on tick t, stage s
computes microbatch ``t - s`` (when valid) and ships its activation to stage
``s+1`` over the ring — a bubble of ``S - 1`` ticks at the start/end,
amortized by the microbatch count M.

``spmd_pipeline`` is model-agnostic: ``stage_fn(stage_params, x) -> x`` is
one stage's compute, stage params are leaves with a leading ``[S, ...]`` dim
(sharded over 'stage'), and the input is pre-split into M microbatches.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _pipeline_body(
    stage_params: Any,
    microbatches: jax.Array,
    *,
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    axis_name: str,
    compute_dtype,
) -> jax.Array:
    """Runs inside shard_map: stage_params are stage-local (leading dim 1),
    microbatches [M, B, ...] are replicated along the stage axis.

    ``microbatches`` arrive (and all cross-stage traffic travels) in the
    caller's wire dtype — f32 by default, because bf16 through the backward
    of the replicated input's transpose-psum / ppermute trips an XLA-CPU
    compiler CHECK (AllReducePromotion "Invalid binary instruction opcode
    copy"), and f32 hand-off is numerically lossless between stages.
    Compute inside each stage runs in ``compute_dtype``.
    """
    S = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    M = microbatches.shape[0]
    mb_shape = microbatches.shape[1:]
    wire_dtype = microbatches.dtype
    local_params = jax.tree.map(lambda p: p[0], stage_params)

    def tick(carry, t):
        state, outputs = carry
        # stage 0 ingests microbatch t (clamped); others take the ring input
        feed = microbatches[jnp.minimum(t, M - 1)]
        x = jnp.where(idx == 0, feed, state)
        y = stage_fn(local_params, x.astype(compute_dtype)).astype(wire_dtype)
        # the last stage banks its finished microbatch (valid when t >= S-1)
        out_idx = t - (S - 1)
        valid = jnp.logical_and(idx == S - 1, out_idx >= 0)
        outputs = jax.lax.cond(
            valid,
            lambda o: jax.lax.dynamic_update_index_in_dim(o, y, jnp.maximum(out_idx, 0), 0),
            lambda o: o,
            outputs,
        )
        # ring hand-off to the next stage (stage S-1 → 0 wraps; ignored there)
        state = jax.lax.ppermute(y, axis_name, [(i, (i + 1) % S) for i in range(S)])
        return (state, outputs), None

    state0 = jnp.zeros(mb_shape, wire_dtype)
    outputs0 = jnp.zeros((M, *mb_shape), wire_dtype)
    (_, outputs), _ = jax.lax.scan(tick, (state0, outputs0), jnp.arange(M + S - 1))
    # outputs live on the last stage only; make them uniform across the axis
    mask = (idx == S - 1).astype(wire_dtype)
    summed = jax.lax.psum(outputs * mask, axis_name)
    return summed.astype(compute_dtype)


def spmd_pipeline(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x: jax.Array,
    *,
    mesh: Mesh,
    num_microbatches: int,
    axis_name: str = "stage",
    wire_dtype=jnp.float32,
) -> jax.Array:
    """Apply an S-stage pipeline to a batch.

    - ``stage_params``: pytree, every leaf ``[S, ...]``, sharded P('stage', ...)
    - ``x``: [B, ...] batch; B % num_microbatches == 0
    - returns [B, ...] as if ``fn = stage_S-1 ∘ ... ∘ stage_0`` ran whole.
    """
    B = x.shape[0]
    M = num_microbatches
    if B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    compute_dtype = x.dtype
    mesh_platform = next(iter(mesh.devices.flat)).platform
    if jnp.dtype(wire_dtype).itemsize < 4 and mesh_platform == "cpu":
        raise ValueError(
            f"wire_dtype {jnp.dtype(wire_dtype).name} would go through bf16 "
            "collective backward on the CPU backend, which trips an XLA "
            "compiler CHECK — use float32 (narrow wire is a TPU-only option)"
        )
    # wire dtype applies from the shard_map boundary in: the replicated
    # input's backward is itself a stage-axis psum (see _pipeline_body)
    mb = x.astype(wire_dtype).reshape(M, B // M, *x.shape[1:])

    param_specs = jax.tree.map(lambda p: P(axis_name, *([None] * (p.ndim - 1))), stage_params)
    body = jax.shard_map(
        partial(_pipeline_body, stage_fn=stage_fn, axis_name=axis_name, compute_dtype=compute_dtype),
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        axis_names={axis_name},
        check_vma=False,
    )
    out = body(stage_params, mb)
    return out.reshape(B, *out.shape[2:])


def _add_trees(a, b):
    return jax.tree.map(jnp.add, a, b)


def _f32_zeros_like(tree):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), tree)


def spmd_pipeline_1f1b(
    stage_fn: Callable[..., Any],
    stage_params: Any,
    batch: Any,
    embed_params: Any,
    head_params: Any,
    embed_fn: Callable[[Any, Any], jax.Array],
    loss_head_fn: Callable[[Any, jax.Array, Any], tuple[jax.Array, jax.Array]],
    *,
    mesh: Mesh,
    num_microbatches: int,
    axis_name: str = "stage",
    batch_axes: tuple[str, ...] = ("data", "fsdp"),
    wire_dtype=jnp.bfloat16,
    compute_dtype=jnp.bfloat16,
    stage_has_aux: bool = False,
    aux_seed_scale: jax.Array | float = 1.0,
) -> tuple[jax.Array, jax.Array, jax.Array, tuple[Any, Any, Any]]:
    """One-forward-one-backward (1F1B) pipeline **train step core**: returns
    ``(nll_sum, n_tokens, aux_total, (d_stage, d_embed, d_head_params))``.

    Unlike the GPipe path (``spmd_pipeline`` + autodiff), the backward is
    hand-scheduled INSIDE the same tick loop: on tick t, stage s runs the
    forward of microbatch ``t - s`` and the backward of microbatch
    ``t - 2S + 1 + s``, with activations travelling the stage ring forward
    and gradients travelling it backward. Consequences:

    - peak live activations per stage are bounded by the residual buffer
      (2S + 1 microbatch inputs) instead of GPipe's M — the win when M ≫ S;
    - the loss head runs *inside* the last stage's tick (no [M, …] output
      bank psum-broadcast to every stage);
    - no autodiff ever touches a collective, so the bf16 wire works on every
      backend (the GPipe path must widen to f32 off-TPU);
    - the microbatch batch dim composes with data/fsdp sharding: the batch
      is sharded over ``batch_axes`` and every gradient is psum-reduced over
      them before leaving the shard_map;
    - embed forward/VJP, loss-head value+grad, and the whole backward unit
      sit behind ``lax.cond`` on the OWNING stage (and tick validity), so a
      non-owning stage pays none of their FLOPs — inside shard_map's manual
      SPMD, cond lowers to a real per-device branch, not a select. The
      conds contain no collectives (the rings run unconditionally every
      tick), so divergent predicates cannot deadlock.

    Contract: ``batch`` is a pytree of [B, ...] arrays (tokens, optional
    segment_ids, ...), microbatched internally to [M, B/M, ...];
    ``stage_fn(stage_local_params, x, mb) -> y`` — or ``(y, aux_scalar)``
    with ``stage_has_aux=True`` (MoE balance/z losses); the aux convention
    is ``aux_total = (1/M)·Σ_mb Σ_stages aux`` with matching cotangent seed,
    i.e. aux is averaged over microbatches (and over batch shards — for
    non-linear aux like MoE balance this is the standard per-group
    approximation of the full-batch statistic).
    ``embed_fn(embed_params, mb) -> x0``; ``loss_head_fn(head_params,
    y_last, mb) -> (nll_sum, n_valid_tokens)``. Losses are summed, NOT
    token-normalized — divide grads by ``n_tokens`` for a mean-loss step.

    ``aux_seed_scale``: the returned grads differentiate
    ``nll_sum + aux_seed_scale · aux_total``. A caller that divides all
    grads by ``n_tokens`` afterwards (the mean-loss recipe above) should
    pass its (pre-computable) token count here so the aux contribution
    survives the division at unit scale — see mixtral.pp_value_and_grad.
    """
    S = mesh.shape[axis_name]
    M = num_microbatches
    B = jax.tree.leaves(batch)[0].shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    present = tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1)
    n_bshards = 1
    for a in present:
        n_bshards *= mesh.shape[a]
    batch_mb = jax.tree.map(lambda a: a.reshape(M, B // M, *a.shape[1:]), batch)
    aux_scale = 1.0 / (M * n_bshards)

    def fwd_only(lp, x, mb):
        y = stage_fn(lp, x, mb)
        return y[0] if stage_has_aux else y

    def body(stage_p, embed_p, head_p, mbs):
        idx = jax.lax.axis_index(axis_name)
        local_params = jax.tree.map(lambda p: p[0], stage_p)
        mb0 = jax.tree.map(lambda a: a[0], mbs)
        x_probe = jax.eval_shape(embed_fn, embed_p, mb0)
        mb_shape = x_probe.shape  # [b, Tin, D]
        BUF = 2 * S + 1  # last slot is the trash slot for invalid writes

        def head_value_grads(hp, y, mb):
            def f(hp, y):
                nll, n = loss_head_fn(hp, y, mb)
                return nll, n

            (nll, n), (dhp, dy) = jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(hp, y)
            return nll, n.astype(jnp.float32), dhp, dy

        def tick(carry, t):
            fwd_in, bwd_in, resid, dstage, dembed, dhead, nll_acc, ntok_acc, aux_acc = carry
            last = idx == S - 1
            first = idx == 0

            # ---- forward unit: microbatch mf enters this stage
            mf = t - idx
            fwd_valid = jnp.logical_and(mf >= 0, mf < M)
            mb_f = jax.tree.map(lambda a: a[jnp.clip(mf, 0, M - 1)], mbs)
            # only stage 0 embeds; the rest take the ring input
            x = jax.lax.cond(
                first,
                lambda: embed_fn(embed_p, mb_f).astype(compute_dtype),
                lambda: fwd_in.astype(compute_dtype),
            )
            # bubble ticks (invalid mf) skip the stage compute entirely
            y = jax.lax.cond(
                fwd_valid,
                lambda: fwd_only(local_params, x, mb_f).astype(compute_dtype),
                lambda: jnp.zeros(mb_shape, compute_dtype),
            )
            slot_w = jnp.where(fwd_valid, mf % (2 * S), 2 * S)
            resid = jax.lax.dynamic_update_index_in_dim(resid, x, slot_w, 0)

            # ---- backward unit: microbatch mb leaves this stage
            mb = t - 2 * S + 1 + idx
            bwd_valid = jnp.logical_and(mb >= 0, mb < M)
            mb_b = jax.tree.map(lambda a: a[jnp.clip(mb, 0, M - 1)], mbs)

            def bwd_compute():
                slot_r = jnp.where(bwd_valid, mb % (2 * S), 2 * S)
                x_res = jax.lax.dynamic_index_in_dim(resid, slot_r, 0, keepdims=False)
                if stage_has_aux:
                    (y_res, aux_res), stage_vjp = jax.vjp(
                        lambda lp, x: stage_fn(lp, x, mb_b), local_params, x_res
                    )
                else:
                    y_res, stage_vjp = jax.vjp(
                        lambda lp, x: stage_fn(lp, x, mb_b), local_params, x_res
                    )
                    aux_res = jnp.zeros((), jnp.float32)
                # loss head: last stage only
                nll, n, dhp, dy = jax.lax.cond(
                    last,
                    lambda: head_value_grads(head_p, y_res, mb_b),
                    lambda: (
                        jnp.zeros((), jnp.float32),
                        jnp.zeros((), jnp.float32),
                        jax.tree.map(jnp.zeros_like, head_p),
                        jnp.zeros_like(y_res),
                    ),
                )
                g = jnp.where(last, dy.astype(wire_dtype), bwd_in).astype(y_res.dtype)
                if stage_has_aux:
                    dp_m, dx_m = stage_vjp((g, jnp.asarray(aux_scale * aux_seed_scale, jnp.float32)))
                else:
                    dp_m, dx_m = stage_vjp(g)
                # embed VJP: stage 0 only (in-tick scatter-add into the
                # running accumulator — no [M, …] bank, which would
                # reinstate the O(M) memory 1F1B avoids)
                dE_m = jax.lax.cond(
                    first,
                    lambda: jax.vjp(lambda ep: embed_fn(ep, mb_b), embed_p)[1](
                        dx_m.astype(x_probe.dtype)
                    )[0],
                    lambda: jax.tree.map(jnp.zeros_like, embed_p),
                )
                return nll, n, dp_m, dx_m, dhp, dE_m, aux_res * aux_scale

            def bwd_skip():
                return (
                    jnp.zeros((), jnp.float32),
                    jnp.zeros((), jnp.float32),
                    jax.tree.map(jnp.zeros_like, local_params),
                    jnp.zeros(mb_shape, compute_dtype),
                    jax.tree.map(jnp.zeros_like, head_p),
                    jax.tree.map(jnp.zeros_like, embed_p),
                    jnp.zeros((), jnp.float32),
                )

            nll, n, dp_m, dx_m, dhp, dE_m, aux_mb = jax.lax.cond(
                bwd_valid, bwd_compute, bwd_skip
            )

            dstage = _add_trees(dstage, dp_m)
            dhead = _add_trees(dhead, dhp)
            dembed = _add_trees(
                dembed, jax.tree.map(lambda a: a.astype(jnp.float32), dE_m)
            )
            nll_acc = nll_acc + nll
            ntok_acc = ntok_acc + n
            aux_acc = aux_acc + aux_mb

            # ---- rings: activations forward, gradients backward
            fwd_out = jax.lax.ppermute(
                y.astype(wire_dtype), axis_name, [(i, (i + 1) % S) for i in range(S)]
            )
            bwd_out = jax.lax.ppermute(
                dx_m.astype(wire_dtype), axis_name, [(i, (i - 1) % S) for i in range(S)]
            )
            return (
                fwd_out, bwd_out, resid, dstage, dembed, dhead, nll_acc, ntok_acc, aux_acc,
            ), None

        carry0 = (
            jnp.zeros(mb_shape, wire_dtype),
            jnp.zeros(mb_shape, wire_dtype),
            jnp.zeros((BUF, *mb_shape), compute_dtype),
            _f32_zeros_like(local_params),
            _f32_zeros_like(embed_p),
            _f32_zeros_like(head_p),
            jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.float32),
        )
        (_, _, _, dstage, dembed, dhead, nll, ntok, aux), _ = jax.lax.scan(
            tick, carry0, jnp.arange(M + 2 * S - 1)
        )

        # reduce: batch shards partial-sum everything; the stage axis
        # all-reduces the per-stage-owned pieces (zeros elsewhere)
        axes_all = (axis_name, *present)
        nll = jax.lax.psum(nll, axes_all)
        ntok = jax.lax.psum(ntok, axes_all)
        aux = jax.lax.psum(aux, axes_all)
        dembed = jax.tree.map(lambda a: jax.lax.psum(a, axes_all), dembed)
        dhead = jax.tree.map(lambda a: jax.lax.psum(a, axes_all), dhead)
        if present:
            dstage = jax.tree.map(lambda a: jax.lax.psum(a, present), dstage)
        dstage = jax.tree.map(lambda a: a[None], dstage)  # local [1, ...] → P(stage)
        return nll, ntok, aux, dstage, dembed, dhead

    param_specs = jax.tree.map(lambda p: P(axis_name, *([None] * (p.ndim - 1))), stage_params)
    rep = jax.tree.map(lambda p: P(), embed_params)
    rep_head = jax.tree.map(lambda p: P(), head_params)
    mb_specs = jax.tree.map(
        lambda a: P(None, present or None, *([None] * (a.ndim - 2))), batch_mb
    )
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(param_specs, rep, rep_head, mb_specs),
        out_specs=(P(), P(), P(), param_specs, rep, rep_head),
        axis_names={axis_name, *present},
        check_vma=False,
    )
    nll, ntok, aux, dstage, dembed, dhead = fn(stage_params, embed_params, head_params, batch_mb)
    return nll, ntok, aux, (dstage, dembed, dhead)


def spmd_pipeline_1f1b_interleaved(
    stage_fn: Callable[..., Any],
    chunk_params: Any,
    batch: Any,
    embed_params: Any,
    head_params: Any,
    embed_fn: Callable[[Any, Any], jax.Array],
    loss_head_fn: Callable[[Any, jax.Array, Any], tuple[jax.Array, jax.Array]],
    *,
    mesh: Mesh,
    num_microbatches: int,
    num_chunks: int,
    axis_name: str = "stage",
    batch_axes: tuple[str, ...] = ("data", "fsdp"),
    wire_dtype=jnp.bfloat16,
    compute_dtype=jnp.bfloat16,
) -> tuple[jax.Array, jax.Array, tuple[Any, Any, Any]]:
    """INTERLEAVED 1F1B (virtual pipeline stages): every device owns
    ``num_chunks`` (V) model chunks; global stage ``g = v·S + s`` so a
    microbatch visits each device V times. Bubble shrinks from
    ``(2S−1)`` stage-units to ``≈(2S−1)/V`` (the classic interleaved
    trade: V× more live activations per device, V× less bubble).

    Schedule (lockstep SPMD, chunk-sized ticks; m in groups of S):

    - fwd of (m, v) on device s at ``t = s + (m//S)·VS + v·S + (m%S)``
    - bwd of (m, v) on device s at
      ``t = VS + (V−1−v)·S + (S−1−s) + (m//S)·VS + (m%S)``

    Both recurrences advance exactly one tick per ring hop — including
    the device-(S−1)→0 wrap that carries chunk v's output into chunk
    v+1 — so ONE fwd ppermute and ONE bwd ppermute per tick move all V
    chunks' traffic (stacked on a leading V dim). Per (device, chunk,
    tick) there is at most one fwd and one bwd unit (mixed-radix
    bijection), and all expensive units sit behind ``lax.cond`` exactly
    like the non-interleaved schedule. Requires ``M % S == 0``.

    ``chunk_params``: pytree with leading ``[S, V, ...]`` dims (see
    ``split_layers_into_chunks``), sharded P(axis_name). Contract of
    ``stage_fn/embed_fn/loss_head_fn`` matches ``spmd_pipeline_1f1b``
    (no stage-aux support here yet). Returns
    ``(nll_sum, n_tokens, (d_chunk_params, d_embed, d_head))``.
    """
    S = mesh.shape[axis_name]
    V = num_chunks
    M = num_microbatches
    B = jax.tree.leaves(batch)[0].shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    if M % S:
        raise ValueError(
            f"interleaved 1F1B needs microbatches {M} % stages {S} == 0"
        )
    present = tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1)
    batch_mb = jax.tree.map(lambda a: a.reshape(M, B // M, *a.shape[1:]), batch)
    VS = V * S
    # total ticks: one past the last backward unit (m=M−1, v=0, s=0)
    T_TOT = VS + (V - 1) * S + (S - 1) + (M // S - 1) * VS + (S - 1) + 1
    # residual slots per chunk: an activation's worst-case lifetime is
    # 2VS-1 ticks, during which at most 2S-1 newer microbatches write the
    # same chunk's slots (m advances S per VS ticks) -> 2S+1 suffices,
    # the same geometry as the non-interleaved schedule
    RES = 2 * S + 1

    def body(chunk_p, embed_p, head_p, mbs):
        idx = jax.lax.axis_index(axis_name)
        local = jax.tree.map(lambda p: p[0], chunk_p)  # [V, ...] per leaf
        mb0 = jax.tree.map(lambda a: a[0], mbs)
        x_probe = jax.eval_shape(embed_fn, embed_p, mb0)
        mb_shape = x_probe.shape

        def head_value_grads(hp, y, mb):
            def f(hp, y):
                return loss_head_fn(hp, y, mb)

            (nll, n), (dhp, dy) = jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(hp, y)
            return nll, n.astype(jnp.float32), dhp, dy

        def unit_indices(t, v):
            """(fwd_valid, m_f, bwd_valid, m_b) for chunk v at tick t."""
            u_f = t - idx - v * S
            r_f = jax.lax.rem(u_f, VS)
            ok_f = jnp.logical_and(u_f >= 0, r_f < S)
            m_f = jax.lax.div(u_f, VS) * S + r_f
            ok_f = jnp.logical_and(ok_f, m_f < M)
            u_b = t - VS - (V - 1 - v) * S - (S - 1 - idx)
            r_b = jax.lax.rem(u_b, VS)
            ok_b = jnp.logical_and(u_b >= 0, r_b < S)
            m_b = jax.lax.div(u_b, VS) * S + r_b
            ok_b = jnp.logical_and(ok_b, m_b < M)
            return ok_f, jnp.clip(m_f, 0, M - 1), ok_b, jnp.clip(m_b, 0, M - 1)

        def tick(carry, t):
            fwd_in, bwd_in, resid, dchunk, dembed, dhead, nll_acc, ntok_acc = carry
            y_out = []
            dx_out = []
            for v in range(V):  # static unroll over this device's chunks
                first_g = jnp.logical_and(idx == 0, v == 0)
                last_g = jnp.logical_and(idx == S - 1, v == V - 1)
                ok_f, m_f, ok_b, m_b = unit_indices(t, v)
                lp = jax.tree.map(lambda p: p[v], local)

                # ---- forward unit of chunk v
                mb_f = jax.tree.map(lambda a: a[m_f], mbs)
                x = jax.lax.cond(
                    first_g,
                    lambda: embed_fn(embed_p, mb_f).astype(compute_dtype),
                    lambda: fwd_in[v].astype(compute_dtype),
                )
                y = jax.lax.cond(
                    ok_f,
                    lambda: stage_fn(lp, x, mb_f).astype(compute_dtype),
                    lambda: jnp.zeros(mb_shape, compute_dtype),
                )
                slot_w = jnp.where(ok_f, jax.lax.rem(m_f, RES), RES)
                resid = resid.at[v].set(
                    jax.lax.dynamic_update_index_in_dim(resid[v], x, slot_w, 0)
                )
                y_out.append(y)

                # ---- backward unit of chunk v
                mb_b = jax.tree.map(lambda a: a[m_b], mbs)

                def bwd_compute(v=v, lp=lp, m_b=m_b, ok_b=ok_b, mb_b=mb_b,
                                last_g=last_g, first_g=first_g):
                    slot_r = jnp.where(ok_b, jax.lax.rem(m_b, RES), RES)
                    x_res = jax.lax.dynamic_index_in_dim(
                        resid[v], slot_r, 0, keepdims=False
                    )
                    y_res, vjp = jax.vjp(lambda p, x: stage_fn(p, x, mb_b), lp, x_res)
                    nll, n, dhp, dy = jax.lax.cond(
                        last_g,
                        lambda: head_value_grads(head_p, y_res, mb_b),
                        lambda: (
                            jnp.zeros((), jnp.float32),
                            jnp.zeros((), jnp.float32),
                            jax.tree.map(jnp.zeros_like, head_p),
                            jnp.zeros_like(y_res),
                        ),
                    )
                    g = jnp.where(last_g, dy.astype(wire_dtype), bwd_in[v]).astype(
                        y_res.dtype
                    )
                    dp_m, dx_m = vjp(g)
                    dE_m = jax.lax.cond(
                        first_g,
                        lambda: jax.vjp(lambda ep: embed_fn(ep, mb_b), embed_p)[1](
                            dx_m.astype(x_probe.dtype)
                        )[0],
                        lambda: jax.tree.map(jnp.zeros_like, embed_p),
                    )
                    return nll, n, dp_m, dx_m, dhp, dE_m

                def bwd_skip():
                    return (
                        jnp.zeros((), jnp.float32),
                        jnp.zeros((), jnp.float32),
                        jax.tree.map(jnp.zeros_like, jax.tree.map(lambda p: p[v], local)),
                        jnp.zeros(mb_shape, compute_dtype),
                        jax.tree.map(jnp.zeros_like, head_p),
                        jax.tree.map(jnp.zeros_like, embed_p),
                    )

                nll, n, dp_m, dx_m, dhp, dE_m = jax.lax.cond(ok_b, bwd_compute, bwd_skip)
                dchunk = jax.tree.map(
                    lambda acc, g, vv=v: acc.at[vv].add(g), dchunk, dp_m
                )
                dhead = _add_trees(dhead, dhp)
                dembed = _add_trees(
                    dembed, jax.tree.map(lambda a: a.astype(jnp.float32), dE_m)
                )
                nll_acc = nll_acc + nll
                ntok_acc = ntok_acc + n
                dx_out.append(dx_m)

            y_all = jnp.stack([y.astype(wire_dtype) for y in y_out])     # [V, ...]
            dx_all = jnp.stack([d.astype(wire_dtype) for d in dx_out])
            fwd_out = jax.lax.ppermute(
                y_all, axis_name, [(i, (i + 1) % S) for i in range(S)]
            )
            # the wrap also advances the chunk: what device 0 receives for
            # "chunk v" left device S-1 as chunk v's output but must enter
            # chunk v+1 — roll the chunk dim on the wrap receiver only
            rolled = jnp.roll(fwd_out, 1, axis=0)
            fwd_out = jnp.where(idx == 0, rolled, fwd_out)
            bwd_out = jax.lax.ppermute(
                dx_all, axis_name, [(i, (i - 1) % S) for i in range(S)]
            )
            rolled_b = jnp.roll(bwd_out, -1, axis=0)
            bwd_out = jnp.where(idx == S - 1, rolled_b, bwd_out)
            return (
                fwd_out, bwd_out, resid, dchunk, dembed, dhead, nll_acc, ntok_acc,
            ), None

        carry0 = (
            jnp.zeros((V, *mb_shape), wire_dtype),
            jnp.zeros((V, *mb_shape), wire_dtype),
            jnp.zeros((V, RES + 1, *mb_shape), compute_dtype),
            _f32_zeros_like(local),
            _f32_zeros_like(embed_p),
            _f32_zeros_like(head_p),
            jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.float32),
        )
        (_, _, _, dchunk, dembed, dhead, nll, ntok), _ = jax.lax.scan(
            tick, carry0, jnp.arange(T_TOT)
        )

        axes_all = (axis_name, *present)
        nll = jax.lax.psum(nll, axes_all)
        ntok = jax.lax.psum(ntok, axes_all)
        dembed = jax.tree.map(lambda a: jax.lax.psum(a, axes_all), dembed)
        dhead = jax.tree.map(lambda a: jax.lax.psum(a, axes_all), dhead)
        if present:
            dchunk = jax.tree.map(lambda a: jax.lax.psum(a, present), dchunk)
        dchunk = jax.tree.map(lambda a: a[None], dchunk)
        return nll, ntok, dchunk, dembed, dhead

    param_specs = jax.tree.map(
        lambda p: P(axis_name, *([None] * (p.ndim - 1))), chunk_params
    )
    rep = jax.tree.map(lambda p: P(), embed_params)
    rep_head = jax.tree.map(lambda p: P(), head_params)
    mb_specs = jax.tree.map(
        lambda a: P(None, present or None, *([None] * (a.ndim - 2))), batch_mb
    )
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(param_specs, rep, rep_head, mb_specs),
        out_specs=(P(), P(), param_specs, rep, rep_head),
        axis_names={axis_name, *present},
        check_vma=False,
    )
    nll, ntok, dchunk, dembed, dhead = fn(chunk_params, embed_params, head_params, batch_mb)
    return nll, ntok, (dchunk, dembed, dhead)


def split_layers_into_chunks(stacked_layer_params: Any, num_stages: int, num_chunks: int) -> Any:
    """[L, ...] scan-stacked layers → [S, V, L/(S·V), ...] for the
    interleaved schedule: global stage ``g = v·S + s`` owns layer block g,
    so device s's chunk v holds layers ``g·Lc ... (g+1)·Lc``."""

    def reshape(p):
        L = p.shape[0]
        SV = num_stages * num_chunks
        if L % SV:
            raise ValueError(f"{L} layers not divisible by {SV} stage-chunks")
        Lc = L // SV
        # [L] → [V, S, Lc, ...] (g = v·S + s varies s fastest) → [S, V, Lc]
        r = p.reshape(num_chunks, num_stages, Lc, *p.shape[1:])
        return r.transpose(1, 0, *range(2, r.ndim))

    return jax.tree.map(reshape, stacked_layer_params)


def stack_stages(params_per_stage: list[Any]) -> Any:
    """[pytree_s for s in stages] → pytree with leading stage dim."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params_per_stage)


def split_layers_into_stages(stacked_layer_params: Any, num_stages: int) -> Any:
    """Reshape scan-stacked layer params [L, ...] → [S, L/S, ...] so a model's
    layer stack becomes pipeline stages of equal depth."""

    def reshape(p):
        L = p.shape[0]
        if L % num_stages:
            raise ValueError(f"{L} layers not divisible by {num_stages} stages")
        return p.reshape(num_stages, L // num_stages, *p.shape[1:])

    return jax.tree.map(reshape, stacked_layer_params)
