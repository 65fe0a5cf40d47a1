"""Expert parallelism: MoE routing + dispatch over the ``expert`` mesh axis.

Absent from the reference (SURVEY.md §2.5); needed for Mixtral-style models
(BASELINE.json config #5). GShard/Switch-style **dense dispatch**: routing
builds a [B, T, E, C] dispatch tensor (top-k gating, capacity-bounded) and
the expert exchange is two einsums whose E dimension is sharded over the
``expert`` axis — XLA lowers the resharding into the ragged all-to-all on
ICI, and the same code runs unsharded when the axis is 1.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tony_tpu.parallel.sharding import constrain


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    router_z_coef: float = 1e-3      # router z-loss (stability)
    aux_loss_coef: float = 1e-2      # load-balance loss
    # ragged (grouped GEMM — fused Pallas kernel when aligned, default)
    # | ragged_xla (force jax.lax.ragged_dot) | gather (indexed, capacity)
    # | dense (GShard einsum)
    dispatch: str = "ragged"
    # how a token's router logits become gates: "softmax" (Mixtral: softmax over
    # all experts, top-k of it, renormalised) | "sigmoid" (each expert scored by
    # itself; the top-k of score + a bias that chooses and does not weigh; the
    # chosen scores renormalised, then x routed_scale)
    scoring: str = "softmax"
    routed_scale: float = 1.0
    # (first, count): the experts this layer holds of the num_experts it routes
    # over. Routing and the gates' normalisation are over all of them; only
    # assignments that land on a held expert are sorted, computed and combined,
    # and what the absent experts would add is left out. None: all held. The
    # ragged dispatch only, forward only (serving).
    held: tuple | None = None


def capacity(tokens_per_batch: int, cfg: MoEConfig) -> int:
    c = int(cfg.top_k * tokens_per_batch * cfg.capacity_factor / cfg.num_experts)
    return max(c, cfg.top_k)


def _gating(
    x: jax.Array, router_w: jax.Array, cfg: MoEConfig, token_mask: jax.Array | None = None,
    bias: jax.Array | None = None,
):
    """Gating shared by every dispatch scheme: router softmax, top-k gates
    (renormalized, Mixtral convention), aux losses. With
    ``cfg.scoring == "sigmoid"``: float32 sigmoid scores, the top-k of
    ``score + bias`` (``bias`` [E] chooses and does not weigh), the chosen
    SCORES renormalised and scaled by ``cfg.routed_scale``; a float32 router
    is multiplied in float32 at full precision (two scores that nearly tie
    decide which expert a token takes).

    ``token_mask`` [B, T] (packed batches): masked-out tokens — padding —
    get zero gates and are excluded from the balance/z losses, so pads
    neither contribute to the output nor train the router on garbage
    hidden states.

    Returns (gate_vals [B,T,K] mask-zeroed, gate_idx [B,T,K], aux)."""
    E = cfg.num_experts

    # bf16 inputs with f32 accumulation: an explicit x.astype(f32) would
    # materialize a full f32 activation copy just for this tiny projection
    if cfg.scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"scoring must be 'softmax' or 'sigmoid', got {cfg.scoring!r}")
    if cfg.scoring == "sigmoid" or (cfg.held is not None and router_w.dtype == jnp.float32):
        # sigmoid scores, and a softmax router of a layer that holds a share of its experts (serving): the
        # router in its own dtype at full precision: which side of a near tie a token falls decides a whole
        # expert's part
        logits = jnp.einsum(
            "btd,de->bte", x.astype(router_w.dtype), router_w,
            preferred_element_type=jnp.float32, precision=jax.lax.Precision.HIGHEST,
        )
    else:
        logits = jnp.einsum(
            "btd,de->bte", x, router_w.astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
    if cfg.scoring == "sigmoid":
        probs = jax.nn.sigmoid(logits)
        _, gate_idx = jax.lax.top_k(probs if bias is None else probs + bias, cfg.top_k)
        gate_vals = jnp.take_along_axis(probs, gate_idx, axis=-1)
        scale = cfg.routed_scale
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, cfg.top_k)        # [B,T,K]
        scale = 1.0
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    if scale != 1.0:
        gate_vals = gate_vals * scale
    choice_onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)   # [B,T,K,E]
    if token_mask is not None:
        m = token_mask.astype(jnp.float32)
        gate_vals = gate_vals * m[:, :, None]
        choice_onehot = choice_onehot * m[:, :, None, None]

    # aux losses: load-balance (Switch) + router z-loss, over VALID tokens
    if token_mask is None:
        B, T, _ = x.shape
        n_valid = jnp.float32(B * T)
        me = probs.mean(axis=(0, 1))                                 # [E] mean prob
        z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    else:
        m = token_mask.astype(jnp.float32)
        n_valid = jnp.maximum(m.sum(), 1.0)
        me = (probs * m[:, :, None]).sum(axis=(0, 1)) / n_valid
        z = jnp.sum(jax.nn.logsumexp(logits, axis=-1) ** 2 * m) / n_valid
    ce = choice_onehot.sum(axis=2).sum(axis=(0, 1)) / n_valid        # [E] token fraction
    aux = {
        "moe_balance_loss": cfg.aux_loss_coef * E * jnp.sum(me * ce) * (1.0 / cfg.top_k),
        "moe_z_loss": cfg.router_z_coef * z,
        "moe_n_valid": n_valid,
    }
    return gate_vals, gate_idx, choice_onehot, aux


def _route_common(
    x: jax.Array, router_w: jax.Array, cfg: MoEConfig, token_mask: jax.Array | None = None
):
    """Shared routing prefix of the capacity-based dispatch schemes: gating
    + per-choice capacity-slot assignment + aux losses (sans dropped-frac,
    which depends on the dispatch representation).

    Returns (gate_vals [B,T,K], gate_idx [B,T,K], onehot [B,T,K,E],
    pos_in_expert [B,T,K,E], aux)."""
    B, T, _ = x.shape
    gate_vals, gate_idx, onehot, aux = _gating(x, router_w, cfg, token_mask)

    # expert-choice position assignment: for each (expert, k-slot) count
    # prior tokens routed to that expert to get its capacity slot
    E = cfg.num_experts
    flat = onehot.transpose(0, 2, 1, 3).reshape(B, cfg.top_k * T, E)  # k-major order
    pos_in_expert = (jnp.cumsum(flat, axis=1) - flat).reshape(B, cfg.top_k, T, E).transpose(0, 2, 1, 3)
    return gate_vals, gate_idx, onehot, pos_in_expert, aux


def route(
    x: jax.Array, router_w: jax.Array, cfg: MoEConfig, token_mask: jax.Array | None = None
) -> tuple[jax.Array, jax.Array, dict]:
    """Top-k routing with capacity (dense/GShard representation).

    x: [B, T, D]; router_w: [D, E] →
    dispatch [B, T, E, C] bool-ish, combine [B, T, E, C] f32, aux losses.
    """
    B, T, _ = x.shape
    C = capacity(T, cfg)
    gate_vals, _, onehot, pos_in_expert, aux = _route_common(x, router_w, cfg, token_mask)
    within_cap = pos_in_expert < C                                   # [B,T,K,E]

    slot_onehot = jax.nn.one_hot(pos_in_expert.astype(jnp.int32), C, dtype=jnp.float32)  # [B,T,K,E,C]
    dispatch = (onehot * within_cap)[..., None] * slot_onehot        # [B,T,K,E,C]
    combine = dispatch * gate_vals[..., None, None]
    dispatch = dispatch.sum(axis=2)                                  # [B,T,E,C]
    combine = combine.sum(axis=2)
    n_valid = aux.pop("moe_n_valid")
    aux["moe_dropped_frac"] = 1.0 - dispatch.sum() / (n_valid * cfg.top_k)
    return dispatch, combine, aux


def route_indices(x, router_w, cfg: MoEConfig, token_mask: jax.Array | None = None):
    """Top-k routing producing GATHER indices instead of dispatch tensors.

    Returns (src [B, E, C] token index per expert slot, slot_valid
    [B, E, C] 0/1, gate [B, E, C] combine weight, aux). Same capacity and
    gating math as ``route`` (shared prefix), but the per-slot assignment is
    expressed as indices, so dispatch/combine become a row gather and a
    masked scatter-add — O(E·C·D) data movement instead of the
    O(T·E·C·D) one-hot einsum FLOPs, and no [B,T,K,E,C] intermediate.
    """
    B, T, _ = x.shape
    E, C = cfg.num_experts, capacity(T, cfg)
    K = cfg.top_k

    gate_vals, gate_idx, onehot, pos_in_expert, aux = _route_common(x, router_w, cfg, token_mask)
    pos_of_choice = jnp.sum(pos_in_expert * onehot, axis=-1).astype(jnp.int32)  # [B,T,K]
    within_cap = pos_of_choice < C
    if token_mask is not None:
        # masked tokens have zeroed onehot → pos 0, which would CLAIM slot 0
        # of their expert and clobber a real token: exclude them outright
        within_cap = jnp.logical_and(within_cap, token_mask[:, :, None])

    # scatter each (t, k) choice into its (expert, slot) cell — ONE scatter
    # of a packed (token, gate) payload; valid falls out of the -1 init.
    # (A sort + searchsorted construction was measured 6 MFU pt SLOWER than
    # scattering on v5e — XLA's TPU sort is the bottleneck, not the scatter;
    # three separate scatters for src/valid/gate cost ~0.5pt over one.)
    expert_of_choice = gate_idx                                        # [B,T,K]
    t_idx = jnp.broadcast_to(jnp.arange(T)[None, :, None], (B, T, K))
    safe_slot = jnp.where(within_cap, pos_of_choice, C - 1)
    # payload [.., 2]: (token index as f32 — exact for T < 2^24, gate weight)
    payload = jnp.stack(
        [t_idx.astype(jnp.float32), gate_vals.astype(jnp.float32)], axis=-1
    )

    def scatter_b(e_i, s_i, p_i, ok_i):
        # each (e, slot) receives at most one choice (slots are unique by
        # construction); mode="drop" discards the masked duplicates at C-1
        e_f, s_f = e_i.reshape(-1), s_i.reshape(-1)
        p_f = p_i.reshape(-1, 2)
        e_f = jnp.where(ok_i.reshape(-1), e_f, cfg.num_experts)  # OOB → dropped
        cells = jnp.full((E, C, 2), -1.0, jnp.float32)
        return cells.at[e_f, s_f].set(p_f, mode="drop")

    cells = jax.vmap(scatter_b)(expert_of_choice, safe_slot, payload, within_cap)
    valid = cells[..., 0] >= 0.0                                       # [B,E,C]
    src = jnp.where(valid, cells[..., 0], 0.0).astype(jnp.int32)
    gate = jnp.where(valid, cells[..., 1], 0.0)

    n_valid = aux.pop("moe_n_valid")
    aux["moe_dropped_frac"] = 1.0 - jnp.sum(valid).astype(jnp.float32) / (n_valid * K)
    return src, valid, gate, aux


def route_ragged(
    x, router_w, cfg: MoEConfig, token_mask: jax.Array | None = None,
    tile: int | None = None, bias: jax.Array | None = None,
):
    """Capacity-FREE routing for the grouped-GEMM (ragged) dispatch.

    Instead of (expert, capacity-slot) cells, produce the expert-major
    token order directly: a counting sort of all N = B·T·K routing choices
    by expert id, built from one cumsum (rank within expert) plus the
    exclusive prefix-sum of per-expert counts — no capacity bound, no
    drops, no [B,T,E,C] tensors, and no TPU sort (6 MFU pt slower than
    arithmetic construction in the builders' r2 run, older than this code).

    Masked (pad) tokens still occupy group slots — ``jax.lax.ragged_dot``
    computes garbage for rows beyond ``sum(group_sizes)``, so every choice
    must live inside a real group — but their gates are zero (``_gating``),
    so they add only the pad fraction of expert FLOPs and nothing to the
    output or the router losses.

    With ``tile`` set (the Pallas fused-kernel path, ops/moe_gemm.py), each
    group's span is padded up to a multiple of ``tile`` — pad rows scatter
    nothing, so they keep the zero-init token index 0 and are never read
    back by the combine. Where a backward exists (``cfg.held`` is None) a
    group with no rows still gets one tile: the backward kernel initialises
    an expert's weight-grad block at the expert's first tile. The row count
    becomes the STATIC ``PN = (ceil(N/tile) + E) · tile ≥ sum(padded group
    sizes)``.

    With ``cfg.held = (first, count)`` only the choices that land on experts
    ``first .. first + count - 1`` are sorted: the groups are the ``count``
    held experts, a choice of an absent expert has no row (its ``dest`` is
    the row count, past every row, and its gate is zero), and the row count
    keeps its static bound (every choice could be held, and every group
    could end in a partial tile). A held layer is forward only, so a held
    expert that no row chose has a group of size zero and no tile: the
    kernel never fetches its slab, and the padded sizes may sum to zero.

    Returns (sort_tok [N or PN] int32 — flat B·T token index in
    expert-major order, dest [N] int32 — each choice's position in that
    order, gate_vals [B,T,K] f32, gate_sorted [N or PN] f32 (zero on pad
    rows), group_sizes [E] int32 (padded when tile is set), aux).
    """
    B, T, _ = x.shape
    E, K = cfg.num_experts, cfg.top_k
    N = B * T * K

    gate_vals, gate_idx, _, aux = _gating(x, router_w, cfg, token_mask, bias)
    on = None
    if cfg.held is not None:
        first, E = cfg.held
        gate_idx = gate_idx - first
        on = (gate_idx >= 0) & (gate_idx < E)
        gate_idx = jnp.where(on, gate_idx, -1)          # one_hot(-1) is a row of zeros: in no group
        gate_vals = jnp.where(on, gate_vals, 0.0)
    # rank-within-expert via per-batch-row cumsum ([B, T·K, E], depth
    # log(T·K) with B in parallel — the construction r2 measured as free)
    # + a tiny [B, E] prefix across rows; global order is b-major within
    # each expert's span
    oh = jax.nn.one_hot(gate_idx.reshape(B, T * K), E, dtype=jnp.int32)  # [B, TK, E]
    pos_b = jnp.cumsum(oh, axis=1) - oh                                  # rank within (b, e)
    counts_b = oh.sum(axis=1)                                            # [B, E]
    prefix_b = jnp.cumsum(counts_b, axis=0) - counts_b                   # earlier rows' counts
    group_sizes = counts_b.sum(axis=0)                                   # [E], sums to N
    rows = N
    if tile is not None:
        # whole tiles; at least one where a backward has a weight-grad block to initialise
        group_sizes = jnp.maximum(-(-group_sizes // tile), 0 if cfg.held is not None else 1) * tile
        rows = (-(-N // tile) + E) * tile                                # static upper bound
    offsets = jnp.cumsum(group_sizes) - group_sizes                      # exclusive prefix
    dest = jnp.sum(
        (pos_b + (offsets[None, :] + prefix_b)[:, None, :]) * oh, axis=-1
    ).reshape(N)                                                         # [N], injective
    if on is not None:
        dest = jnp.where(on.reshape(N), dest, rows)                      # past every row: scattered nowhere

    # invert the permutation with two small typed scatters (token ids stay
    # int32 — a packed f32 payload would corrupt ids beyond 2^24 tokens).
    # gate_sorted keeps ZERO on pad rows, which is what makes the combine's
    # gather-form backward blank them out (see _combine_gather).
    tok = jnp.arange(N, dtype=jnp.int32) // K                            # flat B·T token id
    sort_tok = jnp.zeros((rows,), jnp.int32).at[dest].set(tok, mode="drop")
    gate_sorted = jnp.zeros((rows,), jnp.float32).at[dest].set(
        gate_vals.reshape(N).astype(jnp.float32), mode="drop"
    )

    aux = dict(aux)
    aux.pop("moe_n_valid")
    aux["moe_dropped_frac"] = jnp.zeros((), jnp.float32)                 # capacity-free: no drops
    return sort_tok, dest, gate_vals, gate_sorted, group_sizes, aux


def _kernel_eligible(cfg: MoEConfig, D: int, F: int, dtype) -> bool:
    """One copy of the fused-kernel eligibility rule: ``dispatch="ragged"``
    selects by what it can observe — the fused Pallas kernel for MXU-aligned
    bf16 geometry on a TPU backend (or under the interpret harness), three
    ``ragged_dot`` grouped GEMMs otherwise; ``"ragged_xla"`` is the explicit
    ask for the latter."""
    from tony_tpu.ops.interpret import interpret

    return (
        cfg.dispatch == "ragged"
        and D % 128 == 0
        and F % 128 == 0
        and dtype == jnp.bfloat16
        and (jax.default_backend() == "tpu" or interpret())
    )


def _expert_swiglu(xs, w_gate, w_up, w_down, group_sizes, tile, layer=None, name="moe_swiglu_grouped"):
    """Grouped expert SwiGLU on sorted rows: the fused Pallas kernel when
    ``tile`` is set, else three jax.lax.ragged_dot grouped GEMMs.
    ``layer`` (forward only, serving): the banks are every layer's, stacked,
    and this is the index of the layer's; the kernel takes the whole stack and
    runs the groups' own row tiles, ``sum(group_sizes) // tile`` of them (none
    for a group of size zero, so possibly none at all), and skips the rest of
    the static row bound, leaving those rows unwritten."""
    from tony_tpu.ops import moe_gemm

    if tile is not None:
        tg = moe_gemm.tile_group_map(group_sizes, xs.shape[0] // tile, tile)
        if layer is not None:
            return moe_gemm.moe_swiglu_rows(xs, w_gate, w_up, w_down, tg, tile, group_sizes.sum() // tile, layer, name)
        return moe_gemm.moe_swiglu_grouped(xs, w_gate, w_up, w_down, tg, tile)
    if layer is not None:
        w_gate, w_up, w_down = w_gate[layer], w_up[layer], w_down[layer]
    g = jax.nn.silu(jax.lax.ragged_dot(xs, w_gate, group_sizes))
    u = jax.lax.ragged_dot(xs, w_up, group_sizes)
    return jax.lax.ragged_dot((g * u).astype(xs.dtype), w_down, group_sizes)


@jax.custom_vjp
def _dispatch_gather(x_flat, sort_tok, dest):
    """xs = x_flat[sort_tok] with a GATHER-form backward.

    The autodiff transpose of a row gather is a scatter-add, which costs
    ~1.7× a gather at [N, D] bench shape (builders' r3 probes, older than
    this code). Because
    every token appears exactly top_k times and ``dest`` enumerates those
    appearances, the cotangent is expressible as a gather:
    ``dx[t] = Σ_k dxs[dest[t, k]]`` — no scatter anywhere."""
    return x_flat[sort_tok]


def _dispatch_gather_fwd(x_flat, sort_tok, dest):
    return x_flat[sort_tok], (sort_tok, dest, x_flat.shape[0])


def _dispatch_gather_bwd(res, dxs):
    import numpy as np

    sort_tok, dest, BT = res
    K = dest.shape[0] // BT
    dx = dxs[dest].reshape(BT, K, dxs.shape[-1]).sum(axis=1)
    return (
        dx,
        np.zeros(sort_tok.shape, jax.dtypes.float0),
        np.zeros(dest.shape, jax.dtypes.float0),
    )


_dispatch_gather.defvjp(_dispatch_gather_fwd, _dispatch_gather_bwd)


@jax.custom_vjp
def _span_dispatch_gather(x_flat, tok_span, idx, gates):
    """EP-span row gather with a GATHER-form backward: fwd is
    ``x_flat[tok_span]``; the cotangent is
    ``dx[t] = Σ_k in-span dxs[idx[t,k]]`` (out-of-span choices carry zero
    ``gates``, whose sign function doubles as the in-span mask here).
    ``idx``/``gates`` are positional residuals only — their cotangents are
    zero/float0 (gates' real gradient flows through the combine)."""
    return x_flat[tok_span]


def _span_dispatch_gather_fwd(x_flat, tok_span, idx, gates):
    return x_flat[tok_span], (tok_span, idx, gates, x_flat.shape[0])


def _span_dispatch_gather_bwd(res, dxs):
    import numpy as np

    tok_span, idx, gates, BT = res
    K = idx.shape[0] // BT
    mask = (gates != 0.0).reshape(BT, K)
    picked = dxs[idx].reshape(BT, K, dxs.shape[-1])
    dx = jnp.sum(jnp.where(mask[..., None], picked, 0), axis=1)
    return (
        dx.astype(dxs.dtype),
        np.zeros(tok_span.shape, jax.dtypes.float0),
        np.zeros(idx.shape, jax.dtypes.float0),
        jnp.zeros_like(gates),
    )


_span_dispatch_gather.defvjp(_span_dispatch_gather_fwd, _span_dispatch_gather_bwd)


@jax.custom_vjp
def _combine_gather(ys, dest, sort_tok, gate_vals, gate_sorted):
    """y[t] = Σ_k gate[t,k] · ys[dest[t,k]] with a GATHER-form backward.

    Forward gathers expert outputs back to choice order and K-sums with
    the gates. The transpose w.r.t. ``ys`` is again a gather, not a
    scatter: sorted row j belongs to token ``sort_tok[j]`` with weight
    ``gate_sorted[j]`` (zero on pad rows), so
    ``dys[j] = gate_sorted[j] · dy[sort_tok[j]]``."""
    BT, K = gate_vals.shape
    yc = ys[dest].reshape(BT, K, ys.shape[-1])
    return jnp.einsum("tkd,tk->td", yc, gate_vals.astype(ys.dtype))


def _combine_gather_fwd(ys, dest, sort_tok, gate_vals, gate_sorted):
    return _combine_gather(ys, dest, sort_tok, gate_vals, gate_sorted), (
        ys, dest, sort_tok, gate_vals, gate_sorted,
    )


def _combine_gather_bwd(res, dy):
    import numpy as np

    ys, dest, sort_tok, gate_vals, gate_sorted = res
    K = gate_vals.shape[1]
    # one row gather serves both outputs: dys_raw = dy[sort_tok] feeds the
    # gate-scaled cotangent AND the gate grad as a row-dot —
    # ``dgate[t,k] = ys[dest[t,k]]·dy[t] = (ys ⊙ dys_raw).sum(-1)[dest[t,k]]``
    # (sort_tok[dest[t,k]] == t) — replacing the former ys[dest] row gather
    # + [N,D] einsum with a fusable elementwise-reduce + a scalar gather.
    dys_raw = dy[sort_tok]
    dys = dys_raw * gate_sorted[:, None].astype(dy.dtype)
    dgate_sorted = (ys.astype(jnp.float32) * dys_raw.astype(jnp.float32)).sum(-1)
    dgate = dgate_sorted[dest].reshape(gate_vals.shape)
    return (
        dys.astype(ys.dtype),
        np.zeros(dest.shape, jax.dtypes.float0),
        np.zeros(sort_tok.shape, jax.dtypes.float0),
        dgate.astype(gate_vals.dtype),
        jnp.zeros_like(gate_sorted),
    )


_combine_gather.defvjp(_combine_gather_fwd, _combine_gather_bwd)


def _ragged_expert_ffn_ep(
    x, router_w, w_gate, w_up, w_down, cfg: MoEConfig, mesh, token_mask,
):
    """Expert-SHARDED ragged dispatch: the capacity-free grouped-GEMM path
    under an ``expert`` mesh axis (SURVEY §2.5 "EP ragged all-to-all").

    The sorted row order is expert-major, so shard s owns one CONTIGUOUS
    span of rows. Each shard therefore:

    1. runs the (replicated, deterministic) routing on its batch shard;
    2. slices its span's token indices and gathers ONLY those rows —
       per-shard data movement is its own tokens, the gather itself is the
       ragged all-to-all (rows cross batch shards via the index gather);
    3. runs the fused grouped GEMM (or ragged_dot) on its local experts;
    4. partial-combines choices whose dest falls in its span and psums the
       result over the expert axis.

    Both the dispatch and combine keep GATHER-form backwards (span
    variants of _dispatch_gather/_combine_gather). The span length bound
    is static: ``(ceil(N/tile)+E_local)·tile`` rows. Aux losses are
    per-batch-shard means (pmean): exact for the z/balance statistic only
    when every shard holds the same valid-token count — with packed
    batches whose pads concentrate on one shard, pad-heavy shards'
    tokens are up-weighted (the standard per-group MoE approximation).
    """
    E = cfg.num_experts
    ep = mesh.shape["expert"]
    if E % ep:
        raise ValueError(f"num_experts {E} must divide the expert axis {ep}")
    E_local = E // ep
    B, T, D = x.shape
    K = cfg.top_k
    from tony_tpu.ops import moe_gemm

    tile = moe_gemm.TILE_M if _kernel_eligible(cfg, D, w_gate.shape[-1], x.dtype) else None
    batch_axes = tuple(a for a in ("data", "fsdp") if mesh.shape.get(a, 1) > 1)

    def body(x_l, router_l, wg_l, wu_l, wd_l, tm_l):
        from jax.ad_checkpoint import checkpoint_name

        ei = jax.lax.axis_index("expert")
        Bl = x_l.shape[0]
        Nl = Bl * T * K
        sort_tok, dest, gate_vals, gate_sorted, group_sizes, aux = route_ragged(
            x_l, router_l, cfg, tm_l if token_mask is not None else None, tile=tile
        )
        sort_tok = checkpoint_name(sort_tok, "moe_route")
        dest = checkpoint_name(dest, "moe_route")
        gate_vals = checkpoint_name(gate_vals, "moe_route")
        gate_sorted = checkpoint_name(gate_sorted, "moe_route")
        group_sizes = checkpoint_name(group_sizes, "moe_route")

        offsets = jnp.cumsum(group_sizes) - group_sizes
        start = offsets[ei * E_local]                        # span start (dynamic)
        gs_local = jax.lax.dynamic_slice(group_sizes, (ei * E_local,), (E_local,))
        # static span bound: every token could land on this shard
        span = (-(-Nl // tile) + E_local) * tile if tile is not None else Nl
        # pad the per-row arrays so the dynamic slices NEVER clamp (a
        # clamped start would silently misalign rows against gs_local)
        pad0 = jnp.zeros((span,), jnp.int32)
        tok_span = jax.lax.dynamic_slice(
            jnp.concatenate([sort_tok, pad0]), (start,), (span,)
        )
        gate_span = jax.lax.dynamic_slice(
            jnp.concatenate([gate_sorted, pad0.astype(gate_sorted.dtype)]),
            (start,), (span,),
        )
        local_total = gs_local.sum()
        rel = dest - start
        in_span = jnp.logical_and(rel >= 0, rel < local_total)
        idx = jnp.clip(rel, 0, span - 1)
        gates = jnp.where(
            in_span.reshape(Bl * T, K), gate_vals.reshape(Bl * T, K), 0.0
        )

        xs = _span_dispatch_gather(x_l.reshape(Bl * T, D), tok_span, idx, gates)
        ys = _expert_swiglu(xs, wg_l, wu_l, wd_l, gs_local, tile)
        # rows past the local content are unspecified (ragged_dot tail /
        # pad tiles): zero them so the masked combine can't import NaNs
        row_ok = jnp.arange(span)[:, None] < local_total
        ys = jnp.where(row_ok, ys, 0)
        y = _combine_gather(ys, idx, tok_span, gates, gate_span)
        y = jax.lax.psum(y, "expert")
        # aux computed identically on every expert shard (replicated
        # routing) but differs across batch shards: per-shard means (see
        # the docstring's approximation note)
        if batch_axes:
            aux = {k: jax.lax.pmean(v, batch_axes) for k, v in aux.items()}
        return y.reshape(Bl, T, D).astype(x_l.dtype), aux

    act = P(batch_axes or None, None, None)
    wspec = P("expert", None, None)
    tm = token_mask if token_mask is not None else jnp.ones((B, T), bool)
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(act, P(None, None), wspec, wspec, wspec,
                  P(batch_axes or None, None)),
        out_specs=(act, P()),
        axis_names=set(mesh.axis_names),
        check_vma=False,
    )
    return fn(x, router_w, w_gate, w_up, w_down, tm)


def held_tile(cfg: MoEConfig, choices: int, tile: int) -> int:
    """The row tile of a layer that holds part of its experts. The row bound is
    static (every choice could land here): ``ceil(choices / tile) + held``
    tiles, of which those that hold a row are live (none for a held expert no
    row chose) and the rest are skipped at about 3 us a grid step; a live tile
    costs its expert's whole slab. So the tile is the given one while an expert
    expects fewer rows than it holds (a decode step's 16 rows, a short prefill),
    and twice that beyond (a 2048-row chunk: half the tiles, and an expert's
    slab read once, not twice). On the chip at 6144 x 2048, 256 rows: 32 -> 2.18
    ms a layer, 64 -> 1.77, 128 -> 1.74. Where the fused kernel runs
    (``held_form``'s ``"in_kernel"`` and ``"fetched"``) the bound is grid steps
    and a few ``[bound]`` vectors of scalars, and of the rows' outputs only the
    live tiles' are written; under ``ragged_dot`` (``"staged"``) it is also a
    buffer: XLA writes ``x[sort_tok]`` at all of it whatever was chosen."""
    return 2 * tile if choices // cfg.num_experts >= tile else tile


#: the most tokens a call may have for the kernel to gather its rows and sum its choices itself (``held_form``)
HELD_IN_KERNEL_TOKENS = 512
#: VMEM the call's tokens may take there, beside ``moe_gemm._WEIGHT_VMEM`` of weight blocks and a row tile's buffers
_TOKENS_VMEM = 40 * 1024 * 1024


def held_form(T: int, D: int, itemsize: int, top_k: int = 8) -> str:
    """Where a held layer's rows are gathered and its choices summed, from the
    call's shapes alone, where the fused kernel runs (``held_ffn_form``).
    ``"in_kernel"`` (``moe_gemm.moe_swiglu_tokens``: the grouped product takes
    ``x [T, D]`` and returns ``y [T, D]``; the sorted rows and the experts'
    outputs never exist in HBM) where the tokens, twice (the pipeline's two
    buffers), ``y`` twice and the float32 sum fit ``_TOKENS_VMEM`` and T is at
    most ``HELD_IN_KERNEL_TOKENS``: a tile's two one-hot products are ``4 T tile
    D`` operations beside a slab of ``6 D F`` bytes, a hundredth of its fetch at
    T = 64 and a third at 512 x 6144 x 2048. Every decode batch of the benchmark
    (24, 48, 64, 256 slots) and a prefill bucket of up to 512 rows are in the
    kernel. Beyond (a 1024- or 2048-row prefill chunk: 2048 x 6144 would want
    151 MB of VMEM) ``"fetched"``: the tokens stay in HBM, the grouped product
    fetches a live tile's real rows from them by one DMA a row
    (``moe_gemm.moe_swiglu_fetched``) and writes the live tiles' outputs, and a
    second call sums a token's choices from the rows that exist, one DMA a row
    again (``moe_gemm.moe_choices_sum``): nothing of the static row bound goes
    through HBM. A row travels alone as a slab of ``D / 128`` sublanes, whole
    tiles of 8 where D is a multiple of 1024, and the two calls' buffers (a row
    tile of up to 256 rows as slabs, rows, sum and output; 64 tokens' ``top_k``
    rows twice) have to fit ``_TOKENS_VMEM``: else ``"staged"``, XLA's gathers
    at the static bound before the product and after, as under ``ragged_dot``.
    On the chip, a layer alone, staged | in the kernel (PERF.md section 6, PR
    54): 64 x 4096 x 768 of 36 experts 1.24 | 0.95 ms, 256 x 6144 x 2048 of 16
    2.14 | 1.71, 512 x 6144 x 2048 2.41 | 1.76 (the largest it holds: 37.7 MB of
    tokens, sum and result); staged | fetched (PERF.md section 6, PR 62): 2048 x
    4096 x 1280 of 40 experts 4.00 | 2.74 ms, 1024 x 6144 x 2048 of 16 2.88 |
    2.08, 2048 x 4096 x 768 of 36 (top 10, a tile of 256) 5.08 | 4.26."""
    from tony_tpu.ops import moe_gemm

    if T <= HELD_IN_KERNEL_TOKENS and T * D * (4 * itemsize + 4) <= _TOKENS_VMEM:
        return "in_kernel"
    a_tile = 2 * moe_gemm.TILE_M * D * (4 + itemsize + 4 + 2 * 4)        # slabs, rows, sum, the output's two buffers
    a_sum = moe_gemm.SUM_TOKENS * D * (2 * top_k * 4 + 4 + 2 * itemsize)
    return "fetched" if D % 1024 == 0 and max(a_tile, a_sum) <= _TOKENS_VMEM else "staged"


def held_ffn_form(cfg: MoEConfig, T: int, D: int, F: int, dtype) -> str:
    """The form ``held_expert_ffn`` runs in for T tokens of this geometry:
    ``held_form``'s where the fused kernel runs at all (``_kernel_eligible``),
    ``"staged"`` under ``ragged_dot``. The engine counts its programs by it."""
    dtype = jnp.dtype(dtype)
    return held_form(T, D, dtype.itemsize, cfg.top_k) if _kernel_eligible(cfg, D, F, dtype) else "staged"


def held_expert_ffn(x, router_w, bias, w_gate, w_up, w_down, layer, cfg: MoEConfig, count_mask=None,
                    name: str = "moe_swiglu_grouped"):
    """The routed FFN of a layer that holds ``cfg.held`` of its experts, for
    serving: x [T, D] -> (y [T, D], rows [count] int32). ``w_gate`` / ``w_up``
    [L, count, D, F], ``w_down`` [L, count, F, D]: the held experts' banks of
    every layer that has them, and ``layer`` [] the index of this one. Routing
    is over all ``cfg.num_experts`` (``route_ragged``); the rows sorted,
    multiplied and combined are the choices that landed on a held expert, and
    a held expert that no row chose has no row tile (``route_ragged``), so a
    step reads the held-and-chosen experts' weights and nothing else of size:
    with no choice on any held expert the kernel runs no tile and ``y`` is
    zero. Where the fused kernel runs, ``held_form`` says from the shapes how
    the rows reach it and the choices leave it: ``"in_kernel"`` (a decode
    step, a prefill bucket of up to 512 rows) the one call gathers a tile's
    rows from ``x`` and sums the gated choices into ``y`` in VMEM;
    ``"fetched"`` (a longer prefill chunk) the call fetches a live tile's real
    rows from ``x`` in HBM and a second sums each token's choices from the rows
    that exist. Under ``ragged_dot``, where the kernel is not eligible (and at
    a width no row travels alone at), the rows are ``"staged"`` through HBM at
    the static bound by XLA, before the product and after.
    The three forms round alike (a row's output and the gate cast to the
    activations' type, products summed in float32); fetched and staged sum a
    token's choices in choice order, in the kernel in tile order.
    ``rows`` counts each held expert's real rows (its load this call), from the
    tokens ``count_mask`` [T] marks (a decode step's idle slots are computed
    like any row and counted as none). ``name``: what the fused call is called
    in a trace (a decode step's and a prefill chunk's are told apart by it). No
    capacity: no choice is dropped. No backward."""
    from tony_tpu.ops import moe_gemm

    if cfg.held is None or cfg.dispatch != "ragged":
        raise ValueError(f"held_expert_ffn wants cfg.held and dispatch 'ragged', got {cfg.held!r} / {cfg.dispatch!r}")
    T, D = x.shape
    K, F = cfg.top_k, w_gate.shape[-1]
    tile = held_tile(cfg, T * K, moe_gemm.TILE_M) if _kernel_eligible(cfg, D, F, x.dtype) else None
    sort_tok, dest, gate_vals, gate_sorted, group_sizes, _ = route_ragged(x[None], router_w, cfg, None, tile=tile, bias=bias)
    rows = sort_tok.shape[0]
    on = dest < rows                                                     # [T*K]: the choice has a row
    counted = on if count_mask is None else on & jnp.repeat(count_mask, K)
    # a held expert's real rows: the counted choices whose row lies in its span, one compare over [T*K, count]
    # (a binary search of the spans is six dependent gathers of T*K elements, 77 us a layer at 640 choices of 36)
    ends = jnp.cumsum(group_sizes)

    def rows_in_spans(chosen):
        return (chosen[:, None] & (dest[:, None] >= (ends - group_sizes)[None]) & (dest[:, None] < ends[None])).sum(0, dtype=jnp.int32)

    real = rows_in_spans(counted)
    form = held_ffn_form(cfg, T, D, F, x.dtype)
    if form == "in_kernel":
        tg = moe_gemm.tile_group_map(group_sizes, rows // tile, tile)
        y = moe_gemm.moe_swiglu_tokens(x, sort_tok, gate_sorted, w_gate, w_up, w_down, tg, tile,
                                       group_sizes.sum() // tile, layer, name)
        return y, real
    if form == "fetched":
        tg = moe_gemm.tile_group_map(group_sizes, rows // tile, tile)
        # a tile's real rows are its first: what its group holds (every choice's, counted or not) past the tile's start
        held_rows = real if count_mask is None else rows_in_spans(on)
        starts = jnp.arange(rows // tile, dtype=jnp.int32) * tile
        in_tile = jnp.where(starts < ends[-1], jnp.clip((ends - group_sizes + held_rows)[tg] - starts, 0, tile), 0)
        ys = moe_gemm.moe_swiglu_fetched(x, sort_tok, in_tile, w_gate, w_up, w_down, tg, tile, ends[-1] // tile, layer, name)
        return moe_gemm.moe_choices_sum(ys, dest, gate_vals.reshape(T * K), K, D, x.dtype), real
    ys = _expert_swiglu(x[sort_tok], w_gate, w_up, w_down, group_sizes, tile, layer, name)
    # a choice of an absent expert reads row 0 and is masked, never multiplied: rows past the groups are unwritten
    yc = jnp.where(on[:, None], ys[jnp.where(on, dest, 0)], 0).reshape(T, K, D)
    y = jnp.einsum("tkd,tk->td", yc, gate_vals.reshape(T, K).astype(ys.dtype))
    return y.astype(x.dtype), real


def held_step_counts(rows, live, top_k: int):
    """What a decode step adds to a chunk's counts, [4] int32: from ``rows`` [Lr,
    count] (``held_expert_ffn``'s, of each routed layer) and the ``live`` slots
    [S]: the rows that landed on a held expert, the rows of the fullest held
    expert a layer (the straggler a grouped product waits for), the choices
    made (rows x top_k), and the held experts that a live slot's row chose, a
    layer: those whose slabs the grouped product read (an idle slot's row is
    computed like any and may add one that is not counted)."""
    return jnp.stack([rows.sum(), rows.max(axis=1).sum(), live.sum() * top_k * rows.shape[0], (rows > 0).sum()])


def _ragged_expert_ffn(x, router_w, w_gate, w_up, w_down, cfg: MoEConfig, token_mask):
    """Grouped-GEMM MoE: expert matmuls computed straight from gathered
    rows via ``jax.lax.ragged_dot`` (XLA's megablox-style grouped GEMM) —
    the [E,B,C,D] dispatched bank of the capacity schemes never exists.
    Per layer this removes the ~4 extra full-activation HBM round-trips
    the builders' r2 decomposition charged to the bank plus the
    capacity overcompute (N = K·B·T rows exactly, vs 1.25·K·B·T slots).

    Measured layout choices (the builders' same-session r3 A/Bs, older than this code): the
    combine is a GATHER back to choice order, not a scatter-add — under
    remat replay an op's fwd runs twice per step, and gather-fwd (4.4 ms)
    beats scatter-add-fwd (7.6 ms) at [N, D] bench shape. Fusing gate+up
    into one [E, D, 2F] grouped GEMM via per-layer concat measured 1.3 MFU
    pt SLOWER end-to-end (the concat + its backward split/copies outweigh
    the saved xs read) — kept separate."""
    from jax.ad_checkpoint import checkpoint_name

    from tony_tpu.ops import moe_gemm

    B, T, D = x.shape
    K = cfg.top_k
    dtype = x.dtype
    # fused Pallas kernel (one VMEM pass for the whole expert MLP) when the
    # geometry is MXU-aligned and we're on a TPU backend (or the interpret
    # harness); otherwise three jax.lax.ragged_dot grouped GEMMs
    tile = moe_gemm.TILE_M if _kernel_eligible(cfg, D, w_gate.shape[-1], dtype) else None
    sort_tok, dest, gate_vals, gate_sorted, group_sizes, aux = route_ragged(
        x, router_w, cfg, token_mask, tile=tile
    )
    # pin routing outputs for remat (vector-bound gating pipeline; see gather path)
    sort_tok = checkpoint_name(sort_tok, "moe_route")
    dest = checkpoint_name(dest, "moe_route")
    gate_vals = checkpoint_name(gate_vals, "moe_route")
    gate_sorted = checkpoint_name(gate_sorted, "moe_route")
    group_sizes = checkpoint_name(group_sizes, "moe_route")

    xs = _dispatch_gather(x.reshape(B * T, D), sort_tok, dest)           # [N|PN, D]
    # NAMED but not saved by the default flash policy: saving xs would skip
    # the gather replay in the backward, but the PN·D/layer it costs forces
    # a smaller batch — measured net NEGATIVE (b24 32.6% / b28 33.2% pinned
    # vs b32 33.8% unpinned). The name lets a rung of its own (a tuple of
    # names as remat_policy: ops/attention.remat_block) re-test the tradeoff
    # per shape.
    xs = checkpoint_name(xs, "moe_disp")
    ys = _expert_swiglu(xs, w_gate, w_up, w_down, group_sizes, tile)
    # combine in choice order: gather each (token, k) choice's row and
    # weight-sum over k — gathers in the backward too (_combine_gather)
    y = _combine_gather(
        ys, dest, sort_tok, gate_vals.reshape(B * T, K), gate_sorted
    )
    # combine output [B·T, D]: saving it stops the backward from replaying
    # the combine gather chain (ladder name, not in the default save list)
    y = checkpoint_name(y, "moe_combine")
    return y.reshape(B, T, D).astype(dtype), aux


def _expert_mlp(xe, w_gate, w_up, w_down, mesh):
    """xe [E, B, C, D] → [E, B, C, D] through each expert's SwiGLU."""
    if mesh is not None:
        xe = constrain(xe, mesh, P("expert", ("data", "fsdp"), None, None))
    g = jax.nn.silu(jnp.einsum("ebcd,edf->ebcf", xe, w_gate))
    u = jnp.einsum("ebcd,edf->ebcf", xe, w_up)
    ye = jnp.einsum("ebcf,efd->ebcd", g * u, w_down)
    if mesh is not None:
        ye = constrain(ye, mesh, P("expert", ("data", "fsdp"), None, None))
    return ye


def moe_ffn(
    x: jax.Array,
    router_w: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    cfg: MoEConfig,
    mesh=None,
    token_mask: jax.Array | None = None,
) -> tuple[jax.Array, dict]:
    """SwiGLU mixture-of-experts FFN.

    x: [B, T, D]; router_w [D, E]; w_gate/w_up [E, D, F]; w_down [E, F, D].
    Expert weights shard P('expert', 'fsdp', 'model'); the dispatched-token
    tensor constrains to P(batch, 'expert', ...) so the exchange rides the
    expert axis (ICI all-to-all). Three dispatch schemes (cfg.dispatch):
    "ragged" (default) is the grouped-GEMM path — capacity-free counting
    sort + ``jax.lax.ragged_dot``, no dispatched bank; "gather" moves token
    rows into (expert, capacity-slot) cells by index; "dense" is the GShard
    one-hot einsum pair (kept for parity/verification — same math).

    The ragged path runs in BOTH regimes: unsharded expert axis (incl.
    the single-chip bench) uses the flat grouped-GEMM path; an expert
    axis > 1 routes to the contiguous-span shard_map path
    (_ragged_expert_ffn_ep) — still capacity-free, no drops, each shard
    computing only its own experts' span.
    """
    dtype = x.dtype
    if cfg.held is not None:
        raise ValueError("a layer that holds part of its experts runs through held_expert_ffn (serving, forward only)")
    if cfg.dispatch in ("ragged", "ragged_xla"):
        expert_sharded = (
            mesh is not None
            and "expert" in getattr(mesh, "axis_names", ())
            and mesh.shape["expert"] > 1
        )
        if not expert_sharded:
            return _ragged_expert_ffn(x, router_w, w_gate, w_up, w_down, cfg, token_mask)
        if mesh.shape.get("model", 1) == 1 and mesh.shape.get("context", 1) == 1:
            # the span shard_map honors batch+expert axes (weight fsdp
            # shards all-gather at use — FSDP semantics); a model/context
            # axis would silently REPLICATE the MoE compute, so those
            # layouts keep the GSPMD gather dispatch below
            return _ragged_expert_ffn_ep(
                x, router_w, w_gate, w_up, w_down, cfg, mesh, token_mask
            )
        import dataclasses

        cfg = dataclasses.replace(cfg, dispatch="gather")
    if cfg.dispatch == "dense":
        dispatch, combine, aux = route(x, router_w, cfg, token_mask)
        xe = jnp.einsum("btec,btd->ebcd", dispatch.astype(dtype), x)  # [E,B,C,D]
        ye = _expert_mlp(xe, w_gate, w_up, w_down, mesh)
        y = jnp.einsum("ebcd,btec->btd", ye, combine.astype(dtype))
        return y.astype(dtype), aux
    if cfg.dispatch != "gather":
        raise ValueError(f"dispatch must be 'gather' or 'dense', got {cfg.dispatch!r}")

    src, valid, gate, aux = route_indices(x, router_w, cfg, token_mask)
    # routing outputs are tiny ([B,E,C] ints/floats) but their recompute in a
    # remat backward re-runs the whole gating pipeline (softmax, top-k,
    # cumsum, scatter — vector-bound): name them so remat policies can pin
    # them alongside the flash-kernel outputs (ops/attention.remat_block)
    from jax.ad_checkpoint import checkpoint_name

    src = checkpoint_name(src, "moe_route")
    valid = checkpoint_name(valid, "moe_route")
    gate = checkpoint_name(gate, "moe_route")

    def gather_b(xb, srcb):                                           # [T,D],[E,C]
        return xb[srcb]                                               # [E,C,D]

    # NO valid-mask multiply on the dispatch side: invalid slots gather some
    # row and compute garbage through the expert, but the combine weight is
    # 0 there, so nothing reaches the output — and skipping the mask (and the
    # E<->B transposes the old [E,B,C,D] layout forced) saves full HBM
    # round-trips of the dispatched bank.
    xe = jax.vmap(gather_b)(x, src).transpose(1, 0, 2, 3)             # [E,B,C,D]
    # E-major expert matmuls: +0.8 MFU pt vs batch-major on v5e (the einsum's
    # batched dim wants to lead; XLA folds the explicit transpose into the
    # gather's output layout)
    ye = _expert_mlp(xe, w_gate, w_up, w_down, mesh).transpose(1, 0, 2, 3)
    w = jnp.where(valid, gate, 0.0).astype(dtype)

    def combine_b(yeb, srcb, wb):
        flat = (yeb * wb[..., None]).reshape(-1, yeb.shape[-1])       # [E*C, D]
        out = jnp.zeros((x.shape[1], yeb.shape[-1]), flat.dtype)
        return out.at[srcb.reshape(-1)].add(flat)                     # scatter-add

    y = jax.vmap(combine_b)(ye, src, w)
    return y.astype(dtype), aux
