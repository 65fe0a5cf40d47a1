"""Elementwise/normalization building blocks (XLA-fused on TPU).

These stay as plain jnp expressions on purpose: XLA fuses RMSNorm/RoPE/SwiGLU
into adjacent matmuls (the HBM-bandwidth win hand-written kernels would chase)
— Pallas is reserved for ops XLA can't schedule well (attention, ring
collectives, quantization; see ops/attention.py, ops/quant.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm in f32 accumulation regardless of input dtype."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mean) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight + bias


def rope_frequencies(
    dim: int, max_seq: int, theta: float = 10000.0, scaling: tuple = ()
) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables [max_seq, dim//2] in f32.

    ``scaling`` (hashable tuple so configs stay frozen/static):
      ()                                → no scaling,
      ("linear", factor)                → positions divided by factor,
      ("llama3", factor, low_freq_factor, high_freq_factor, original_max)
        → Llama-3.1 frequency-band scaling (matches the HF implementation:
        low-frequency bands divided by factor, high-frequency bands kept,
        the middle band smoothly interpolated),
      ("yarn", factor, beta_fast, beta_slow, original_max, mscale, mscale_all_dim)
        → YaRN (HF's ``_compute_yarn_parameters``, truncated correction
        range): pair i below ``low`` keeps its frequency, above ``high`` has
        it divided by factor, a linear ramp between; cos and sin are scaled
        by ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``.
    """
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    t = jnp.arange(max_seq, dtype=jnp.float32)
    attention_factor = 1.0
    if scaling:
        kind = scaling[0]
        if kind == "linear":
            t = t / float(scaling[1])
        elif kind == "llama3":
            factor, lo, hi, orig = (float(s) for s in scaling[1:])
            wavelen = 2.0 * jnp.pi / inv_freq
            smooth = (orig / wavelen - lo) / (hi - lo)
            scaled = jnp.where(
                wavelen > orig / lo,                       # low-frequency band
                inv_freq / factor,
                jnp.where(
                    wavelen < orig / hi,                   # high-frequency band
                    inv_freq,
                    (1.0 - smooth) * inv_freq / factor + smooth * inv_freq,
                ),
            )
            inv_freq = scaled
        elif kind == "yarn":
            factor, fast, slow, orig, mscale, mscale_all = (float(s) for s in scaling[1:])
            low, high = yarn_correction_range(dim, theta, fast, slow, orig)
            ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0)
            inv_freq = inv_freq * (1.0 - ramp) + inv_freq / factor * ramp
            attention_factor = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all)
        else:
            raise ValueError(f"unknown rope scaling kind {kind!r} (linear|llama3|yarn)")
    freqs = jnp.outer(t, inv_freq)
    if attention_factor != 1.0:
        return jnp.cos(freqs) * attention_factor, jnp.sin(freqs) * attention_factor
    return jnp.cos(freqs), jnp.sin(freqs)


def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN's magnitude correction: 0.1 mscale ln(scale) + 1 (1 for no stretch)."""
    return 1.0 if scale <= 1.0 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_correction_range(dim: int, theta: float, beta_fast: float, beta_slow: float, original_max: float) -> tuple[int, int]:
    """(low, high): the rotary pairs between which YaRN blends. Pair i turns
    ``original_max theta^(-2i/dim) / 2 pi`` times over the original context;
    ``low`` is the last pair that turns ``beta_fast`` times or more (floored),
    ``high`` the first that turns ``beta_slow`` times or fewer (ceiled)."""
    at = lambda turns: dim * math.log(original_max / (turns * 2.0 * math.pi)) / (2.0 * math.log(theta))
    return max(math.floor(at(beta_fast)), 0), min(math.ceil(at(beta_slow)), dim - 1)


def apply_rope(
    x: jax.Array, cos: jax.Array, sin: jax.Array, positions: jax.Array | None = None
) -> jax.Array:
    """Rotary embedding; x: [B, H, T, D], tables [>=T, D//2].

    ``positions``: [T] shared positions, or [B, T] per-batch positions
    (packed sequences restart positions at each segment)."""
    T = x.shape[-2]
    if positions is None:
        c, s = cos[:T], sin[:T]
    else:
        c, s = cos[positions], sin[positions]
        if positions.ndim == 2:  # [B, T, D/2] → broadcast over heads
            c, s = c[:, None], s[:, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    """SwiGLU MLP: (silu(x@Wg) * (x@Wu)) @ Wd, bf16-friendly. The two
    products carry names for the remat ladder (ops/attention.REMAT_LADDER)."""
    g = jax.nn.silu(checkpoint_name(jnp.einsum("...d,df->...f", x, w_gate), "ffn_gate"))
    u = checkpoint_name(jnp.einsum("...d,df->...f", x, w_up), "ffn_up")
    return jnp.einsum("...f,fd->...d", g * u, w_down)


def gelu_mlp(x: jax.Array, w_in: jax.Array, b_in: jax.Array, w_out: jax.Array, b_out: jax.Array) -> jax.Array:
    h = jax.nn.gelu(jnp.einsum("...d,df->...f", x, w_in) + b_in)
    return jnp.einsum("...f,fd->...d", h, w_out) + b_out


def cross_entropy_loss(
    logits: jax.Array, targets: jax.Array, ignore_index: int = -100
) -> tuple[jax.Array, jax.Array]:
    """Token-mean CE in f32; returns (loss, n_valid_tokens)."""
    mask = targets != ignore_index
    safe_targets = jnp.where(mask, targets, 0)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe_targets[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    n = jnp.maximum(mask.sum(), 1)
    return nll.sum() / n, n


def chunked_cross_entropy_loss(
    x: jax.Array,
    lm_head: jax.Array,
    targets: jax.Array,
    ignore_index: int = -100,
    chunk: int = 512,
    mesh=None,
) -> tuple[jax.Array, jax.Array]:
    """Fused lm-head + CE that never materializes the [B, T, V] logits.

    A ``lax.scan`` over sequence chunks computes each chunk's logits, its
    logsumexp, and the gold logit, keeping only O(B·chunk·V) live. Under
    differentiation the same scan takes the gradient while it has the logits
    in hand (``softmax - onehot``, and the two products that carry it to ``x``
    and ``lm_head``): three products with a vocabulary dimension a chunk, none
    replayed, and the backward only scales what the forward kept. At
    Llama-scale vocab this removes the largest activation in the train step
    (the bf16 logits + f32 softmax temps), which is what bounds the per-chip
    batch size.

    x: [B, T, D] final hidden states; lm_head: [D, V]; targets: [B, T].
    ``mesh``: the mesh ``x``'s batch is sharded over (data x fsdp), if any: a
    chip then sums the head's gradient over its own rows of the batch through
    the whole scan, and one reduce-scatter after it leaves each chip its rows
    of the sum. None, a batch axis of one device, or axes that do not divide
    B and D (a batch the partitioner cannot shard either) is the same scan bare.
    """
    B, T, D = x.shape
    chunk = T if chunk <= 0 else min(chunk, T)
    pad = (-T) % chunk
    if pad:
        # pad to a chunk multiple with ignored targets: keeps the memory
        # bound AND the chunk-sized matmuls for awkward sequence lengths
        # (a divisor-based fallback would degenerate to tiny chunks)
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)), constant_values=ignore_index)
    n = jnp.maximum((targets != ignore_index).sum(), 1)
    return _head_loss(x, lm_head, targets, n, ignore_index, chunk, mesh), n


def _head_scan(x, lm_head, targets, n, ignore_index, chunk, mesh, with_grads):
    """The scan over chunks behind ``chunked_cross_entropy_loss``: ``(loss,)``,
    the mean nll over ``n`` targets, or ``with_grads`` ``(loss, dx, dw)`` with
    its gradients against ``x`` (in x's type) and ``lm_head`` (summed over the
    chunks in float32, then in lm_head's type)."""
    B, T, D = x.shape
    inv_n = 1.0 / n.astype(jnp.float32)
    # the batch's axes of the mesh, where each chip can be handed whole rows of
    # the batch and whole rows of the reduced gradient
    axes = () if mesh is None else tuple(a for a in ("data", "fsdp") if mesh.shape.get(a, 1) > 1)
    shards = math.prod(mesh.shape[a] for a in axes)
    if B % shards or D % shards:
        # a batch with fewer rows than the axes have chips (the loop hands one
        # over as it is, and the partitioner replicates it): the bare scan
        axes = ()

    def local(x, lm_head, targets, inv_n):
        def chunk_nll(carry, at):
            total, *grads = carry
            xc = jax.lax.dynamic_slice_in_dim(x, at, chunk, axis=1)
            tc = jax.lax.dynamic_slice_in_dim(targets, at, chunk, axis=1)
            logits = jnp.einsum(
                "bcd,dv->bcv", xc, lm_head, preferred_element_type=jnp.float32
            )
            mask = tc != ignore_index
            safe = jnp.where(mask, tc, 0)
            logz = jax.nn.logsumexp(logits, axis=-1)
            # gold logit via masked reduce (fuses; no gather, so vocab-parallel
            # TP shards reduce locally and psum instead of rematerializing)
            iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
            gold_at = iota == safe[..., None]
            gold = jnp.sum(jnp.where(gold_at, logits, 0.0), axis=-1)
            total = total + jnp.sum((logz - gold) * mask)
            if not with_grads:
                return (total,), None
            dw, dx = grads
            # d(mean nll)/dlogits, handed to its two products in the activations'
            # type, and as an array: fused into the products as the compiler
            # would, dW's forms it again for every tile of its output (42.6 ms a
            # step at [2, 8192, 4096] x [4096, 32000] where the product of an
            # array takes 28.5, and dx's 27.0 for 22.4: my chip run, PR 51)
            dlogits = jax.lax.optimization_barrier((
                (jnp.exp(logits - logz[..., None]) - gold_at) * (mask * inv_n)[..., None]
            ).astype(x.dtype))
            dxc = jnp.einsum(
                "bcv,dv->bcd", dlogits, lm_head, preferred_element_type=jnp.float32
            ).astype(x.dtype)
            dw = dw + jnp.einsum(
                "bcd,bcv->dv", xc, dlogits, preferred_element_type=jnp.float32
            )
            return (total, dw, jax.lax.dynamic_update_slice_in_dim(dx, dxc, at, axis=1)), None

        init = (jnp.float32(0.0),)
        if with_grads:
            init += (jnp.zeros(lm_head.shape, jnp.float32), jnp.zeros_like(x))
        with jax.named_scope("head_loss"):
            (total, *grads), _ = jax.lax.scan(chunk_nll, init, jnp.arange(0, T, chunk))
        if axes:
            total = jax.lax.psum(total, axes)
        if not with_grads:
            return (total,)
        dw, dx = grads
        if axes:  # the one exchange of anything head-sized: each chip keeps its rows
            dw = jax.lax.psum_scatter(dw, axes, scatter_dimension=0, tiled=True)
        # The float32 sum ends HERE: left to itself the compiler folds the cast
        # into the optimizer's update and keeps the float32 array through every
        # layer's backward (+0.26 GB at the step's reported peak on one chip),
        # and runs the reduction behind the last layer's backward with the whole
        # unreduced sum still alive (+0.39 GB allocated a chip over four). dx,
        # which that backward starts from, waits for both (compile-only, PR 51).
        dx, dw = jax.lax.optimization_barrier((dx, dw.astype(lm_head.dtype)))
        return total, dx, dw

    if axes:
        rows = P(axes)
        local = jax.shard_map(
            local, mesh=mesh, in_specs=(rows, P(), rows, P()),
            out_specs=(P(), rows, rows) if with_grads else (P(),),
            axis_names=set(axes), check_vma=False,
        )
    total, *grads = local(x, lm_head, targets, inv_n)
    return (total / n, *grads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _head_loss(x, lm_head, targets, n, ignore_index, chunk, mesh):
    return _head_scan(x, lm_head, targets, n, ignore_index, chunk, mesh, with_grads=False)[0]


def _head_loss_fwd(x, lm_head, targets, n, ignore_index, chunk, mesh):
    loss, dx, dw = _head_scan(x, lm_head, targets, n, ignore_index, chunk, mesh, with_grads=True)
    return loss, (dx, dw)


def _head_loss_bwd(ignore_index, chunk, mesh, kept, g):
    dx, dw = kept
    return (g * dx).astype(dx.dtype), (g * dw).astype(dw.dtype), None, None


_head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)
