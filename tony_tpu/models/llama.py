"""Llama-family decoder (the flagship model; BASELINE.json config #4).

Pure-functional JAX: params are a plain pytree with **stacked layers**
(leading dim L on every block param) so the forward pass is a single
``lax.scan`` — one compiled block regardless of depth — and pipeline
parallelism can split the same stacked dim over the ``stage`` axis.

Parallelism (SURVEY.md §2.5 rebuild plan):
- FSDP: weights sharded over ``fsdp`` (all-gather on use via XLA propagation)
- TP: Megatron-style — qkv/gate/up column-parallel over ``model``, wo/down
  row-parallel; vocab-parallel embedding + lm head
- CP: sequence dim over ``context`` with ring attention (parallel/context.py)
- bf16 params/activations, f32 norm+softmax accumulation, optional remat
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from tony_tpu.ops import attention as attn_ops
from tony_tpu.ops import layers as L
from tony_tpu.parallel.context import ring_attention
from tony_tpu.parallel.sharding import ShardingRules, constrain

BATCH_AXES = ("data", "fsdp")


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128_256
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14_336
    max_seq: int = 8192
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    remat: bool = True
    #: what the scanned block saves for its backward: a rung of
    #: ops/attention.REMAT_LADDER (a tuple of names) or auto | full | flash |
    #: dots. "auto": the train loop chooses the rung from the device's memory
    #: (train/trainer.py); anywhere else it is "full"
    remat_policy: str | tuple = "auto"
    attn_impl: str = "auto"   # auto | flash | reference
    cp_impl: str = "xla"      # context parallel: xla (ppermute ring) | ulysses (all-to-all)
    ce_chunk: int = 512       # fused lm-head+CE chunk length; 0 = materialize logits
    sliding_window: int = 0   # >0: Mistral/Mixtral-style sliding-window attention
    rope_scaling: tuple = ()  # () | ("linear", f) | ("llama3", f, lo, hi, orig) — see ops/layers.rope_frequencies

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    def num_params(self) -> int:
        D, F, V, Dh = self.d_model, self.d_ff, self.vocab_size, self.head_dim
        per_layer = (
            D * self.n_heads * Dh            # wq
            + 2 * D * self.n_kv_heads * Dh   # wk, wv
            + self.n_heads * Dh * D          # wo
            + 3 * D * F                      # gate, up, down
            + 2 * D                          # norms
        )
        return V * D + self.n_layers * per_layer + D + D * V

    def flops_per_token(self) -> int:
        """Training FLOPs/token — the one shared formula (train/metrics.py):
        6N + causal-attention term 12·L·D·T/2."""
        from tony_tpu.train.metrics import transformer_flops_per_token

        return transformer_flops_per_token(
            self.num_params(), self.n_layers, self.d_model, self.max_seq, training=True
        )


# -- presets (BASELINE.json configs) ----------------------------------------
LLAMA3_8B = LlamaConfig()
LLAMA_1B = LlamaConfig(
    vocab_size=32_000, d_model=2048, n_layers=16, n_heads=16, n_kv_heads=8,
    d_ff=5504, max_seq=2048,
)
LLAMA_TINY = LlamaConfig(
    vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=128, max_seq=128, remat=False, attn_impl="reference",
)

PRESETS = {"llama3-8b": LLAMA3_8B, "llama-1b": LLAMA_1B, "tiny": LLAMA_TINY}


def serving_programs(cfg: LlamaConfig, kv: str):
    """What the serving engine asks a model module for (models/serving.py)."""
    from tony_tpu.models.serving import llama_programs

    return llama_programs(cfg, kv)


def init(key: jax.Array, cfg: LlamaConfig) -> dict:
    """Initialize the parameter pytree (truncated-normal fan-in scaling)."""
    D, F, V = cfg.d_model, cfg.d_ff, cfg.vocab_size
    Dh, H, Hkv, Lyr = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads, cfg.n_layers
    dt = cfg.jdtype
    ks = jax.random.split(key, 9)

    def norm_init(*shape):
        return jnp.ones(shape, dt)

    def dense(k, *shape, fan_in):
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32) * fan_in**-0.5).astype(dt)

    return {
        "embed": dense(ks[0], V, D, fan_in=1.0),
        "layers": {
            "attn_norm": norm_init(Lyr, D),
            "wq": dense(ks[1], Lyr, D, H * Dh, fan_in=D),
            "wk": dense(ks[2], Lyr, D, Hkv * Dh, fan_in=D),
            "wv": dense(ks[3], Lyr, D, Hkv * Dh, fan_in=D),
            "wo": dense(ks[4], Lyr, H * Dh, D, fan_in=H * Dh),
            "mlp_norm": norm_init(Lyr, D),
            "w_gate": dense(ks[5], Lyr, D, F, fan_in=D),
            "w_up": dense(ks[6], Lyr, D, F, fan_in=D),
            "w_down": dense(ks[7], Lyr, F, D, fan_in=F),
        },
        "final_norm": norm_init(D),
        "lm_head": dense(ks[8], D, V, fan_in=D),  # independent of embed (not tied)
    }


def sharding_rules(cfg: LlamaConfig) -> ShardingRules:
    """FSDP × TP rules (stacked leading layer dim never sharded here; the
    pipeline module re-shards it over 'stage')."""
    return ShardingRules([
        (r"embed", P("model", "fsdp")),                  # vocab-parallel
        (r"layers/(wq|wk|wv|w_gate|w_up)", P(None, "fsdp", "model")),
        (r"layers/(wo|w_down)", P(None, "model", "fsdp")),
        (r"layers/.*norm", P(None, None)),
        (r"final_norm", P(None)),
        (r"lm_head", P("fsdp", "model")),
    ])


def _attention(q, k, v, cfg: LlamaConfig, mesh, segment_ids=None) -> jax.Array:
    """Dispatch: context-parallel attention (cfg.cp_impl: XLA ring or
    Ulysses all-to-all) when the context axis is real, else fused
    single-device MHA.

    q: [B, H, T, Dh]; k/v: [B, Hkv, T, Dh]; segment_ids [B, T] (packing).
    """
    if cfg.cp_impl not in ("xla", "ulysses"):
        raise ValueError(f"cp_impl must be 'xla' or 'ulysses', got {cfg.cp_impl!r}")
    if mesh is not None and mesh.shape.get("context", 1) > 1:
        if segment_ids is not None:
            raise ValueError(
                "context parallelism does not compose with sequence packing "
                "(segment_ids): neither the ring nor Ulysses carries a segment table"
            )
        if cfg.sliding_window > 0:
            raise ValueError(
                "context parallelism does not compose with sliding_window: "
                "neither the ring nor Ulysses masks a band"
            )
        n_rep = cfg.n_heads // cfg.n_kv_heads
        spec = P(None, None, "context", None)
        if cfg.cp_impl == "ulysses":
            # all-to-all seq↔head reshard: cheaper collectives than the ring
            # when n_heads >= context degree (docs/parallelism.md). KV stays
            # at Hkv width on the wire when it divides the context degree
            # (mha's GQA aliasing then applies); otherwise broadcast first.
            from tony_tpu.parallel.context import ulysses_attention

            cp = mesh.shape["context"]
            if cfg.n_heads % cp:
                raise ValueError(
                    f"cp_impl='ulysses' needs n_heads {cfg.n_heads} divisible "
                    f"by the context degree {cp} (use the 'xla' ring)"
                )
            if cfg.n_kv_heads % cp:
                k = attn_ops.repeat_kv(k, n_rep)
                v = attn_ops.repeat_kv(v, n_rep)
            fn = partial(
                ulysses_attention, axis_name="context",
                attn_fn=partial(attn_ops.mha, causal=True, impl=cfg.attn_impl),
            )
        else:
            k = attn_ops.repeat_kv(k, n_rep)
            v = attn_ops.repeat_kv(v, n_rep)
            fn = partial(ring_attention, axis_name="context", causal=True)
        ring = jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            axis_names={"context"},
            check_vma=False,
        )
        return ring(q, k, v)
    return attn_ops.mha_on_mesh(
        q, k, v, mesh=mesh, causal=True, impl=cfg.attn_impl,
        segment_ids=segment_ids, window=cfg.sliding_window,
    )


def mask_packed_targets(tokens: jax.Array, seg: jax.Array | None):
    """Shared packed-batch target masking (llama + mixtral): next-token
    pairs must stay within one segment, and segment 0 (padding) never
    contributes loss. Returns (targets [B, T], seg_in [B, T] or None)."""
    targets = tokens[:, 1:]
    if seg is None:
        return targets, None
    ok = (seg[:, 1:] == seg[:, :-1]) & (seg[:, 1:] != 0)
    return jnp.where(ok, targets, -100), seg[:, :-1]


def embed_lookup(embed: jax.Array, tokens: jax.Array, mesh=None) -> jax.Array:
    """Embedding lookup that compiles cleanly on every mesh.

    Whenever the activation sharding spans two or more mesh axes (hybrid
    data×fsdp, or fsdp×tp×cp), XLA's gather-op sharding cannot move the
    take's output between the table's layout and the batch layout and
    falls back to "involuntary full rematerialization"
    (replicate-then-reshard) in fwd AND bwd. A one-hot dot has native
    GSPMD sharding rules — vocab contraction over the 'model' shards, D
    stays on fsdp, batch stays put — at the FLOP cost of one extra
    lm-head-sized matmul, so it's used ONLY on those multi-axis meshes; a
    single sharded axis (e.g. the pure-FSDP 8B plan) and the unsharded
    case keep the plain take, whose transition XLA handles cleanly.
    """
    if mesh is not None:
        active = sum(
            1 for a in ("data", "fsdp", "model", "context") if mesh.shape.get(a, 1) > 1
        )
        if active >= 2:
            onehot = jax.nn.one_hot(tokens, embed.shape[0], dtype=embed.dtype)
            return jnp.einsum("btv,vd->btd", onehot, embed)
    return jnp.take(embed, tokens, axis=0)


def segment_positions(segment_ids: jax.Array) -> jax.Array:
    """[B, T] per-segment positions (0-based, restarting at each segment
    boundary) for RoPE on packed batches."""
    B, T = segment_ids.shape
    idx = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    is_start = jnp.concatenate(
        [jnp.ones((B, 1), bool), segment_ids[:, 1:] != segment_ids[:, :-1]], axis=1
    )
    start = jax.lax.cummax(jnp.where(is_start, idx, 0), axis=1)
    return idx - start


def _block(
    x: jax.Array, lp: dict, cos, sin, cfg: LlamaConfig, mesh,
    segment_ids=None, positions=None,
) -> tuple[jax.Array, None]:
    """One decoder block (pre-norm attention + SwiGLU), scan-compatible.
    Shared by the flat layer scan (hidden_states) and the pipeline stage
    body (pp_loss_fn, where mesh is None — stages run per-device)."""
    B, T = x.shape[0], x.shape[1]
    Dh, H, Hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    act_spec = P(BATCH_AXES, "context", None)
    h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q = jnp.einsum("btd,dh->bth", h, lp["wq"]).reshape(B, T, H, Dh).transpose(0, 2, 1, 3)
    k = jnp.einsum("btd,dh->bth", h, lp["wk"]).reshape(B, T, Hkv, Dh).transpose(0, 2, 1, 3)
    v = jnp.einsum("btd,dh->bth", h, lp["wv"]).reshape(B, T, Hkv, Dh).transpose(0, 2, 1, 3)
    q = L.apply_rope(q, cos, sin, positions=positions)
    k = L.apply_rope(k, cos, sin, positions=positions)
    # named for the remat ladder (ops/attention.REMAT_LADDER; so are swiglu's
    # two products): a name is an identity that a policy can pin
    q, k, v = (checkpoint_name(a, "attn_qkv") for a in (q, k, v))
    o = _attention(q, k, v, cfg, mesh, segment_ids=segment_ids)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, H * Dh)
    x = x + jnp.einsum("bth,hd->btd", o, lp["wo"])
    if mesh is not None:
        x = constrain(x, mesh, act_spec)
    x = checkpoint_name(x, "attn_res")
    h = L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    x = x + L.swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
    if mesh is not None:
        x = constrain(x, mesh, act_spec)
    return x, None


def hidden_states(
    params: dict, tokens: jax.Array, cfg: LlamaConfig, mesh=None, segment_ids=None
) -> jax.Array:
    """tokens [B, T] int32 → final-norm hidden states [B, T, D].

    ``segment_ids`` [B, T] enables packed-sequence training: attention is
    confined within segments (flash-kernel-native masking) and RoPE
    positions restart at every segment boundary."""
    T = tokens.shape[1]
    cos, sin = L.rope_frequencies(cfg.head_dim, T, cfg.rope_theta, cfg.rope_scaling)
    positions = segment_positions(segment_ids) if segment_ids is not None else None

    x = embed_lookup(params["embed"], tokens, mesh)
    if mesh is not None:
        x = constrain(x, mesh, P(BATCH_AXES, "context", None))

    x, _ = attn_ops.scan_blocks(
        partial(_block, cos=cos, sin=sin, cfg=cfg, mesh=mesh,
                segment_ids=segment_ids, positions=positions),
        x, params["layers"], cfg.remat, cfg.remat_policy,
    )

    return L.rms_norm(x, params["final_norm"], cfg.norm_eps)


def pp_loss_fn(
    params: dict, batch: dict, cfg: LlamaConfig, mesh, num_microbatches: int = 2
) -> tuple[jax.Array, dict]:
    """TEACHING-PATH pipeline loss (GPipe schedule + autodiff): the stacked
    layer dim splits into equal-depth stages over the mesh's ``stage`` axis
    (parallel/pipeline.spmd_pipeline); embedding and the (chunked) CE head
    run outside the pipeline, replicated over stages.

    Production training uses ``pp_value_and_grad`` (1F1B) — the train loop
    only ever routes there. This path stays as the independently-verifiable
    spec the 1F1B parity tests compare against: microbatches enter
    REPLICATED along data/fsdp (no DP speedup), the output bank broadcasts
    to every stage, and neither packing nor a context axis composes.
    """
    from tony_tpu.parallel.pipeline import spmd_pipeline, split_layers_into_stages

    S = mesh.shape.get("stage", 1)
    if S <= 1:
        return loss_fn(params, batch, cfg, mesh)
    if mesh.shape.get("context", 1) > 1:
        raise ValueError("pp_loss_fn does not compose with a context axis")
    if "segment_ids" in batch:
        raise ValueError(
            "pp_loss_fn does not support packed batches (segment_ids) yet — "
            "silently ignoring them would train across document boundaries"
        )
    tokens = batch["tokens"]
    T = tokens.shape[1] - 1
    cos, sin = L.rope_frequencies(cfg.head_dim, T, cfg.rope_theta, cfg.rope_scaling)
    x = jnp.take(params["embed"], tokens[:, :-1], axis=0)

    block_fn = attn_ops.remat_block(
        partial(_block, cos=cos, sin=sin, cfg=cfg, mesh=None),
        cfg.remat, cfg.remat_policy,
    )

    def stage_fn(stage_lp, h):
        h, _ = jax.lax.scan(block_fn, h, stage_lp)
        return h

    stages = split_layers_into_stages(params["layers"], S)
    x = spmd_pipeline(stage_fn, stages, x, mesh=mesh, num_microbatches=num_microbatches)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    # always the fused CE (ce_chunk<=0 → one full-length chunk in the
    # callee): the PP path never materializes [B, T, V] logits
    loss, n = L.chunked_cross_entropy_loss(
        x, params["lm_head"], tokens[:, 1:], chunk=cfg.ce_chunk
    )
    return loss, {"loss": loss, "tokens": n}


def pp_value_and_grad(
    params: dict, batch: dict, cfg: LlamaConfig, mesh, num_microbatches: int = 2,
    wire_dtype=jnp.bfloat16, num_chunks: int = 1,
) -> tuple[jax.Array, dict, dict]:
    """1F1B pipeline train-step core: ``(loss, metrics, grads)`` with grads
    shaped exactly like ``params``.

    The hand-scheduled backward (parallel/pipeline.spmd_pipeline_1f1b)
    interleaves each microbatch's backward with later microbatches' forwards,
    bounding live activations per stage at O(S) microbatches instead of the
    GPipe path's O(M); the CE head runs inside the last stage's tick behind
    a ``lax.cond`` (other stages pay none of its FLOPs), and the microbatch
    batch dim shards over data/fsdp. Packed batches (segment_ids) are
    supported: attention confinement, per-segment RoPE, and boundary target
    masking all apply per microbatch. Use via ``make_pp_train_step``
    (train/trainer.py).
    """
    from tony_tpu.parallel.pipeline import spmd_pipeline_1f1b, split_layers_into_stages

    S = mesh.shape.get("stage", 1)
    if S <= 1:
        loss_and_grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg, mesh), has_aux=True
        )(params)
        (loss, metrics), grads = loss_and_grads
        return loss, metrics, grads
    if mesh.shape.get("context", 1) > 1:
        raise ValueError("pipeline parallelism does not compose with a context axis")
    tokens = batch["tokens"]
    T = tokens.shape[1] - 1
    cos, sin = L.rope_frequencies(cfg.head_dim, T, cfg.rope_theta, cfg.rope_scaling)

    def _mb_ctx(mb):
        seg = mb.get("segment_ids")
        seg_in = seg[:, :-1] if seg is not None else None
        positions = segment_positions(seg_in) if seg_in is not None else None
        return seg_in, positions

    def stage_fn(stage_lp, h, mb):
        seg_in, positions = _mb_ctx(mb)
        block_fn = attn_ops.remat_block(
            partial(_block, cos=cos, sin=sin, cfg=cfg, mesh=None,
                    segment_ids=seg_in, positions=positions),
            cfg.remat, cfg.remat_policy,
        )
        h, _ = jax.lax.scan(block_fn, h, stage_lp)
        return h

    def embed_fn(embed_p, mb):
        return jnp.take(embed_p, mb["tokens"][:, :-1], axis=0)

    def loss_head_fn(head_p, y, mb):
        targets, _ = mask_packed_targets(mb["tokens"], mb.get("segment_ids"))
        x = L.rms_norm(y, head_p["final_norm"], cfg.norm_eps)
        mean, n = L.chunked_cross_entropy_loss(
            x, head_p["lm_head"], targets, chunk=cfg.ce_chunk
        )
        # mean * n == the exact nll SUM even when n is the CE's >=1 clamp
        # (0/1 * 1 = 0); report the TRUE count so an all-pad microbatch
        # doesn't inflate the token total the grads divide by
        return mean * n, jnp.sum(targets != -100)

    pp_batch = {"tokens": tokens}
    if "segment_ids" in batch:
        pp_batch["segment_ids"] = batch["segment_ids"]
    head_params = {"final_norm": params["final_norm"], "lm_head": params["lm_head"]}
    if num_chunks > 1:
        from tony_tpu.parallel.pipeline import (
            spmd_pipeline_1f1b_interleaved,
            split_layers_into_chunks,
        )

        chunks = split_layers_into_chunks(params["layers"], S, num_chunks)
        nll, ntok, (dchunk, dembed, dhead) = spmd_pipeline_1f1b_interleaved(
            stage_fn, chunks, pp_batch, params["embed"], head_params,
            embed_fn, loss_head_fn,
            mesh=mesh, num_microbatches=num_microbatches, num_chunks=num_chunks,
            wire_dtype=wire_dtype, compute_dtype=cfg.jdtype,
        )
        loss = nll / jnp.maximum(ntok, 1.0)
        inv = 1.0 / jnp.maximum(ntok, 1.0)

        def unsplit(g, p):
            # [S, V, Lc, ...] grads → [L, ...] matching the stacked layout
            V = num_chunks
            r = g.reshape(S, V, -1, *p.shape[1:])
            r = r.transpose(1, 0, *range(2, r.ndim))  # [V, S, Lc, ...]
            return (r.reshape(cfg.n_layers, *p.shape[1:]) * inv).astype(p.dtype)

        d_layers = jax.tree.map(unsplit, dchunk, params["layers"])
        grads = {
            "embed": (dembed * inv).astype(params["embed"].dtype),
            "layers": d_layers,
            "final_norm": (dhead["final_norm"] * inv).astype(params["final_norm"].dtype),
            "lm_head": (dhead["lm_head"] * inv).astype(params["lm_head"].dtype),
        }
        return loss, {"loss": loss, "tokens": ntok}, grads
    stages = split_layers_into_stages(params["layers"], S)
    nll, ntok, _, (dstage, dembed, dhead) = spmd_pipeline_1f1b(
        stage_fn, stages, pp_batch, params["embed"], head_params,
        embed_fn, loss_head_fn,
        mesh=mesh, num_microbatches=num_microbatches, wire_dtype=wire_dtype,
        compute_dtype=cfg.jdtype,
    )
    loss = nll / jnp.maximum(ntok, 1.0)
    inv = 1.0 / jnp.maximum(ntok, 1.0)
    d_layers = jax.tree.map(
        lambda g, p: (g.reshape(cfg.n_layers, *g.shape[2:]) * inv).astype(p.dtype),
        dstage, params["layers"],
    )
    grads = {
        "embed": (dembed * inv).astype(params["embed"].dtype),
        "layers": d_layers,
        "final_norm": (dhead["final_norm"] * inv).astype(params["final_norm"].dtype),
        "lm_head": (dhead["lm_head"] * inv).astype(params["lm_head"].dtype),
    }
    return loss, {"loss": loss, "tokens": ntok}, grads


def forward(
    params: dict, tokens: jax.Array, cfg: LlamaConfig, mesh=None, segment_ids=None
) -> jax.Array:
    """tokens [B, T] int32 → logits [B, T, V]."""
    x = hidden_states(params, tokens, cfg, mesh, segment_ids=segment_ids)
    logits = jnp.einsum("btd,dv->btv", x, params["lm_head"])
    if mesh is not None:
        logits = constrain(logits, mesh, P(BATCH_AXES, "context", None))
    return logits


def loss_fn(params: dict, batch: dict, cfg: LlamaConfig, mesh=None) -> tuple[jax.Array, dict]:
    """batch: {"tokens": [B, T+1], optional "segment_ids": [B, T+1]} →
    next-token CE loss.

    With ``cfg.ce_chunk > 0`` the lm-head matmul and CE are fused per
    sequence chunk (ops/layers.chunked_cross_entropy_loss) so the [B, T, V]
    logits never exist — the activation that otherwise bounds batch size.

    With ``segment_ids`` (packed sequences), attention and RoPE respect
    segment boundaries and the cross-boundary targets (a segment's last
    token predicting the NEXT segment's first) are masked out of the loss.
    """
    tokens = batch["tokens"]
    targets, seg_in = mask_packed_targets(tokens, batch.get("segment_ids"))
    if cfg.ce_chunk > 0:
        x = hidden_states(params, tokens[:, :-1], cfg, mesh, segment_ids=seg_in)
        loss, n = L.chunked_cross_entropy_loss(
            x, params["lm_head"], targets, chunk=cfg.ce_chunk, mesh=mesh
        )
    else:
        logits = forward(params, tokens[:, :-1], cfg, mesh, segment_ids=seg_in)
        loss, n = L.cross_entropy_loss(logits, targets)
    return loss, {"loss": loss, "tokens": n}


def synthetic_batch(key: jax.Array, batch_size: int, seq_len: int, cfg: LlamaConfig) -> dict:
    return {
        "tokens": jax.random.randint(key, (batch_size, seq_len + 1), 0, cfg.vocab_size, jnp.int32)
    }


def config_from_dict(d: dict) -> LlamaConfig:
    if isinstance(d, str):
        return PRESETS[d]
    fields = {f.name for f in dataclasses.fields(LlamaConfig)}
    return dataclasses.replace(
        PRESETS.get(d.get("preset", ""), LlamaConfig()),
        **{k: v for k, v in d.items() if k in fields},
    )
