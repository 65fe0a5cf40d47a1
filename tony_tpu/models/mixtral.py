"""Mixtral-style sparse-MoE decoder (BASELINE.json config #5).

Llama backbone (RMSNorm / RoPE / GQA attention, scanned stacked layers) with
the dense FFN replaced by a top-2-of-E SwiGLU mixture routed per token
(parallel/expert.py); expert weights shard over the ``expert`` mesh axis.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tony_tpu.models import llama as llama_mod
from tony_tpu.ops import attention as attn_ops
from tony_tpu.ops import layers as L
from tony_tpu.parallel.expert import MoEConfig, moe_ffn
from tony_tpu.parallel.sharding import ShardingRules, constrain

BATCH_AXES = llama_mod.BATCH_AXES


@dataclass(frozen=True)
class MixtralConfig(llama_mod.LlamaConfig):
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    moe_dispatch: str = "ragged"  # ragged (grouped GEMM / fused kernel) | ragged_xla | gather | dense
    aux_loss_coef: float = 1e-2   # load-balance loss weight
    router_z_coef: float = 1e-3   # router z-loss weight

    @property
    def moe(self) -> MoEConfig:
        return MoEConfig(
            self.num_experts, self.top_k, self.capacity_factor,
            router_z_coef=self.router_z_coef, aux_loss_coef=self.aux_loss_coef,
            dispatch=self.moe_dispatch,
        )

    def num_params(self) -> int:
        base = super().num_params()
        D, F = self.d_model, self.d_ff
        dense_ffn = self.n_layers * 3 * D * F
        moe_ffn_params = self.n_layers * (self.num_experts * 3 * D * F + D * self.num_experts)
        return base - dense_ffn + moe_ffn_params

    def active_params(self) -> int:
        """Params touched per token (top-k of E experts) — the MFU basis."""
        D, F = self.d_model, self.d_ff
        dense_ffn = self.n_layers * 3 * D * F
        active_ffn = self.n_layers * (self.top_k * 3 * D * F + D * self.num_experts)
        return super().num_params() - dense_ffn + active_ffn

    def flops_per_token(self) -> int:
        from tony_tpu.train.metrics import transformer_flops_per_token

        return transformer_flops_per_token(
            self.active_params(), self.n_layers, self.d_model, self.max_seq, training=True
        )


MIXTRAL_8X7B = MixtralConfig(
    vocab_size=32_000, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8,
    d_ff=14_336, max_seq=8192, rope_theta=1e6, num_experts=8, top_k=2,
    # NO sliding window: released Mixtral-8x7B checkpoints set
    # sliding_window=null (fully dense over 32k ctx); only Mistral-7B uses
    # the 4096 SWA band. SWA stays available via config / convert for
    # Mistral-style checkpoints.
    sliding_window=0,
)
MIXTRAL_TINY = MixtralConfig(
    vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
    max_seq=128, num_experts=4, top_k=2, remat=False, attn_impl="reference",
)
PRESETS = {"mixtral-8x7b": MIXTRAL_8X7B, "tiny": MIXTRAL_TINY}


def init(key: jax.Array, cfg: MixtralConfig) -> dict:
    D, F, E, Lyr = cfg.d_model, cfg.d_ff, cfg.num_experts, cfg.n_layers
    dt = cfg.jdtype
    base = llama_mod.init(key, cfg)
    ks = jax.random.split(jax.random.fold_in(key, 1), 4)

    def dense(k, *shape, fan_in):
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32) * fan_in**-0.5).astype(dt)

    layers = dict(base["layers"])
    for gone in ("w_gate", "w_up", "w_down"):
        del layers[gone]
    layers.update(
        router=dense(ks[0], Lyr, D, E, fan_in=D).astype(jnp.float32),
        we_gate=dense(ks[1], Lyr, E, D, F, fan_in=D),
        we_up=dense(ks[2], Lyr, E, D, F, fan_in=D),
        we_down=dense(ks[3], Lyr, E, F, D, fan_in=F),
    )
    base["layers"] = layers
    return base


def sharding_rules(cfg: MixtralConfig) -> ShardingRules:
    return ShardingRules([
        (r"embed", P("model", "fsdp")),
        (r"layers/(wq|wk|wv)", P(None, "fsdp", "model")),
        (r"layers/wo", P(None, "model", "fsdp")),
        (r"layers/router", P(None, None, None)),
        (r"layers/(we_gate|we_up)", P(None, "expert", "fsdp", "model")),
        (r"layers/we_down", P(None, "expert", "model", "fsdp")),
        (r"layers/.*norm", P(None, None)),
        (r"final_norm", P(None)),
        (r"lm_head", P("fsdp", "model")),
    ])


def _layer(
    x: jax.Array, lp: dict, cos, sin, cfg: MixtralConfig, mesh,
    segment_ids=None, positions=None, token_mask=None,
) -> tuple[jax.Array, dict]:
    """One Mixtral decoder layer (pre-norm GQA attention + MoE FFN) →
    (x, per-layer aux dict). Shared by the flat layer scan (hidden_states)
    and the 1F1B pipeline stage body (pp_value_and_grad, mesh=None)."""
    B, T = x.shape[0], x.shape[1]
    Dh, H, Hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    act_spec = P(BATCH_AXES, "context", None)
    h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q = jnp.einsum("btd,dh->bth", h, lp["wq"]).reshape(B, T, H, Dh).transpose(0, 2, 1, 3)
    k = jnp.einsum("btd,dh->bth", h, lp["wk"]).reshape(B, T, Hkv, Dh).transpose(0, 2, 1, 3)
    v = jnp.einsum("btd,dh->bth", h, lp["wv"]).reshape(B, T, Hkv, Dh).transpose(0, 2, 1, 3)
    q = L.apply_rope(q, cos, sin, positions=positions)
    k = L.apply_rope(k, cos, sin, positions=positions)
    o = llama_mod._attention(q, k, v, cfg, mesh, segment_ids=segment_ids)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, H * Dh)
    x = x + jnp.einsum("bth,hd->btd", o, lp["wo"])
    if mesh is not None:
        x = constrain(x, mesh, act_spec)
    h = L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    y, aux = moe_ffn(
        h, lp["router"], lp["we_gate"], lp["we_up"], lp["we_down"], cfg.moe,
        mesh, token_mask=token_mask,
    )
    x = x + y
    if mesh is not None:
        x = constrain(x, mesh, act_spec)
    return x, aux


def hidden_states(
    params: dict, tokens: jax.Array, cfg: MixtralConfig, mesh=None, segment_ids=None
) -> tuple[jax.Array, dict]:
    """tokens [B, T] → (final-norm hidden states [B, T, D], moe aux losses).

    ``segment_ids`` [B, T] (packed sequences): segment-confined attention +
    per-segment RoPE positions, same contract as llama.hidden_states."""
    T = tokens.shape[1]
    cos, sin = L.rope_frequencies(cfg.head_dim, T, cfg.rope_theta, cfg.rope_scaling)
    positions = (
        llama_mod.segment_positions(segment_ids) if segment_ids is not None else None
    )
    token_mask = (segment_ids != 0) if segment_ids is not None else None

    x = llama_mod.embed_lookup(params["embed"], tokens, mesh)
    if mesh is not None:
        x = constrain(x, mesh, P(BATCH_AXES, "context", None))

    def block(carry, lp):
        x, aux_acc = carry
        x, aux = _layer(
            x, lp, cos, sin, cfg, mesh,
            segment_ids=segment_ids, positions=positions, token_mask=token_mask,
        )
        aux_acc = {
            "moe_balance_loss": aux_acc["moe_balance_loss"] + aux["moe_balance_loss"],
            "moe_z_loss": aux_acc["moe_z_loss"] + aux["moe_z_loss"],
            "moe_dropped_frac": aux_acc["moe_dropped_frac"] + aux["moe_dropped_frac"] / cfg.n_layers,
        }
        return (x, aux_acc), None

    aux0 = {k: jnp.zeros((), jnp.float32) for k in ("moe_balance_loss", "moe_z_loss", "moe_dropped_frac")}
    (x, aux), _ = attn_ops.scan_blocks(block, (x, aux0), params["layers"], cfg.remat, cfg.remat_policy)

    return L.rms_norm(x, params["final_norm"], cfg.norm_eps), aux


def forward(
    params: dict, tokens: jax.Array, cfg: MixtralConfig, mesh=None, segment_ids=None
) -> tuple[jax.Array, dict]:
    """tokens [B, T] → (logits [B, T, V], moe aux losses summed over layers)."""
    x, aux = hidden_states(params, tokens, cfg, mesh, segment_ids=segment_ids)
    logits = jnp.einsum("btd,dv->btv", x, params["lm_head"])
    return logits, aux


def loss_fn(params: dict, batch: dict, cfg: MixtralConfig, mesh=None) -> tuple[jax.Array, dict]:
    """With ``cfg.ce_chunk > 0`` the lm-head + CE fuse per sequence chunk so
    the [B, T, V] logits never materialize; packed batches (segment_ids)
    get segment-confined attention and boundary/pad target masking (same
    scheme as llama.loss_fn)."""
    tokens = batch["tokens"]
    targets, seg_in = llama_mod.mask_packed_targets(tokens, batch.get("segment_ids"))
    if cfg.ce_chunk > 0:
        x, aux = hidden_states(params, tokens[:, :-1], cfg, mesh, segment_ids=seg_in)
        ce, n = L.chunked_cross_entropy_loss(
            x, params["lm_head"], targets, chunk=cfg.ce_chunk, mesh=mesh
        )
    else:
        logits, aux = forward(params, tokens[:, :-1], cfg, mesh, segment_ids=seg_in)
        ce, n = L.cross_entropy_loss(logits, targets)
    loss = ce + aux["moe_balance_loss"] + aux["moe_z_loss"]
    return loss, {"loss": loss, "ce_loss": ce, "tokens": n, **aux}


def pp_value_and_grad(
    params: dict, batch: dict, cfg: MixtralConfig, mesh, num_microbatches: int = 2,
    wire_dtype=jnp.bfloat16,
) -> tuple[jax.Array, dict, dict]:
    """1F1B pipeline train-step core for the MoE model: ``(loss, metrics,
    grads)``, grads shaped like ``params`` — the PP×EP deployment shape of
    an 8×7B (SURVEY.md §2.5 PP row; experts stay stage-local, so the ragged
    grouped-GEMM dispatch runs unsharded inside each stage).

    MoE aux losses (balance + z) thread through the hand-scheduled backward
    as a per-stage scalar with a matching cotangent seed
    (parallel/pipeline.spmd_pipeline_1f1b ``stage_has_aux``): the objective
    is ``CE_mean + aux_mean`` where aux is averaged over microbatches — the
    standard per-group approximation of the full-batch balance statistic.
    Packed batches (segment_ids) compose: confinement, per-segment RoPE,
    pad-aware routing, and boundary target masking all apply per microbatch.

    Wire-dtype note: the default bf16 wire quantizes each stage's input
    activations, which can flip near-tie top-k routing choices relative to
    an unpipelined f32 run — bounded routing jitter (equivalent to the
    bf16 activations every stage>0 layer already sees), not an error; pass
    ``wire_dtype=jnp.float32`` when bitwise routing stability matters.
    """
    from tony_tpu.parallel.pipeline import spmd_pipeline_1f1b, split_layers_into_stages

    S = mesh.shape.get("stage", 1)
    if S <= 1:
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg, mesh), has_aux=True
        )(params)
        return loss, metrics, grads
    if mesh.shape.get("context", 1) > 1:
        raise ValueError("pipeline parallelism does not compose with a context axis")
    if mesh.shape.get("expert", 1) > 1:
        raise ValueError(
            "stage_axis > 1 keeps experts stage-local (ragged dispatch inside "
            "each stage) — use an expert axis of 1 with pipeline parallelism"
        )
    tokens = batch["tokens"]
    T = tokens.shape[1] - 1
    cos, sin = L.rope_frequencies(cfg.head_dim, T, cfg.rope_theta, cfg.rope_scaling)

    def stage_fn(stage_lp, h, mb):
        seg = mb.get("segment_ids")
        seg_in = seg[:, :-1] if seg is not None else None
        positions = llama_mod.segment_positions(seg_in) if seg_in is not None else None
        token_mask = (seg_in != 0) if seg_in is not None else None

        def block(carry, lp):
            x, aux_acc = carry
            x, aux = _layer(
                x, lp, cos, sin, cfg, None,
                segment_ids=seg_in, positions=positions, token_mask=token_mask,
            )
            return (x, aux_acc + aux["moe_balance_loss"] + aux["moe_z_loss"]), None

        block_fn = attn_ops.remat_block(block, cfg.remat, cfg.remat_policy)
        (h, aux), _ = jax.lax.scan(block_fn, (h, jnp.zeros((), jnp.float32)), stage_lp)
        return h, aux

    def embed_fn(embed_p, mb):
        return jnp.take(embed_p, mb["tokens"][:, :-1], axis=0)

    def loss_head_fn(head_p, y, mb):
        targets, _ = llama_mod.mask_packed_targets(mb["tokens"], mb.get("segment_ids"))
        x = L.rms_norm(y, head_p["final_norm"], cfg.norm_eps)
        mean, n = L.chunked_cross_entropy_loss(
            x, head_p["lm_head"], targets, chunk=cfg.ce_chunk
        )
        # true count, not the CE's >=1 clamp: keeps ntok == ntok_pre so the
        # aux cotangent lands at exactly unit scale (see seed note below)
        return mean * n, jnp.sum(targets != -100)

    # the valid-target count is computable before the schedule runs; seeding
    # the aux cotangent with it makes the post-hoc /ntok division land the
    # aux gradients at exactly unit scale (see spmd_pipeline_1f1b docstring)
    targets_all, _ = llama_mod.mask_packed_targets(tokens, batch.get("segment_ids"))
    ntok_pre = jnp.sum(targets_all != -100).astype(jnp.float32)

    pp_batch = {"tokens": tokens}
    if "segment_ids" in batch:
        pp_batch["segment_ids"] = batch["segment_ids"]
    stages = split_layers_into_stages(params["layers"], S)
    head_params = {"final_norm": params["final_norm"], "lm_head": params["lm_head"]}
    nll, ntok, aux_total, (dstage, dembed, dhead) = spmd_pipeline_1f1b(
        stage_fn, stages, pp_batch, params["embed"], head_params,
        embed_fn, loss_head_fn,
        mesh=mesh, num_microbatches=num_microbatches, wire_dtype=wire_dtype,
        compute_dtype=cfg.jdtype, stage_has_aux=True, aux_seed_scale=ntok_pre,
    )
    ce = nll / jnp.maximum(ntok, 1.0)
    loss = ce + aux_total
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
    metrics = {"loss": loss, "ce_loss": ce, "tokens": ntok, "moe_aux_loss": aux_total}
    return loss, metrics, grads


synthetic_batch = llama_mod.synthetic_batch


def config_from_dict(d: dict | str) -> MixtralConfig:
    if isinstance(d, str):
        return PRESETS[d]
    fields = {f.name for f in dataclasses.fields(MixtralConfig)}
    return dataclasses.replace(
        PRESETS.get(d.get("preset", ""), MixtralConfig()),
        **{k: v for k, v in d.items() if k in fields},
    )


def serving_programs(cfg: MixtralConfig, kv: str):
    """The serving engine's programs (models/serving.py): the llama family's,
    whose decode step dispatches the FFN on the layer's keys."""
    return llama_mod.serving_programs(cfg, kv)
