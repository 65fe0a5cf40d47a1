"""BERT-style bidirectional encoder with an MLM head (BASELINE.json config #2
— the MultiWorkerMirrored-analog workload, here data/fsdp/tensor-parallel).

Same functional conventions as llama.py: stacked scanned layers, rule-based
sharding, f32 norm/softmax accumulation.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tony_tpu.ops import attention as attn_ops
from tony_tpu.ops import layers as L
from tony_tpu.parallel.sharding import ShardingRules, constrain

BATCH_AXES = ("data", "fsdp")


@dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30_522
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 3072
    max_seq: int = 512
    type_vocab: int = 2
    norm_eps: float = 1e-12
    dtype: str = "bfloat16"
    remat: bool = False
    attn_impl: str = "auto"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    def num_params(self) -> int:
        D, F, V = self.d_model, self.d_ff, self.vocab_size
        per_layer = 4 * D * D + 4 * D + 2 * D * F + D + F + 4 * D
        return (V + self.max_seq + self.type_vocab) * D + 2 * D + self.n_layers * per_layer + D * V + V

    def flops_per_token(self, masked_frac: float | None = None) -> int:
        """Training FLOPs/token (PaLM convention, as train/metrics.py);
        the attention term is NOT halved — bidirectional, no causal mask.
        With ``masked_frac``, the MLM-head matmul is counted only at the
        masked positions actually projected (the gathered-positions path)."""
        attn = 12 * self.n_layers * self.d_model * self.max_seq
        flops = 6 * self.num_params() + attn
        if masked_frac is not None:
            head = self.d_model * self.vocab_size
            flops -= int(6 * head * (1.0 - masked_frac))
        return flops


BERT_BASE = BertConfig()
BERT_TINY = BertConfig(
    vocab_size=256, d_model=64, n_layers=2, n_heads=4, d_ff=128, max_seq=64,
    attn_impl="reference",
)
PRESETS = {"bert-base": BERT_BASE, "tiny": BERT_TINY}


def init(key: jax.Array, cfg: BertConfig) -> dict:
    D, F, V = cfg.d_model, cfg.d_ff, cfg.vocab_size
    Lyr = cfg.n_layers
    dt = cfg.jdtype
    ks = jax.random.split(key, 10)

    def dense(k, *shape, fan_in):
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32) * fan_in**-0.5).astype(dt)

    return {
        "tok_embed": dense(ks[0], V, D, fan_in=1.0),
        "pos_embed": dense(ks[1], cfg.max_seq, D, fan_in=1.0),
        "type_embed": dense(ks[2], cfg.type_vocab, D, fan_in=1.0),
        "embed_norm": {"w": jnp.ones((D,), dt), "b": jnp.zeros((D,), dt)},
        "layers": {
            "wqkv": dense(ks[3], Lyr, D, 3 * D, fan_in=D),
            "bqkv": jnp.zeros((Lyr, 3 * D), dt),
            "wo": dense(ks[4], Lyr, D, D, fan_in=D),
            "bo": jnp.zeros((Lyr, D), dt),
            "attn_norm": {"w": jnp.ones((Lyr, D), dt), "b": jnp.zeros((Lyr, D), dt)},
            "w_in": dense(ks[5], Lyr, D, F, fan_in=D),
            "b_in": jnp.zeros((Lyr, F), dt),
            "w_out": dense(ks[6], Lyr, F, D, fan_in=F),
            "b_out": jnp.zeros((Lyr, D), dt),
            "mlp_norm": {"w": jnp.ones((Lyr, D), dt), "b": jnp.zeros((Lyr, D), dt)},
        },
        "mlm_head": dense(ks[7], D, V, fan_in=D),
        "mlm_bias": jnp.zeros((V,), dt),
    }


def sharding_rules(cfg: BertConfig) -> ShardingRules:
    return ShardingRules([
        (r"tok_embed", P("model", "fsdp")),
        (r"(pos|type)_embed", P(None, "fsdp")),
        (r"layers/(wqkv|w_in)", P(None, "fsdp", "model")),
        (r"layers/(bqkv|b_in)", P(None, "model")),
        (r"layers/(wo|w_out)", P(None, "model", "fsdp")),
        (r"mlm_head", P("fsdp", "model")),
        (r".*", P()),
    ])


def hidden_states(params: dict, tokens: jax.Array, cfg: BertConfig, mesh=None,
                  type_ids: jax.Array | None = None,
                  segment_ids: jax.Array | None = None) -> jax.Array:
    """Encoder output [B, T, D] without the MLM head.

    ``segment_ids`` [B, T] (packed batches, data.pack_sequences layout):
    attention is confined within segments (flash-kernel segment masking,
    bidirectional) and the learned absolute positions restart at every
    segment boundary — the packing r2 built for the decoder models, applied
    to the padded-512 MLM batches it was built for (SURVEY §5.7 / VERDICT
    r2 weak #7). Pad tokens (segment 0) attend only among themselves and
    must simply carry no masked positions.
    """
    B, T = tokens.shape
    H, Dh = cfg.n_heads, cfg.head_dim
    act_spec = P(BATCH_AXES, None, None)

    if segment_ids is not None:
        from tony_tpu.models.llama import segment_positions

        pos_e = jnp.take(params["pos_embed"], segment_positions(segment_ids), axis=0)
    else:
        pos_e = params["pos_embed"][:T]
    x = (
        jnp.take(params["tok_embed"], tokens, axis=0)
        + pos_e
        + jnp.take(params["type_embed"], type_ids if type_ids is not None else jnp.zeros_like(tokens), axis=0)
    )
    x = L.layer_norm(x, params["embed_norm"]["w"], params["embed_norm"]["b"], cfg.norm_eps)
    if mesh is not None:
        x = constrain(x, mesh, act_spec)

    def block(x, lp):
        qkv = jnp.einsum("btd,dh->bth", x, lp["wqkv"]) + lp["bqkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, H, Dh).transpose(0, 2, 1, 3)
        k = k.reshape(B, T, H, Dh).transpose(0, 2, 1, 3)
        v = v.reshape(B, T, H, Dh).transpose(0, 2, 1, 3)
        o = attn_ops.mha_on_mesh(
            q, k, v, mesh=mesh, causal=False, impl=cfg.attn_impl,
            segment_ids=segment_ids,
        )
        o = o.transpose(0, 2, 1, 3).reshape(B, T, H * Dh)
        x = L.layer_norm(
            x + jnp.einsum("bth,hd->btd", o, lp["wo"]) + lp["bo"],
            lp["attn_norm"]["w"], lp["attn_norm"]["b"], cfg.norm_eps,
        )
        x = L.layer_norm(
            x + L.gelu_mlp(x, lp["w_in"], lp["b_in"], lp["w_out"], lp["b_out"]),
            lp["mlp_norm"]["w"], lp["mlp_norm"]["b"], cfg.norm_eps,
        )
        if mesh is not None:
            x = constrain(x, mesh, act_spec)
        return x, None

    block_fn = jax.checkpoint(block) if cfg.remat else block
    x, _ = jax.lax.scan(block_fn, x, params["layers"])
    return x


def forward(params: dict, tokens: jax.Array, cfg: BertConfig, mesh=None,
            type_ids: jax.Array | None = None,
            segment_ids: jax.Array | None = None) -> jax.Array:
    """Full-vocab MLM logits [B, T, V] at every position."""
    x = hidden_states(params, tokens, cfg, mesh, type_ids, segment_ids=segment_ids)
    return jnp.einsum("btd,dv->btv", x, params["mlm_head"]) + params["mlm_bias"]


def loss_fn(params: dict, batch: dict, cfg: BertConfig, mesh=None) -> tuple[jax.Array, dict]:
    """MLM loss.

    Two batch layouts:
    - gathered (preferred): ``masked_pos`` [B, M] + ``masked_targets``
      [B, M] — the MLM head projects ONLY the masked positions (as original
      BERT does), skipping ~85% of the head matmul and never materializing
      the [B, T, V] logits.
    - dense: ``targets`` [B, T] with -100 = unmasked; full-logits path.
    """
    if "masked_pos" in batch:
        x = hidden_states(params, batch["tokens"], cfg, mesh,
                          segment_ids=batch.get("segment_ids"))
        pos = batch["masked_pos"]                                     # [B, M]
        xm = jnp.take_along_axis(x, pos[..., None], axis=1)           # [B, M, D]
        logits = jnp.einsum("bmd,dv->bmv", xm, params["mlm_head"]) + params["mlm_bias"]
        loss, n = L.cross_entropy_loss(logits, batch["masked_targets"])
        return loss, {"loss": loss, "tokens": n}
    logits = forward(params, batch["tokens"], cfg, mesh,
                     segment_ids=batch.get("segment_ids"))
    targets = batch["targets"]
    if "segment_ids" in batch:
        # packed rows: never score padding, whatever the caller put there
        targets = jnp.where(batch["segment_ids"] != 0, targets, -100)
    loss, n = L.cross_entropy_loss(logits, targets)
    return loss, {"loss": loss, "tokens": n}


def synthetic_batch(key: jax.Array, batch_size: int, seq_len: int, cfg: BertConfig,
                    mask_frac: float = 0.15) -> dict:
    """Gathered MLM layout: exactly M = round(mask_frac·T) masked positions
    per row (fixed count = static shapes for the gathered-head loss path;
    this is also how production BERT pipelines batch MLM)."""
    k1, k2 = jax.random.split(key)
    tokens = jax.random.randint(k1, (batch_size, seq_len), 0, cfg.vocab_size, jnp.int32)
    M = max(1, round(seq_len * mask_frac))
    # top-M of uniform noise = M distinct positions, sorted for locality
    noise = jax.random.uniform(k2, (batch_size, seq_len))
    pos = jnp.sort(jnp.argsort(noise, axis=-1)[:, :M], axis=-1).astype(jnp.int32)
    targets = jnp.take_along_axis(tokens, pos, axis=1)
    return {"tokens": tokens, "masked_pos": pos, "masked_targets": targets}


def dense_synthetic_batch(key: jax.Array, batch_size: int, seq_len: int, cfg: BertConfig,
                         mask_frac: float = 0.15) -> dict:
    """Dense [B, T] targets layout (-100 = unmasked) for the full-logits path."""
    k1, k2 = jax.random.split(key)
    tokens = jax.random.randint(k1, (batch_size, seq_len), 0, cfg.vocab_size, jnp.int32)
    masked = jax.random.uniform(k2, (batch_size, seq_len)) < mask_frac
    return {"tokens": tokens, "targets": jnp.where(masked, tokens, -100)}


def config_from_dict(d: dict | str) -> BertConfig:
    if isinstance(d, str):
        return PRESETS[d]
    fields = {f.name for f in dataclasses.fields(BertConfig)}
    return dataclasses.replace(
        PRESETS.get(d.get("preset", ""), BertConfig()),
        **{k: v for k, v in d.items() if k in fields},
    )
