"""ResNet-v1.5 image classifier (BASELINE.json config #3 — the PyTorch-DDP →
torch-xla analog workload, here pure JAX with data-parallel sharding).

Convs via lax.conv_general_dilated in NHWC (the TPU-native layout — channels
on the 128-lane minor dim feeds the MXU without relayout). BatchNorm is
functional: batch statistics computed in-step; running stats carried in a
separate ``state`` pytree updated as an aux output (no hidden mutation).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tony_tpu.parallel.sharding import ShardingRules

STAGE_BLOCKS = {18: (2, 2, 2, 2), 34: (3, 4, 6, 3), 50: (3, 4, 6, 3), 101: (3, 4, 23, 3)}
BOTTLENECK = {50: True, 101: True, 18: False, 34: False}


@dataclass(frozen=True)
class ResNetConfig:
    depth: int = 50
    num_classes: int = 1000
    width: int = 64
    image_size: int = 224
    bn_momentum: float = 0.9
    dtype: str = "bfloat16"
    # space-to-depth stem (same math, 4× MXU lane occupancy on the 3-channel
    # stem conv). Off by default: the builders' rounds-1-5 runs (older than
    # this code) saw it neutral to slightly slower at b512; not measured on
    # the chip since, so the exact transform is kept selectable.
    stem_s2d: bool = False

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def blocks(self) -> tuple[int, ...]:
        return STAGE_BLOCKS[self.depth]

    @property
    def bottleneck(self) -> bool:
        return BOTTLENECK[self.depth]


RESNET50 = ResNetConfig()
RESNET_TINY = ResNetConfig(depth=18, num_classes=10, width=8, image_size=32, dtype="float32")
PRESETS = {"resnet50": RESNET50, "tiny": RESNET_TINY}


def _conv_init(key, kh, kw, cin, cout, dtype):
    fan_in = kh * kw * cin
    return (jax.random.truncated_normal(key, -2, 2, (kh, kw, cin, cout), jnp.float32)
            * (2.0 / fan_in) ** 0.5).astype(dtype)


def _bn_params(c, dtype):
    return {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)}


def _bn_state(c):
    return {"mean": jnp.zeros((c,), jnp.float32), "var": jnp.ones((c,), jnp.float32)}


def init(key: jax.Array, cfg: ResNetConfig) -> tuple[dict, dict]:
    """Returns (params, state) — state carries BatchNorm running stats."""
    dt = cfg.jdtype
    keys = iter(jax.random.split(key, 256))
    params: dict[str, Any] = {"stem": {"conv": _conv_init(next(keys), 7, 7, 3, cfg.width, dt),
                                       "bn": _bn_params(cfg.width, dt)}}
    state: dict[str, Any] = {"stem": {"bn": _bn_state(cfg.width)}}

    expansion = 4 if cfg.bottleneck else 1
    cin = cfg.width
    for stage, n_blocks in enumerate(cfg.blocks):
        cmid = cfg.width * (2**stage)
        cout = cmid * expansion
        for b in range(n_blocks):
            name = f"stage{stage}_block{b}"
            stride = 2 if (b == 0 and stage > 0) else 1
            blk_p: dict[str, Any] = {}
            blk_s: dict[str, Any] = {}
            if cfg.bottleneck:
                shapes = [(1, 1, cin, cmid, 1), (3, 3, cmid, cmid, stride), (1, 1, cmid, cout, 1)]
            else:
                shapes = [(3, 3, cin, cmid, stride), (3, 3, cmid, cout, 1)]
            for i, (kh, kw, ci, co, _s) in enumerate(shapes):
                blk_p[f"conv{i}"] = _conv_init(next(keys), kh, kw, ci, co, dt)
                blk_p[f"bn{i}"] = _bn_params(co, dt)
                blk_s[f"bn{i}"] = _bn_state(co)
            if cin != cout or stride != 1:
                blk_p["proj"] = _conv_init(next(keys), 1, 1, cin, cout, dt)
                blk_p["proj_bn"] = _bn_params(cout, dt)
                blk_s["proj_bn"] = _bn_state(cout)
            params[name] = blk_p
            state[name] = blk_s
            cin = cout
    params["head"] = {"w": (jax.random.normal(next(keys), (cin, cfg.num_classes)) * cin**-0.5).astype(dt),
                      "b": jnp.zeros((cfg.num_classes,), dt)}
    return params, state


def sharding_rules(cfg: ResNetConfig) -> ShardingRules:
    # convs are small: replicate weights, shard only the batch (pure DP);
    # the head's [C, classes] can shard over model for very wide variants.
    return ShardingRules([(r"head/w", P("fsdp", "model")), (r".*", P())])


def _conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _stem_conv_s2d(images, w):
    """The 7×7/2 stem conv as a space-to-depth 4×4/1 conv — numerically
    identical, but 12 input channels instead of 3, which quadruples MXU
    lane occupancy on the layer that otherwise runs at 3/128 efficiency
    (the standard MLPerf-TPU ResNet stem transform).

    images [B, S, S, 3] with even S; w [7, 7, 3, C].
    """
    B, S, _, _ = images.shape
    C = w.shape[-1]
    # SAME padding for k=7/s=2 is (2, 3); one extra trailing row/col of
    # zeros (total 2+S+4) keeps the length even for the 2×2 blocking and
    # only ever multiplies the zero-padded kernel tap
    x = jnp.pad(images, ((0, 0), (2, 4), (2, 4), (0, 0)))
    Sp = (S + 6) // 2
    x = x.reshape(B, Sp, 2, Sp, 2, 3).transpose(0, 1, 3, 2, 4, 5).reshape(B, Sp, Sp, 12)
    w8 = jnp.pad(w, ((0, 1), (0, 1), (0, 0), (0, 0)))                 # [8,8,3,C]
    ws = w8.reshape(4, 2, 4, 2, 3, C).transpose(0, 2, 1, 3, 4, 5).reshape(4, 4, 12, C)
    return jax.lax.conv_general_dilated(
        x, ws, (1, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, p, s, momentum, train):
    xf = x.astype(jnp.float32)
    if train:
        mean = jnp.mean(xf, axis=(0, 1, 2))
        var = jnp.var(xf, axis=(0, 1, 2))
        new_s = {"mean": momentum * s["mean"] + (1 - momentum) * mean,
                 "var": momentum * s["var"] + (1 - momentum) * var}
    else:
        mean, var = s["mean"], s["var"]
        new_s = s
    out = (xf - mean) * jax.lax.rsqrt(var + 1e-5)
    return (out.astype(x.dtype) * p["scale"] + p["bias"]), new_s


def forward(params: dict, state: dict, images: jax.Array, cfg: ResNetConfig,
            train: bool = True, mesh=None) -> tuple[jax.Array, dict]:
    """images [B, H, W, 3] → (logits [B, classes], new_state)."""
    new_state: dict[str, Any] = {}
    images = images.astype(cfg.jdtype)
    if cfg.stem_s2d and images.shape[1] == images.shape[2] and images.shape[1] % 2 == 0:
        x = _stem_conv_s2d(images, params["stem"]["conv"])
    else:
        x = _conv(images, params["stem"]["conv"], 2)
    x, bn_s = _bn(x, params["stem"]["bn"], state["stem"]["bn"], cfg.bn_momentum, train)
    new_state["stem"] = {"bn": bn_s}
    x = jax.nn.relu(x)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")

    expansion = 4 if cfg.bottleneck else 1
    cin = cfg.width
    for stage, n_blocks in enumerate(cfg.blocks):
        cmid = cfg.width * (2**stage)
        cout = cmid * expansion
        for b in range(n_blocks):
            name = f"stage{stage}_block{b}"
            blk_p, blk_s = params[name], state[name]
            new_blk_s: dict[str, Any] = {}
            stride = 2 if (b == 0 and stage > 0) else 1
            shortcut = x
            strides = ([1, stride, 1] if cfg.bottleneck else [stride, 1])
            h = x
            for i, s_i in enumerate(strides):
                h = _conv(h, blk_p[f"conv{i}"], s_i)
                h, bn_s = _bn(h, blk_p[f"bn{i}"], blk_s[f"bn{i}"], cfg.bn_momentum, train)
                new_blk_s[f"bn{i}"] = bn_s
                if i < len(strides) - 1:
                    h = jax.nn.relu(h)
            if "proj" in blk_p:
                shortcut = _conv(shortcut, blk_p["proj"], stride)
                shortcut, bn_s = _bn(shortcut, blk_p["proj_bn"], blk_s["proj_bn"], cfg.bn_momentum, train)
                new_blk_s["proj_bn"] = bn_s
            x = jax.nn.relu(h + shortcut)
            new_state[name] = new_blk_s
            cin = cout

    x = jnp.mean(x, axis=(1, 2))
    logits = x @ params["head"]["w"] + params["head"]["b"]
    return logits, new_state


def loss_fn(params: dict, batch: dict, cfg: ResNetConfig, mesh=None,
            state: dict | None = None) -> tuple[jax.Array, dict]:
    logits, new_state = forward(params, state if state is not None else batch["bn_state"],
                                batch["image"], cfg, train=True, mesh=mesh)
    labels = batch["label"]
    loss = jnp.mean(
        -jax.nn.log_softmax(logits.astype(jnp.float32))[jnp.arange(labels.shape[0]), labels])
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, {"loss": loss, "accuracy": acc, "bn_state": new_state}


def synthetic_batch(key: jax.Array, batch_size: int, cfg: ResNetConfig) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "image": jax.random.uniform(k1, (batch_size, cfg.image_size, cfg.image_size, 3), jnp.float32),
        "label": jax.random.randint(k2, (batch_size,), 0, cfg.num_classes, jnp.int32),
    }


def config_from_dict(d: dict | str) -> ResNetConfig:
    if isinstance(d, str):
        return PRESETS[d]
    fields = {f.name for f in dataclasses.fields(ResNetConfig)}
    return dataclasses.replace(
        PRESETS.get(d.get("preset", ""), ResNetConfig()),
        **{k: v for k, v in d.items() if k in fields},
    )
