"""The llama and mixtral families' plain reference: Mistral/Mixtral forward in
jax.numpy and float32.

No kernel, no cache, no batching, one sequence at a time: RMSNorm, rotary
embedding (rotate-half, as the published code), grouped-query causal attention
inside the sliding band, SwiGLU, and for Mixtral a softmax router whose top-k
gates are renormalised and a dense sum over the chosen experts. Attention is
taken in blocks of queries against the whole context so that 8192 positions fit.

Inputs come from the seed alone: `init_weights` draws the weights (the program
is handed them; it makes none); the check's tokens are compare.zipf_tokens.

`forward` is differentiable (layers and attention blocks are checkpointed, so
a gradient at 8192 positions fits beside the weights): the training comparison
also reads the reference's gradient of the mean loss.

Precisions: "f32" is the reference (float32 operands, matmul precision
"highest"). "fp8" is the control of "How `correct` is decided": the same
mathematics with both operands of every matrix product rounded to
float8_e4m3 (per-tensor scale) - the nearest precision below the bf16 the
configurations state. The benchmark's own runs never compute it.

Departures from the published description: none known. The weights' values
are bf16 (what the configuration serves); the reference upcasts them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: the precision of the control (`prec` of `forward`)
CONTROL = "fp8"
#: the leaves whose gradient the training comparison reads, as paths into the
#: parameter tree: what flows into them has passed the flash backward of their
#: own layer (dq, dk, dv) and every layer above it
GRAD_LEAVES = (("layers", "wq"), ("layers", "wk"), ("layers", "wv"))


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 65536), seed // 65536)


def init_weights(key: jax.Array, s: dict) -> dict:
    """The parameter tree in the layout models/llama.py and models/mixtral.py
    read: stacked layers, bf16 (float32 router). Truncated normal, fan-in scaled."""
    d, f, v, hd = s["d_model"], s["d_ff"], s["vocab"], s["head_dim"]
    h, hkv, n, e = s["heads"], s["kv_heads"], s["layers"], s["experts"]
    dt = jnp.dtype(s["dtype"])
    ks = jax.random.split(key, 10)

    def dense(k, *shape, fan_in, dtype=dt):
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32) * fan_in ** -0.5).astype(dtype)

    layers = {
        "attn_norm": jnp.ones((n, d), dt),
        "wq": dense(ks[1], n, d, h * hd, fan_in=d),
        "wk": dense(ks[2], n, d, hkv * hd, fan_in=d),
        "wv": dense(ks[3], n, d, hkv * hd, fan_in=d),
        "wo": dense(ks[4], n, h * hd, d, fan_in=h * hd),
        "mlp_norm": jnp.ones((n, d), dt),
    }
    if e:
        layers.update(
            router=dense(ks[9], n, d, e, fan_in=d, dtype=jnp.float32),
            we_gate=dense(ks[5], n, e, d, f, fan_in=d),
            we_up=dense(ks[6], n, e, d, f, fan_in=d),
            we_down=dense(ks[7], n, e, f, d, fan_in=f),
        )
    else:
        layers.update(
            w_gate=dense(ks[5], n, d, f, fan_in=d),
            w_up=dense(ks[6], n, d, f, fan_in=d),
            w_down=dense(ks[7], n, f, d, fan_in=f),
        )
    return {
        "embed": dense(ks[0], v, d, fan_in=1.0),
        "layers": layers,
        "final_norm": jnp.ones((d,), dt),
        "lm_head": dense(ks[8], d, v, fan_in=d),
    }



def _round_fp8(a: jax.Array) -> jax.Array:
    """The value rounded to float8_e4m3; the gradient passes straight through
    (a cotangent cast to fp8 would underflow to nothing, and a control that
    returns no gradient at all fails too easily to be one)."""
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    q = (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return jax.lax.stop_gradient(q) + (a - jax.lax.stop_gradient(a))


def _mm(eq: str, a: jax.Array, b: jax.Array, prec: str) -> jax.Array:
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if prec == "fp8":
        a, b = _round_fp8(a), _round_fp8(b)
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _rope(x, theta):
    """x [T, H, Dh] float32; rotate-half."""
    t, _, dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.outer(jnp.arange(t, dtype=jnp.float32), inv)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _attention(q, k, v, window: int, prec: str, q_block: int):
    """q [T, H, Dh], k/v [T, Hkv, Dh] -> [T, H, Dh]; causal, inside the band."""
    t, h, dh = q.shape
    hkv = k.shape[1]
    qg = q.reshape(t // q_block, q_block, hkv, h // hkv, dh)
    kpos = jnp.arange(t)

    def block(args):
        i, qb = args
        qpos = i * q_block + jnp.arange(q_block)
        s = _mm("qkgd,tkd->kgqt", qb, k, prec) * dh ** -0.5
        ok = kpos[None, :] <= qpos[:, None]
        if window > 0:
            ok &= kpos[None, :] > qpos[:, None] - window
        p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        return _mm("kgqt,tkd->qkgd", p, v, prec)

    # checkpointed: a gradient keeps a block's queries, not its [q_block, T] scores
    out = jax.lax.map(jax.checkpoint(block), (jnp.arange(t // q_block), qg))
    return out.reshape(t, h, dh)


def _ffn(x, lp, s, prec):
    if not s["experts"]:
        g = jax.nn.silu(_mm("td,df->tf", x, lp["w_gate"], prec))
        return _mm("tf,fd->td", g * _mm("td,df->tf", x, lp["w_up"], prec), lp["w_down"], prec)
    probs = jax.nn.softmax(_mm("td,de->te", x, lp["router"], prec), axis=-1)
    top, idx = jax.lax.top_k(probs, s["top_k"])
    top = top / top.sum(-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], idx].set(top)

    def one(y, ew):
        wg, wu, wd, gate = ew
        g = jax.nn.silu(_mm("td,df->tf", x, wg, prec))
        return y + gate[:, None] * _mm("tf,fd->td", g * _mm("td,df->tf", x, wu, prec), wd, prec), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (lp["we_gate"], lp["we_up"], lp["we_down"], gates.T))
    return y


def hidden(params: dict, tokens: jax.Array, s: dict, prec: str = "f32", q_block: int = 512) -> jax.Array:
    """tokens [T] -> the last layer's output after the final norm [T, D],
    float32. T must divide by q_block (pad at the end: a causal model's
    earlier positions do not see the padding)."""
    t = tokens.shape[0]
    if t % q_block:
        raise ValueError(f"{t} positions do not divide into blocks of {q_block}")
    h, hkv, dh = s["heads"], s["kv_heads"], s["head_dim"]
    x = params["embed"].astype(jnp.float32)[tokens]

    def layer(x, lp):
        y = _rms_norm(x, lp["attn_norm"], s["norm_eps"])
        q = _rope(_mm("td,dh->th", y, lp["wq"], prec).reshape(t, h, dh), s["rope_theta"])
        k = _rope(_mm("td,dh->th", y, lp["wk"], prec).reshape(t, hkv, dh), s["rope_theta"])
        v = _mm("td,dh->th", y, lp["wv"], prec).reshape(t, hkv, dh)
        o = _attention(q, k, v, s["window"], prec, q_block).reshape(t, h * dh)
        x = x + _mm("th,hd->td", o, lp["wo"], prec)
        return x + _ffn(_rms_norm(x, lp["mlp_norm"], s["norm_eps"]), lp, s, prec), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["layers"])
    return _rms_norm(x, params["final_norm"], s["norm_eps"])


def forward(params: dict, tokens: jax.Array, s: dict, prec: str = "f32", q_block: int = 512) -> jax.Array:
    """tokens [T] -> logits [T, V] float32."""
    return _mm("td,dv->tv", hidden(params, tokens, s, prec, q_block), params["lm_head"], prec)


def nll(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Per-position negative log-likelihood, float32."""
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
