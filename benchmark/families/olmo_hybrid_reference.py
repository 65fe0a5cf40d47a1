"""The olmo_hybrid family's plain reference: the Olmo-Hybrid forward pass in
jax.numpy and float32, matrix products at precision "highest".

No kernel, no cache, no chunked form of the recurrence, one sequence at a time,
nothing shared with tony_tpu/. Every layer is ``h = x + Norm_a(Mixer(x))``, ``y =
h + Norm_f(FFN(h))`` (`_after`: the norm on each sublayer's OUTPUT), the FFN a
SwiGLU; a final RMSNorm and an untied head. A layer's mixer is what
`layer_types` says of it:

  linear_attention  one projection to q, k (heads of `lin_key_dim`) and v (heads of
                    `lin_value_dim`); a causal depthwise convolution of `conv_taps`
                    inputs over time on each channel, no bias, then SiLU
                    (`_conv_silu`); q and k L2-normed a head (x rsqrt(sum x^2 + eps)),
                    q x lin_key_dim^-1/2 (`_l2`); beta = 2 sigmoid(b), g = -exp(A_log)
                    softplus(a + dt_bias) (`_gates`); then THE LITERAL RECURRENCE, a
                    scan over positions with a float32 state [dk, dv] a head, zero
                    at position 0: S' = exp(g) S; S = S' + beta k (v - S'^T k)^T; o =
                    S^T q (`_position`); RMSNorm over each head's o with one weight
                    shared by the heads, x silu(W_g x) (`_gate_out`); W_o.
  full_attention    q, k, v; RMSNorm over the WHOLE width of q and of k, then split
                    into heads (`_qk_norm`); NO rotary embedding; a full score matrix a
                    block of queries under the causal mask, scale head_dim^-1/2; W_o.

What the published configuration does not give is the configuration's `assumed`
(families/olmo_hybrid.py: sizes), each choice one function here.

Positions are processed in blocks of `q_block` wherever a temporary would grow
with the sequence (projections, FFN, scores), so that 21k positions fit beside
the weights; the recurrence runs position by position inside them, the
convolution's last inputs carried from block to block.

`forward` returns logits [T, V] whose rows before the last HEAD_ROWS are NaN
(not computed, and a comparison that reads one cannot pass: NaN is under no
limit), as families/minicpm_sala_reference.py's: the serving check reads 512
rows that lie within the last 2047 of the padded sequence it builds.

Precisions: "f32" is the reference. "fp8" is the control: the same mathematics
with both operands of every matrix product (the recurrence's probe, write and
read among them) rounded to float8_e4m3, the nearest precision below bf16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

CONTROL = "fp8"
#: no training cell: no gradient is compared
GRAD_LEAVES = ()
#: rows at the end of a sequence whose logits `forward` computes
HEAD_ROWS = 2048

LINEAR, FULL = "linear_attention", "full_attention"


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 65536), seed // 65536)


def init_weights(key: jax.Array, s: dict) -> dict:
    """The parameter tree in the layout tony_tpu/models/olmo_hybrid.py reads:
    `layers` is a list with one dict of leaves a layer, in order, shaped by the
    layer's kind. Truncated normal, fan-in scaled; norms at one; A_log = log U(1,
    16) and dt_bias the inverse softplus of exp U(log 0.001, log 0.1), float32."""
    d, f, v, dt = s["d_model"], s["d_ff"], s["vocab"], jnp.dtype(s["dtype"])
    ks = iter(jax.random.split(key, 3 + 12 * len(s["layer_types"])))

    def dense(*shape, fan_in):
        return (jax.random.truncated_normal(next(ks), -2, 2, shape, jnp.float32) * fan_in ** -0.5).astype(dt)

    def layer(kind):
        lp = {"attn_norm": jnp.ones((d,), dt), "mlp_norm": jnp.ones((d,), dt),
              "w_gate": dense(d, f, fan_in=d), "w_up": dense(d, f, fan_in=d), "w_down": dense(f, d, fan_in=f)}
        if kind == FULL:
            q, kv = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
            return {**lp, "w_qkv": dense(d, q + 2 * kv, fan_in=d), "q_norm": jnp.ones((q,), dt), "k_norm": jnp.ones((kv,), dt),
                    "wo": dense(q, d, fan_in=q)}
        h, c, out = s["lin_heads"], channels(s), s["lin_heads"] * s["lin_value_dim"]
        step = jnp.exp(jax.random.uniform(next(ks), (h,), jnp.float32, np.log(0.001), np.log(0.1)))
        return {**lp, "w_qkv": dense(d, c, fan_in=d), "conv": dense(s["conv_taps"], c, fan_in=s["conv_taps"]),
                "w_g": dense(d, out, fan_in=d), "w_ab": dense(d, 2 * h, fan_in=d),
                "A_log": jnp.log(jax.random.uniform(next(ks), (h,), jnp.float32, 1.0, 16.0)),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)), "o_norm": jnp.ones((s["lin_value_dim"],), dt),
                "wo": dense(out, d, fan_in=out)}

    return {"embed": dense(v, d, fan_in=1.0), "layers": [layer(kind) for kind in s["layer_types"]],
            "final_norm": jnp.ones((d,), dt), "lm_head": dense(d, v, fan_in=d)}


def channels(s: dict) -> int:
    """What the convolution runs over: q, k and v of every head, side by side."""
    return s["lin_heads"] * (2 * s["lin_key_dim"] + s["lin_value_dim"])


def _round_fp8(a: jax.Array) -> jax.Array:
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(eq: str, a: jax.Array, b: jax.Array, prec: str) -> jax.Array:
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if prec == "fp8":
        a, b = _round_fp8(a), _round_fp8(b)
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _after(x, branch, w, s):
    """Where the norms sit: on the sublayer's output, which is then added."""
    return x + _rms_norm(branch, w, s["norm_eps"])


def _blocks(t: int, q_block: int):
    return jnp.arange(t // q_block), lambda a: a.reshape(t // q_block, q_block, *a.shape[1:])


def _ffn(x, lp, s, prec, q_block):
    """x [T, D] -> x + Norm_f(FFN(x)), a block of positions at a time."""
    _, split = _blocks(x.shape[0], q_block)

    def one(xb):
        g = jax.nn.silu(_mm("td,df->tf", xb, lp["w_gate"], prec))
        return _after(xb, _mm("tf,fd->td", g * _mm("td,df->tf", xb, lp["w_up"], prec), lp["w_down"], prec), lp["mlp_norm"], s)

    return jax.lax.map(one, split(x)).reshape(x.shape)


def _conv_silu(u, tail, w):
    """u [Q, C] after the inputs tail [taps - 1, C]: silu(sum_j w_j u_{t - (taps-1) + j}), and the new tail."""
    q = u.shape[0]
    xp = jnp.concatenate([tail, u])
    return jax.nn.silu(sum(w[j].astype(jnp.float32) * xp[j:j + q] for j in range(w.shape[0]))), xp[q:]


def _l2(a, eps):
    return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + eps)


def _gates(ab, lp, h):
    """ab [Q, 2H] -> (g [Q, H] <= 0, beta [Q, H] in (0, 2): `linear_allow_neg_eigval`)."""
    g = -jnp.exp(lp["A_log"].astype(jnp.float32)) * jax.nn.softplus(ab[:, :h] + lp["dt_bias"].astype(jnp.float32))
    return g, 2.0 * jax.nn.sigmoid(ab[:, h:])


def _position(state, x, prec):
    """One position of the gated delta rule, every head: state [H, dk, dv]."""
    qt, kt, vt, gt, bt = x
    state = jnp.exp(gt)[:, None, None] * state
    w = bt[:, None] * (vt - _mm("hkv,hk->hv", state, kt, prec))
    state = state + _mm("hk,hv->hkv", kt, w, prec)
    return state, _mm("hkv,hk->hv", state, qt, prec)


def _gate_out(o, gate, lp, s):
    """o, gate [Q, H, dv]: RMSNorm a head (one weight for all heads) x silu(gate)."""
    return _rms_norm(o, lp["o_norm"], s["norm_eps"]) * jax.nn.silu(gate)


def _linear_layer(x, lp, s, prec, q_block):
    """x [T, D] -> x + Norm_a(the gated delta-rule mixer's branch)."""
    t, h, dk, dv = x.shape[0], s["lin_heads"], s["lin_key_dim"], s["lin_value_dim"]
    _, split = _blocks(t, q_block)

    def block(carry, xb):
        state, tail = carry
        y, tail = _conv_silu(_mm("td,dc->tc", xb, lp["w_qkv"], prec), tail, lp["conv"])
        q = _l2(y[:, :h * dk].reshape(q_block, h, dk), s["norm_eps"]) * dk ** -0.5
        k = _l2(y[:, h * dk:2 * h * dk].reshape(q_block, h, dk), s["norm_eps"])
        v = y[:, 2 * h * dk:].reshape(q_block, h, dv)
        g, beta = _gates(_mm("td,dh->th", xb, lp["w_ab"], prec), lp, h)
        state, o = jax.lax.scan(lambda st, pos: _position(st, pos, prec), state, (q, k, v, g, beta))
        gate = _mm("td,dh->th", xb, lp["w_g"], prec).reshape(q_block, h, dv)
        out = _mm("th,hd->td", _gate_out(o, gate, lp, s).reshape(q_block, h * dv), lp["wo"], prec)
        return (state, tail), _after(xb, out, lp["attn_norm"], s)

    start = (jnp.zeros((h, dk, dv), jnp.float32), jnp.zeros((s["conv_taps"] - 1, channels(s)), jnp.float32))
    _, out = jax.lax.scan(block, start, split(x))
    return out.reshape(t, -1)


def _qk_norm(a, w, s, heads):
    """a [Q, heads x dh]: RMSNorm over the whole projection, then the heads."""
    return _rms_norm(a, w, s["norm_eps"]).reshape(a.shape[0], heads, s["head_dim"])


def _full_layer(x, lp, s, prec, q_block):
    """x [T, D] -> x + Norm_a(causal softmax attention's branch); no rotary embedding."""
    t, h, hkv, dh = x.shape[0], s["heads"], s["kv_heads"], s["head_dim"]
    idx, split = _blocks(t, q_block)
    qw, kw = h * dh, hkv * dh

    def keys_values(xb):
        kv = _mm("td,dh->th", xb, lp["w_qkv"][:, qw:], prec)
        return _qk_norm(kv[:, :kw], lp["k_norm"], s, hkv), kv[:, kw:].reshape(q_block, hkv, dh)

    k, v = jax.lax.map(keys_values, split(x))
    k, v = k.reshape(t, hkv, dh), v.reshape(t, hkv, dh)
    kpos = jnp.arange(t)

    def queries(args):
        i, xb = args
        q = _qk_norm(_mm("td,dh->th", xb, lp["w_qkv"][:, :qw], prec), lp["q_norm"], s, h).reshape(q_block, hkv, h // hkv, dh)
        scores = _mm("qkgd,tkd->kgqt", q, k, prec) * dh ** -0.5
        seen = kpos[None, :] <= (i * q_block + jnp.arange(q_block))[:, None]
        p = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        o = _mm("kgqt,tkd->qkgd", p, v, prec).reshape(q_block, h * dh)
        return _after(xb, _mm("th,hd->td", o, lp["wo"], prec), lp["attn_norm"], s)

    return jax.lax.map(queries, (idx, split(x))).reshape(t, -1)


def hidden(params: dict, tokens: jax.Array, s: dict, prec: str = "f32", q_block: int = 64) -> jax.Array:
    """tokens [T] -> the trunk after the last layer [T, D], float32 (before the
    final norm). T must divide by q_block (pad at the end: a causal model's
    earlier positions do not see the padding)."""
    t = tokens.shape[0]
    q_block = min(q_block, t)
    if t % q_block:
        raise ValueError(f"{t} positions do not divide into blocks of {q_block}")
    x = params["embed"][tokens].astype(jnp.float32)
    for kind, lp in zip(s["layer_types"], params["layers"], strict=True):
        mixer = _full_layer if kind == FULL else _linear_layer
        x = _ffn(mixer(x, lp, s, prec, q_block), lp, s, prec, q_block)
    return x


def forward(params: dict, tokens: jax.Array, s: dict, prec: str = "f32", q_block: int = 64) -> jax.Array:
    """tokens [T] -> logits [T, V] float32; rows before the last HEAD_ROWS are
    NaN: not computed (this file's head), and never a row that agrees."""
    x = hidden(params, tokens, s, prec, q_block)
    rows = min(HEAD_ROWS, x.shape[0])
    y = _rms_norm(x[-rows:], params["final_norm"], s["norm_eps"])
    return jnp.pad(_mm("td,dv->tv", y, params["lm_head"], prec), ((x.shape[0] - rows, 0), (0, 0)), constant_values=jnp.nan)


def nll(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Per-position negative log-likelihood, float32."""
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
