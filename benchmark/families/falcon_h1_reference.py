"""The falcon_h1 family's plain reference: Falcon-H1's forward pass in jax.numpy and
float32, matrix products at precision "highest".

No kernel, no cache, no chunked form of the recurrence, one sequence at a time,
nothing shared with tony_tpu/. x is [T, D]:

  x0 = embedding_multiplier x embed[token]
  every layer (`_mix`: pre-norm, BOTH mixers read the one normed input, both are added):
      u = rmsnorm(x)
      h = x + attention_out_multiplier Attn(attention_in_multiplier u) + ssm_out_multiplier SSM(ssm_in_multiplier u)
      y = h + FFN(rmsnorm(h))
  logits = lm_head_multiplier x rmsnorm(x_L) W_head      (untied; over the held rows of the vocabulary)

  Attn  q = v W_q (H heads of head_dim), k = key_multiplier (v W_k), v W_v (Hkv heads), no bias, no q/k norm
        (`_qkv`); q and k rotated over the whole head, halves rotated, theta rope_theta (`_rotate`); a full
        score matrix a block of queries under the causal mask, scores / sqrt(head_dim), H / Hkv query heads a
        kv head; W_o.
  SSM   [z | x | B | C | dt] = (v W_in) * m, m the vector that holds ssm_multipliers[0..4] over the segments of
        I | I | G N | G N | H columns (`_in_proj`; the parameter tree keeps the dt columns as a leaf of their
        own); x B C = silu(conv(x B C) + b), a causal depthwise convolution of `conv_taps` inputs over time
        (`_conv_silu`); x as H heads of P, B and C as G groups of N, head h reading group h // (H / G)
        (`_group_of_head`); dt = softplus(dt + dt_bias), a = exp(-exp(A_log) dt), no clamp (`_steps`); then THE
        LITERAL RECURRENCE, a scan over positions with a float32 state [P, N] a head, zero at position 0: S = a
        S + dt x B^T; o = S C + D x (`_position`); gate THEN norm: o silu(z), rmsnorm over EACH group's I / G
        channels times a weight of I (`_gate_then_norm`); W_out.
  FFN   down_multiplier ((silu(gate_multiplier (n W_gate)) * (n W_up)) W_down), (gate_multiplier,
        down_multiplier) = mlp_multipliers (`_scaled_swiglu`).

What the published configuration does not give is the configuration's `assumed`
(families/falcon_h1.py: sizes), each choice one function here and one in the
program.

Positions are processed in blocks of `q_block` wherever a temporary would grow
with the sequence (projections, FFN, scores), and the head a slice of the
vocabulary at a time (its float32 copy whole is 2.7 GB); the recurrence runs
position by position inside the blocks, the convolution's last inputs carried
from block to block. `forward` returns logits [T, V] whose rows before the last
HEAD_ROWS are NaN (not computed, and a comparison that reads one cannot pass:
NaN is under no limit): the serving check reads 512 rows that lie within the
last 2047 of the padded sequence it builds.

Precisions: "f32" is the reference. "fp8" is the control: the same mathematics
with both operands of every matrix product (the recurrence's write and read
among them) rounded to float8_e4m3, the nearest precision below bf16. Every
position is stated: there is no routing here, and so no tie.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from families.exaone_moe_reference import _mm, _rms_norm, nll, seed_key
# the Mamba-2 pieces that do not know a group: the convolution with its bias, softplus(dt + dt_bias) without a clamp (ASSUMED dt_limits), the blocks
from families.granite_hybrid_reference import _blocks, _conv_silu, _steps

__all__ = ["CONTROL", "GRAD_LEAVES", "HEAD_ROWS", "forward", "init_weights", "nll", "seed_key"]

CONTROL = "fp8"
#: no training cell: no gradient is compared
GRAD_LEAVES = ()
#: rows at the end of a sequence whose logits `forward` computes
HEAD_ROWS = 2048
#: slices of the vocabulary's rows the two vocabulary matrices are drawn in and the head is multiplied in
VOCAB_SLICES = 8


def inner(s: dict) -> int:
    """The state-space mixer's inner width: every head's x, side by side (the published `mamba_d_ssm`)."""
    return s["ssm_heads"] * s["ssm_head_dim"]


def channels(s: dict) -> int:
    """What the convolution runs over: x of every head, then every group's B, then every group's C."""
    return inner(s) + 2 * s["ssm_groups"] * s["ssm_state"]


def init_weights(key: jax.Array, s: dict) -> dict:
    """The parameter tree in the layout tony_tpu/models/falcon_h1.py reads: `layers` a
    list with one dict of leaves a layer. Truncated normal; norms at one; A_log =
    log U(1, 16), dt_bias the inverse softplus of exp U(log 0.001, log 0.1), D = 1,
    float32 (ASSUMED ssm_init). `w_in` is the projection's z | x | B | C, `w_dt` its
    dt columns. ASSUMED matrix_init: a matrix's fan-in is its input width times the
    SQUARE of the scalars that stand between it and the stream, a column segment
    at a time where segments have scalars of their own (the keys', the five of
    the state-space projection), so that each scalar cancels against its matrix
    in the draw: embedding_multiplier x embed[token] is of size one an entry, the
    keys, the gate, the step and each branch are of the size a plain fan-in draw
    gives a model without scalars, and the logits are of size one. The two
    vocabulary matrices are drawn VOCAB_SLICES rows' slices at a time (the float32
    draw of one whole is 2.7 GB beside 10.4 GB of weights)."""
    d, v, dt, f = s["d_model"], s["vocab"], jnp.dtype(s["dtype"]), s["d_ff"]
    q, kv, h, i, c = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"], s["ssm_heads"], inner(s), channels(s)
    ks = iter(jax.random.split(key, 4 + 24 * s["layers"]))
    gate_m, down_m = s["mlp_multipliers"]
    a_in, s_in, gn = s["attention_in_multiplier"], s["ssm_in_multiplier"], s["ssm_groups"] * s["ssm_state"]
    m_z, m_x, m_b, m_c, m_dt = s["ssm_multipliers"]

    def draw(k, shape, fan_in):
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32) * fan_in ** -0.5).astype(dt)

    def dense(*shape, fan_in):
        return draw(next(ks), shape, fan_in)

    def columns(*parts):
        return jnp.concatenate([dense(d, width, fan_in=d * scalar ** 2) for width, scalar in parts], axis=1)

    def vocabulary(fan_in):
        n = VOCAB_SLICES if v % VOCAB_SLICES == 0 else 1
        return jax.lax.map(lambda k: draw(k, (v // n, d), fan_in), jax.random.split(next(ks), n)).reshape(v, d)

    def layer():
        step = jnp.exp(jax.random.uniform(next(ks), (h,), jnp.float32, np.log(0.001), np.log(0.1)))
        return {"norm": jnp.ones((d,), dt), "ffn_norm": jnp.ones((d,), dt),
                "w_qkv": columns((q, a_in), (kv, a_in * s["key_multiplier"]), (kv, a_in)),
                "wo": dense(q, d, fan_in=q * s["attention_out_multiplier"] ** 2),
                "w_in": columns((i, s_in * m_z), (i, s_in * m_x), (gn, s_in * m_b), (gn, s_in * m_c)),
                "w_dt": dense(d, h, fan_in=d * (s_in * m_dt) ** 2),
                "conv": dense(s["conv_taps"], c, fan_in=s["conv_taps"]), "conv_bias": dense(c, fan_in=s["conv_taps"]),
                "A_log": jnp.log(jax.random.uniform(next(ks), (h,), jnp.float32, 1.0, 16.0)),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)), "D": jnp.ones((h,), jnp.float32),
                "y_norm": jnp.ones((i,), dt), "w_out": dense(i, d, fan_in=i * s["ssm_out_multiplier"] ** 2),
                "w_gate": dense(d, f, fan_in=d * gate_m ** 2), "w_up": dense(d, f, fan_in=d),
                "w_down": dense(f, d, fan_in=f * down_m ** 2)}

    return {"embed": vocabulary(s["embedding_multiplier"] ** 2), "layers": [layer() for _ in range(s["layers"])],
            "final_norm": jnp.ones((d,), dt), "lm_head": vocabulary(d * s["lm_head_multiplier"] ** 2)}


# -- what the configuration's `assumed` states, one function each ---------------------------------

def _mix(x, attn, ssm, s):
    """ASSUMED parallel_pre_norm: both mixers read the one normed stream; each is added times its way-out scalar."""
    return x + s["attention_out_multiplier"] * attn + s["ssm_out_multiplier"] * ssm


def _rotate(a, pos, s):
    """ASSUMED rope halves_rotated over the whole head: a [Q, heads, dh] at positions pos [Q]."""
    dh = a.shape[-1]
    inv = 1.0 / (s["rope_theta"] ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.outer(pos.astype(jnp.float32), inv)[:, None, :]
    a1, a2 = jnp.split(a, 2, axis=-1)
    return jnp.concatenate([a1 * jnp.cos(ang) - a2 * jnp.sin(ang), a2 * jnp.cos(ang) + a1 * jnp.sin(ang)], -1)


def _in_proj(v, lp, s, prec):
    """ASSUMED in_proj_order z|x|B|C|dt: v [Q, D] -> (z [Q, I], xBC [Q, I + 2 G N], dt [Q, H]), no bias, each
    segment times its entry of ssm_multipliers (the vector m over the projection's columns)."""
    i, gn = inner(s), s["ssm_groups"] * s["ssm_state"]
    *m, m_dt = s["ssm_multipliers"]
    zx = _mm("td,dc->tc", v, lp["w_in"], prec) * np.repeat(np.float32(m), (i, i, gn, gn))
    return zx[:, :i], zx[:, i:], _mm("td,dh->th", v, lp["w_dt"], prec) * m_dt


def _group_of_head(a, s):
    """B or C [Q, G, N] as the heads read it [Q, H, N]: head h reads group h // (H / G)."""
    return jnp.repeat(a, s["ssm_heads"] // s["ssm_groups"], axis=1)


def _position(state, inputs, d_skip, prec):
    """One position of the recurrence, every head: the state [H, P, N] float32; B and C a head's group's [H, N]."""
    xt, bt, ct, step, a = inputs
    state = a[:, None, None] * state + _mm("hp,hn->hpn", step[:, None] * xt, bt, prec)
    return state, _mm("hpn,hn->hp", state, ct, prec) + d_skip[:, None] * xt


def _gate_then_norm(y, z, lp, s):
    """ASSUMED gate_then_rmsnorm_a_group (mamba_rms_norm, mamba_norm_before_gate false): y, z [Q, I]; the statistic
    is over each group's I / G channels, the weight over all I."""
    q, g = y.shape[0], s["ssm_groups"]
    gated = (y * jax.nn.silu(z)).reshape(q, g, -1)
    return _rms_norm(gated, lp["y_norm"].reshape(g, -1), s["norm_eps"]).reshape(q, -1)


def _scaled_swiglu(n, lp, s, prec):
    """ASSUMED mlp_multipliers = (gate, down): the gate scaled BEFORE SiLU, the output after W_down."""
    gate_m, down_m = s["mlp_multipliers"]
    act = jax.nn.silu(gate_m * _mm("td,df->tf", n, lp["w_gate"], prec)) * _mm("td,df->tf", n, lp["w_up"], prec)
    return down_m * _mm("tf,fd->td", act, lp["w_down"], prec)


# -- the layers -------------------------------------------------------------------------------------

def _normed(xb, lp, s):
    return _rms_norm(xb, lp["norm"], s["norm_eps"])


def _qkv(v, lp, s, prec, which):
    """v [Q, D] -> the `which` ("q", or "kv") of: q [Q, H, dh], k = key_multiplier (v W_k) and v W_v [Q, Hkv, dh]; unrotated."""
    n, qw, kw = v.shape[0], s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    if which == "q":
        return _mm("td,dh->th", v, lp["w_qkv"][:, :qw], prec).reshape(n, s["heads"], s["head_dim"])
    kv = _mm("td,dh->th", v, lp["w_qkv"][:, qw:], prec)
    return (s["key_multiplier"] * kv[:, :kw]).reshape(n, s["kv_heads"], s["head_dim"]), kv[:, kw:].reshape(n, s["kv_heads"], s["head_dim"])


def _attention(x, lp, s, prec, q_block):
    """x [T, D] -> Attn(attention_in_multiplier rmsnorm(x)) [T, D]: causal softmax attention over the whole sequence."""
    t, h, hkv, dh = x.shape[0], s["heads"], s["kv_heads"], s["head_dim"]
    idx, split = _blocks(t, q_block)

    def keys_values(args):
        i, xb = args
        k, v = _qkv(s["attention_in_multiplier"] * _normed(xb, lp, s), lp, s, prec, "kv")
        return _rotate(k, i * q_block + jnp.arange(q_block), s), v

    k, v = jax.lax.map(keys_values, (idx, split(x)))
    k, v = k.reshape(t, hkv, dh), v.reshape(t, hkv, dh)
    kpos = jnp.arange(t)

    def queries(args):
        i, xb = args
        pos = i * q_block + jnp.arange(q_block)
        q = _rotate(_qkv(s["attention_in_multiplier"] * _normed(xb, lp, s), lp, s, prec, "q"), pos, s).reshape(q_block, hkv, h // hkv, dh)
        scores = _mm("qkgd,tkd->kgqt", q, k, prec) * dh ** -0.5
        pr = jax.nn.softmax(jnp.where((kpos[None, :] <= pos[:, None])[None, None], scores, -jnp.inf), axis=-1)
        return _mm("th,hd->td", _mm("kgqt,tkd->qkgd", pr, v, prec).reshape(q_block, h * dh), lp["wo"], prec)

    return jax.lax.map(queries, (idx, split(x))).reshape(t, -1)


def _ssm(x, lp, s, prec, q_block):
    """x [T, D] -> SSM(ssm_in_multiplier rmsnorm(x)) [T, D]: the recurrence a position at a time."""
    t, h, p, g, n = x.shape[0], s["ssm_heads"], s["ssm_head_dim"], s["ssm_groups"], s["ssm_state"]
    _, split = _blocks(t, q_block)
    d_skip = lp["D"].astype(jnp.float32)

    def block(carry, xb):
        state, tail = carry
        z, xbc, dt = _in_proj(s["ssm_in_multiplier"] * _normed(xb, lp, s), lp, s, prec)
        xbc, tail = _conv_silu(xbc, tail, lp["conv"], lp["conv_bias"])
        xs = xbc[:, :h * p].reshape(q_block, h, p)
        b, c = (_group_of_head(a.reshape(q_block, g, n), s) for a in (xbc[:, h * p:h * p + g * n], xbc[:, h * p + g * n:]))
        state, y = jax.lax.scan(lambda st, pos: _position(st, pos, d_skip, prec), state, (xs, b, c, *_steps(dt, lp)))
        return (state, tail), _mm("ti,id->td", _gate_then_norm(y.reshape(q_block, h * p), z, lp, s), lp["w_out"], prec)

    start = (jnp.zeros((h, p, n), jnp.float32), jnp.zeros((s["conv_taps"] - 1, channels(s)), jnp.float32))
    _, out = jax.lax.scan(block, start, split(x))
    return out.reshape(t, -1)


def _ffn(h, lp, s, prec, q_block):
    """h [T, D] -> h + FFN(rmsnorm(h)), a block of positions at a time."""
    _, split = _blocks(h.shape[0], q_block)
    return jax.lax.map(lambda hb: hb + _scaled_swiglu(_rms_norm(hb, lp["ffn_norm"], s["norm_eps"]), lp, s, prec), split(h)).reshape(h.shape)


def trunk(params: dict, tokens: jax.Array, s: dict, prec: str = "f32", q_block: int = 512) -> jax.Array:
    """tokens [T] -> the trunk after the last layer [T, D], float32, before the final
    norm. T must divide by q_block (pad at the end: a causal model's earlier
    positions do not see the padding)."""
    t = tokens.shape[0]
    q_block = min(q_block, t)
    if t % q_block:
        raise ValueError(f"{t} positions do not divide into blocks of {q_block}")
    x = params["embed"][tokens].astype(jnp.float32) * s["embedding_multiplier"]
    for lp in params["layers"]:
        x = _ffn(_mix(x, _attention(x, lp, s, prec, q_block), _ssm(x, lp, s, prec, q_block), s), lp, s, prec, q_block)
    return x


def forward(params: dict, tokens: jax.Array, s: dict, prec: str = "f32", q_block: int = 512) -> jax.Array:
    """tokens [T] -> logits [T, V] float32 over the held rows of the head; rows
    before the last HEAD_ROWS are NaN: not computed, and never a row that agrees."""
    x = trunk(params, tokens, s, prec, q_block)
    t, v = x.shape[0], s["vocab"]
    rows = min(HEAD_ROWS, t)
    y = _rms_norm(x[-rows:], params["final_norm"], s["norm_eps"])
    n = VOCAB_SLICES if v % VOCAB_SLICES == 0 else 1
    head = params["lm_head"].reshape(n, v // n, -1)

    def a_slice(i, logits):
        part = s["lm_head_multiplier"] * _mm("td,vd->tv", y, jax.lax.dynamic_index_in_dim(head, i, keepdims=False), prec)
        return jax.lax.dynamic_update_slice(logits, part, (t - rows, i * (v // n)))

    return jax.lax.fori_loop(0, n, a_slice, jnp.full((t, v), jnp.nan, jnp.float32))
