"""The solar_open2 family's plain reference: Solar Open 2's forward pass in jax.numpy
and float32, matrix products at precision "highest".

No kernel, no cache, no blocked form of the recurrence, no sorting of rows, one
sequence at a time, nothing shared with tony_tpu/. x is [T, D]:

  x0 = embed[token]
  every layer, pre-norm (`_block_norm`, eps from the configuration):
      h = x + Mixer(rmsnorm(x));  y = h + Routed(n) + Shared(n),  n = rmsnorm(h)
  logits = rmsnorm(x_L) W_head                     (untied; over the held rows of the vocabulary)

A layer's mixer is what `layer_types` says of it (`gqa_layers` in the published file):

  kda        Kimi Delta Attention (arXiv:2510.26692). u = rmsnorm(x); [q | k | v] = silu(conv(u W_qkv)), a
             causal depthwise convolution of `conv_taps` inputs over time, no bias (`_conv_silu`); H heads of dk
             each; q and k L2-normed a head AFTER the SiLU, q times dk^-1/2 (`_qk`); the log-decay a head AND
             CHANNEL g = -exp(A_log[h]) softplus(u W_fa W_fb + dt_bias) [H, dk], through a projection of rank
             `gate_rank` (`_decay`); beta = 2 sigmoid(u W_b) a head, in (0, 2) (`_beta`:
             kda_allow_neg_eigval); then THE LITERAL RECURRENCE, a scan over positions with a float32 state
             [dk, dv] a head, zero at position 0 (`_position`):
                 S' = Diag(exp g) S;  w = beta (v - S'^T k);  S = S' + k w^T;  o = S^T q
             out = (rmsnorm_head(o) * sigmoid(u W_ga W_gb)) W_o: a norm over each head's dv with one weight for
             all heads, times the output gate, low rank too (`_gated_head_norm`).
  attention  q, k, v (H / Hkv / Hkv heads of head_dim), NO rotary embedding (use_rope false), no q/k norm, no
             bias; a full score matrix a block of queries under the causal mask, scores / sqrt(head_dim);
             out = (attn * sigmoid(u W_gate)) W_o, the gate elementwise from the layer's input (`_output_gate`:
             use_gqa_gate, arXiv:2505.06708).

  Routed     s = sigmoid(n W_r) [T, E] in float32; C = the top_k largest of s + b (b chooses and does not
             weigh: `_choose`); w_e = routed_scale s_e / sum over C of s (`_gates`: norm_topk_prob); every held
             expert is computed for every token and masked by `C and held`:
             sum_e w_e W_down (silu(W_gate n) * W_up n). Shared: the same form, width d_shared, added.
  the share  `held = (first, count)`: the experts this replica holds. The router and the gates are over all E;
             what the absent experts would add is left out.

What the published configuration does not give is the configuration's `assumed`
(families/solar_open2.py: sizes), each choice one function here and one in the
program.

Positions are processed in blocks of `q_block` wherever a temporary would grow
with the sequence (projections, experts, scores), EXPERT_GROUP experts at a time;
the recurrence runs position by position inside them, its state and the
convolution's last inputs carried from block to block. `forward` returns logits
[T, V] whose rows before the last HEAD_ROWS are NaN (not computed, and a comparison
that reads one cannot pass: NaN is under no limit): the serving check reads 512
rows that lie within the last 2047 of the padded sequence it builds.

Precisions: "f32" is the reference. "fp8" is the control: the same mathematics
with both operands of every matrix product (the recurrence's probe, write and
read among them, so the state too) rounded to float8_e4m3, the nearest precision
below bf16.

Where the reference makes no statement: the routing ties, in the form
families/exaone_moe_reference.py states them (`held_slack`: how far the nearest
held expert's choosing score lies from the edge of the chosen set; a row of zeros
where that is under `TIE_MARGIN[dtype]` in some layer). Top-8 of 320 sigmoid
scores, 40 of them held, in each of four layers: the lineage and the situation of
that family (top-8 of 128, 16 held, four routed layers), and its margin.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from families.exaone_moe_reference import _mm, _rms_norm, _swiglu, held_slack, nll, seed_key  # the statement of a routed FFN's ties is that family's

__all__ = ["CONTROL", "GRAD_LEAVES", "HEAD_ROWS", "TIE_MARGIN", "forward", "init_weights", "nll", "seed_key"]

CONTROL = "fp8"
#: by the dtype the program computes in: how near the edge of the chosen set (in units of the choosing score,
#: sigmoid + bias) a held expert may lie before `forward` stops stating that position's logits (PERF.md section 4,
#: PR 57, has the readings with and without it)
TIE_MARGIN = {"bfloat16": 0.0075}
#: no training cell: no gradient is compared
GRAD_LEAVES = ()
#: rows at the end of a sequence whose logits `forward` computes
HEAD_ROWS = 2048
#: held experts whose products over a block of positions exist at a time
EXPERT_GROUP = 8
BANKS = ("we_gate", "we_up", "we_down")

ATTENTION = "attention"                                      # a layer of any other kind is a `kda` one (families/solar_open2.py refuses the rest)


def init_weights(key: jax.Array, s: dict) -> dict:
    """The parameter tree in the layout tony_tpu/models/solar_open2.py reads: `layers`
    a list with one dict of leaves a layer, shaped by its mixer; the held experts'
    banks every layer's, stacked, drawn a layer at a time (the float32 draw of every
    layer's bank at once is as large again as the weights). Truncated normal, fan-in
    scaled; norms at one; the router float32, its bias `router_bias_scale` x a normal
    (ASSUMED: small and not zero, so that choosing by s + b and weighing by s can be
    told apart and the seeded load stays near even); A_log = log U(1, 16) a head,
    dt_bias a head and channel the inverse softplus of exp U(log 0.001, log 1),
    float32 (ASSUMED kda_init: exp(g) from 1e-7 to 0.999 a position before the token's
    own term, strong and weak forgetting side by side in one head)."""
    d, v, dt, n = s["d_model"], s["vocab"], jnp.dtype(s["dtype"]), len(s["layer_types"])
    fe, fs, held, e = s["d_expert"], s["d_shared"], s["held"][1], s["num_experts"]
    ks = iter(jax.random.split(key, 8 + 16 * n))

    def draw(k, shape, fan_in, dtype=dt, scale=1.0):
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32) * (scale * fan_in ** -0.5)).astype(dtype)

    def dense(*shape, fan_in, dtype=dt, scale=1.0):
        return draw(next(ks), shape, fan_in, dtype, scale)

    def stack(*shape, fan_in):
        return jax.lax.map(lambda k: draw(k, shape, fan_in), jax.random.split(next(ks), n))

    def layer(kind):
        lp = {"mixer_norm": jnp.ones((d,), dt), "ffn_norm": jnp.ones((d,), dt), "router": dense(d, e, fan_in=d, dtype=jnp.float32),
              "router_bias": dense(e, fan_in=1.0, dtype=jnp.float32, scale=s["router_bias_scale"]),
              "ws_gate": dense(d, fs, fan_in=d), "ws_up": dense(d, fs, fan_in=d), "ws_down": dense(fs, d, fan_in=fs)}
        if kind == ATTENTION:
            q, kv = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
            return {**lp, "w_qkv": dense(d, q + 2 * kv, fan_in=d), "w_gate": dense(d, q, fan_in=d), "wo": dense(q, d, fan_in=q)}
        h, w, r = s["kda_heads"], width(s), s["gate_rank"]
        step = jnp.exp(jax.random.uniform(next(ks), (w,), jnp.float32, np.log(0.001), 0.0))
        return {**lp, "w_qkv": dense(d, 3 * w, fan_in=d), "conv": dense(s["conv_taps"], 3 * w, fan_in=s["conv_taps"]),
                "w_fa": dense(d, r, fan_in=d), "w_fb": dense(r, w, fan_in=r), "w_b": dense(d, h, fan_in=d),
                "w_ga": dense(d, r, fan_in=d), "w_gb": dense(r, w, fan_in=r),
                "A_log": jnp.log(jax.random.uniform(next(ks), (h,), jnp.float32, 1.0, 16.0)),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)), "o_norm": jnp.ones((s["kda_head_dim"],), dt),
                "wo": dense(w, d, fan_in=w)}

    return {"embed": dense(v, d, fan_in=1.0), "layers": [layer(kind) for kind in s["layer_types"]],
            "we_gate": stack(held, d, fe, fan_in=d), "we_up": stack(held, d, fe, fan_in=d), "we_down": stack(held, fe, d, fan_in=fe),
            "final_norm": jnp.ones((d,), dt), "lm_head": dense(d, v, fan_in=d)}


def width(s: dict) -> int:
    """The recurrent layers' width: every head's channels, side by side (q, k and v have it each)."""
    return s["kda_heads"] * s["kda_head_dim"]


# -- what the configuration's `assumed` states, one function each ---------------------------------

def _block_norm(x, w, s):
    """ASSUMED pre_norm: a branch reads the normed stream and is added to the stream itself."""
    return _rms_norm(x, w, s["norm_eps"])


def _conv_silu(u, tail, w):
    """ASSUMED conv_then_silu: u [Q, C] after the inputs tail [taps - 1, C]: silu(sum_j w_j u_{t - (taps-1) + j}), no
    bias, and the new tail."""
    q = u.shape[0]
    xp = jnp.concatenate([tail, u])
    return jax.nn.silu(sum(w[j].astype(jnp.float32) * xp[j:j + q] for j in range(w.shape[0]))), xp[q:]


def _l2(a, eps):
    return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + eps)


def _qk(y, s):
    """ASSUMED then_l2_norm: the convolution's q and k [Q, H, dk], after their SiLU, L2-normed a head; q times dk^-1/2."""
    w, h, dk = width(s), s["kda_heads"], s["kda_head_dim"]
    q, k = (y[:, i * w:(i + 1) * w].reshape(-1, h, dk) for i in (0, 1))
    return _l2(q, s["norm_eps"]) * dk ** -0.5, _l2(k, s["norm_eps"])


def _low_rank(u, wa, wb, prec):
    """ASSUMED low_rank gates (kda_use_full_proj false): u W_a W_b through `gate_rank` channels."""
    return _mm("tr,rw->tw", _mm("td,dr->tr", u, wa, prec), wb, prec)


def _decay(u, lp, s, prec):
    """ASSUMED the decay's form: g = -exp(A_log[h]) softplus(u W_fa W_fb + dt_bias) [Q, H, dk], a channel's own."""
    rate = jax.nn.softplus(_low_rank(u, lp["w_fa"], lp["w_fb"], prec) + lp["dt_bias"].astype(jnp.float32))
    return -jnp.exp(lp["A_log"].astype(jnp.float32))[None, :, None] * rate.reshape(-1, s["kda_heads"], s["kda_head_dim"])


def _beta(u, lp, prec):
    """kda_allow_neg_eigval: beta = 2 sigmoid(u W_b) [Q, H], so that I - beta k k^T has eigenvalues down to -1."""
    return 2.0 * jax.nn.sigmoid(_mm("td,dh->th", u, lp["w_b"], prec))


def _position(S, inputs, prec):
    """One position of the rule, every head: the state S [H, dk, dv] float32."""
    qt, kt, vt, gt, bt = inputs
    S = jnp.exp(gt)[:, :, None] * S
    w = bt[:, None] * (vt - _mm("hkv,hk->hv", S, kt, prec))
    S = S + _mm("hk,hv->hkv", kt, w, prec)
    return S, _mm("hkv,hk->hv", S, qt, prec)


def _gated_head_norm(o, u, lp, s, prec):
    """ASSUMED the output's form: o [Q, H, dv] -> rmsnorm over each head's dv (one weight for all heads) times
    sigmoid(u W_ga W_gb), [Q, H x dv]."""
    gate = jax.nn.sigmoid(_low_rank(u, lp["w_ga"], lp["w_gb"], prec))
    return _rms_norm(o, lp["o_norm"], s["norm_eps"]).reshape(o.shape[0], -1) * gate


def _output_gate(o, u, lp, prec):
    """ASSUMED use_gqa_gate: the attention's output [Q, H x dh] times sigmoid(u W_gate), elementwise, before W_o."""
    return o * jax.nn.sigmoid(_mm("td,dh->th", u, lp["w_gate"], prec))


def _choose(scores, bias, s):
    """ASSUMED the lineage's choosing bias: [T, E] scores -> bool [T, E], the top_k largest of score + bias; one group."""
    order = jnp.argsort(-(scores + bias.astype(jnp.float32)), axis=-1, stable=True)[:, :s["top_k"]]
    return jnp.zeros(scores.shape, bool).at[jnp.arange(scores.shape[0])[:, None], order].set(True)


def _gates(scores, bias, chosen, s):
    """ASSUMED the bias chooses and does not weigh (norm_topk_prob): the chosen SCORES over their sum, times
    routed_scale; `bias` is handed over and left out."""
    kept = jnp.where(chosen, scores, 0.0)
    return s["routed_scale"] * kept / jnp.sum(kept, axis=-1, keepdims=True)


# -- the layers -------------------------------------------------------------------------------------

def _blocks(t: int, q_block: int):
    return jnp.arange(t // q_block), lambda a: a.reshape(t // q_block, q_block, *a.shape[1:])


def _kda_layer(x, lp, s, prec, q_block):
    """x [T, D] -> x + (the recurrent mixer's branch)."""
    t, h, dk, w = x.shape[0], s["kda_heads"], s["kda_head_dim"], width(s)
    _, split = _blocks(t, q_block)

    def block(carry, xb):
        state, tail = carry
        u = _block_norm(xb, lp["mixer_norm"], s)
        y, tail = _conv_silu(_mm("td,dc->tc", u, lp["w_qkv"], prec), tail, lp["conv"])
        q, k = _qk(y, s)
        inputs = (q, k, y[:, 2 * w:].reshape(q_block, h, dk), _decay(u, lp, s, prec), _beta(u, lp, prec))
        state, o = jax.lax.scan(lambda st, pos: _position(st, pos, prec), state, inputs)
        return (state, tail), xb + _mm("ti,id->td", _gated_head_norm(o, u, lp, s, prec), lp["wo"], prec)

    start = (jnp.zeros((h, dk, dk), jnp.float32), jnp.zeros((s["conv_taps"] - 1, 3 * w), jnp.float32))
    _, out = jax.lax.scan(block, start, split(x))
    return out.reshape(t, -1)


def _attention_layer(x, lp, s, prec, q_block):
    """x [T, D] -> x + (gated causal softmax attention's branch); no rotary embedding."""
    t, h, hkv, dh = x.shape[0], s["heads"], s["kv_heads"], s["head_dim"]
    idx, split = _blocks(t, q_block)
    qw, kw = h * dh, hkv * dh

    def keys_values(xb):
        kv = _mm("td,dh->th", _block_norm(xb, lp["mixer_norm"], s), lp["w_qkv"][:, qw:], prec)
        return kv[:, :kw].reshape(q_block, hkv, dh), kv[:, kw:].reshape(q_block, hkv, dh)

    k, v = jax.lax.map(keys_values, split(x))
    k, v = k.reshape(t, hkv, dh), v.reshape(t, hkv, dh)
    kpos = jnp.arange(t)

    def queries(args):
        i, xb = args
        u = _block_norm(xb, lp["mixer_norm"], s)
        q = _mm("td,dh->th", u, lp["w_qkv"][:, :qw], prec).reshape(q_block, hkv, h // hkv, dh)
        scores = _mm("qkgd,tkd->kgqt", q, k, prec) * dh ** -0.5
        seen = kpos[None, :] <= (i * q_block + jnp.arange(q_block))[:, None]
        pr = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        o = _mm("kgqt,tkd->qkgd", pr, v, prec).reshape(q_block, h * dh)
        return xb + _mm("th,hd->td", _output_gate(o, u, lp, prec), lp["wo"], prec)

    return jax.lax.map(queries, (idx, split(x))).reshape(t, -1)


def routed_ffn_and_slack(h, lp, banks, s, prec="f32", held=None, shared=True, layer=None):
    """h [T, D] (normed) -> (the layer's FFN output [T, D], Routed + Shared, for
    the share `held` = (first, count) (default: the configuration's), `banks`
    (we_gate, we_up, we_down) being those experts' (with `layer`: every layer's,
    stacked, and this the index of the layer's); `held_slack` [T]). Every held
    expert for every token, masked; EXPERT_GROUP experts' products exist at a time."""
    first, count = held = held or s["held"]
    scores = jax.nn.sigmoid(_mm("td,de->te", h, lp["router"], prec))
    chosen = _choose(scores, lp["router_bias"], s)
    w = _gates(scores, lp["router_bias"], chosen, s)[:, first:first + count]                # what lands on an absent expert is left out
    group = next(g for g in range(min(EXPERT_GROUP, count), 0, -1) if count % g == 0)

    def bank(b, g):
        if layer is None:
            return jax.lax.dynamic_slice_in_dim(b, g * group, group, axis=0)
        return jax.lax.dynamic_slice(b, (layer, g * group, 0, 0), (1, group, *b.shape[2:]))[0]

    def experts(g):
        act = jax.nn.silu(_mm("td,edf->tef", h, bank(banks[0], g), prec)) * _mm("td,edf->tef", h, bank(banks[1], g), prec)
        return jnp.einsum("ted,te->td", _mm("tef,efd->ted", act, bank(banks[2], g), prec),
                          jax.lax.dynamic_slice_in_dim(w, g * group, group, axis=1), precision=jax.lax.Precision.HIGHEST)

    y = jax.lax.map(experts, jnp.arange(count // group)).sum(axis=0)
    if shared:
        y = y + _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"], prec)
    return y, held_slack(scores, lp["router_bias"], chosen, held)


def _ffn(x, lp, banks, s, prec, q_block, layer):
    """x [T, D] -> (x + FFN(norm(x)), `held_slack` [T]), a block of positions at a time."""
    _, split = _blocks(x.shape[0], q_block)

    def one(xb):
        y, slack = routed_ffn_and_slack(_block_norm(xb, lp["ffn_norm"], s), lp, banks, s, prec, layer=layer)
        return xb + y, slack

    y, slack = jax.lax.map(one, split(x))
    return y.reshape(x.shape), slack.reshape(x.shape[0])


def trunk(params: dict, tokens: jax.Array, s: dict, prec: str = "f32", q_block: int = 512):
    """tokens [T] -> (the trunk after the last layer [T, D], float32, before the
    final norm; the smallest `held_slack` of the position's layers [T]). T must
    divide by q_block (pad at the end: a causal model's earlier positions do not
    see the padding)."""
    t = tokens.shape[0]
    q_block = min(q_block, t)
    if t % q_block:
        raise ValueError(f"{t} positions do not divide into blocks of {q_block}")
    banks = tuple(params[k] for k in BANKS)
    x, slack = params["embed"][tokens].astype(jnp.float32), jnp.full((t,), jnp.inf)
    for l, (kind, lp) in enumerate(zip(s["layer_types"], params["layers"], strict=True)):
        mixer = _attention_layer if kind == ATTENTION else _kda_layer
        x, layer_slack = _ffn(mixer(x, lp, s, prec, q_block), lp, banks, s, prec, q_block, l)
        slack = jnp.minimum(slack, layer_slack)
    return x, slack


def forward(params: dict, tokens: jax.Array, s: dict, prec: str = "f32", q_block: int = 512) -> jax.Array:
    """tokens [T] -> logits [T, V] float32 over the held rows of the vocabulary;
    rows before the last HEAD_ROWS are NaN: not computed, and never a row that
    agrees. At "f32", where the program computes in a dtype with a `TIE_MARGIN`
    above 0: a row of zeros at a position whose routing of a held expert lies
    within that margin of a tie in some layer."""
    x, slack = trunk(params, tokens, s, prec, q_block)
    rows = min(HEAD_ROWS, x.shape[0])
    logits = _mm("td,dv->tv", _rms_norm(x[-rows:], params["final_norm"], s["norm_eps"]), params["lm_head"], prec)
    margin = TIE_MARGIN.get(s["dtype"], 0.0) if prec == "f32" else 0.0
    if margin:
        logits = jnp.where((slack[-rows:] < margin)[:, None], 0.0, logits)
    return jnp.pad(logits, ((x.shape[0] - rows, 0), (0, 0)), constant_values=jnp.nan)
