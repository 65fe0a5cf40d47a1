"""The granite_hybrid family's plain reference: Granite-4.0-H's forward pass in
jax.numpy and float32, matrix products at precision "highest".

No kernel, no cache, no chunked form of the recurrence, no sorting of rows, one
sequence at a time, nothing shared with tony_tpu/. x is [T, D]:

  x0 = embedding_multiplier x embed[token]
  every layer, pre-norm with a scaled branch (`_branch`, r = residual_multiplier, eps from the configuration):
      h = x + r Mixer(rmsnorm(x));  y = h + r (Routed(n) + Shared(n)),  n = rmsnorm(h)
  logits = rmsnorm(x_L) embed^T / logits_scaling      (tied; over the held rows of the vocabulary)

A layer's mixer is what `layer_types` says of it:

  mamba      [z | xBC | dt] = W_in u (`_in_proj`: I | I + 2N | H columns, in that order, no bias; the
             parameter tree keeps the dt columns as a leaf of their own); xBC = silu(conv(xBC) + b), a
             causal depthwise convolution of `conv_taps` inputs over time (`_conv_silu`); [x | B | C] =
             xBC, x as H heads of P, B and C of N shared by every head (one group); dt = softplus(dt +
             dt_bias), a = exp(-exp(A_log) dt), no clamp on dt (`_steps`); then THE LITERAL RECURRENCE, a
             scan over positions with a float32 state [P, N] a head, zero at position 0: h = a h + dt x
             B^T; y = h C + D x (`_position`); gate THEN norm: g = y silu(z), rmsnorm over all I channels
             (one group) times a weight (`_gate_then_norm`); W_out.
  attention  q, k, v (H / Hkv / Hkv heads of head_dim), NO rotary embedding, no q/k norm, no bias; a full
             score matrix a block of queries under the causal mask, scores x attention_multiplier
             (`_score_scale`); W_o.

  Routed     logits n W_r [T, E] in float32; the top_k largest logits chosen; gates the softmax over THOSE
             (`_route`); every held expert is computed for every token and masked by `chosen and held`:
             sum_e gate_e W_down (silu(W_gate n) * W_up n). Shared: the same form, width d_shared, added.
  the share  `held = (first, count)`: the experts this replica holds. The router and the gates are over
             all E; what the absent experts would add is left out.

What the published configuration does not give is the configuration's `assumed`
(families/granite_hybrid.py: sizes), each choice one function here and one in
the program.

Positions are processed in blocks of `q_block` wherever a temporary would grow
with the sequence (projections, experts, scores), EXPERT_GROUP experts at a time;
the recurrence runs position by position inside them, the convolution's last
inputs carried from block to block. `forward` returns logits [T, V] whose rows
before the last HEAD_ROWS are NaN (not computed, and a comparison that reads one
cannot pass: NaN is under no limit): the serving check reads 512 rows that lie
within the last 2047 of the padded sequence it builds.

Precisions: "f32" is the reference. "fp8" is the control: the same mathematics
with both operands of every matrix product (the recurrence's write and read
among them) rounded to float8_e4m3, the nearest precision below bf16.

Where the reference makes no statement: the routing ties, in the form
families/exaone_moe_reference.py states them (`held_slack`: how far the nearest
held expert's choosing score, sigmoid(logit) as families/mistral4_reference.py
hands it over, lies from the edge of the chosen set; a row of zeros where that
is under `TIE_MARGIN[dtype]` in some layer). The MARGIN is this family's own, and
it is 0: EVERY position is stated. Ten layers of top-10 of 72 tie all the time:
under the older families' 0.0075 a position's ten edges all have to be clear, and
99.2-99.7% of positions would go unstated (median slack 0.0010-0.0011 over 2 x
1024 positions at the published widths: my chip runs, PR 53), which compares
nothing. And they need no such shelter here: a branch enters the stream times
0.22, and the two experts on either side of the edge carry the SMALLEST of ten
softmax gates, so the expert that a rounding swaps moves the logits by less than
bfloat16 moves them anyway: with every position stated sound runs read 0.00013-
0.00107 and the float8 control 0.0032 and up (PERF.md section 4, PR 53).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from families.exaone_moe_reference import _mm, _rms_norm, _swiglu, held_slack, nll, seed_key  # the statement of a routed FFN's ties is that family's

__all__ = ["CONTROL", "GRAD_LEAVES", "HEAD_ROWS", "TIE_MARGIN", "forward", "init_weights", "nll", "seed_key"]

CONTROL = "fp8"
#: by the dtype the program computes in: how near the edge of the chosen set (in units of sigmoid(logit)) a held
#: expert may lie before `forward` stops stating that position's logits. 0: every position is stated (the
#: module's docstring; PERF.md section 4, PR 53, has the readings and the share that 0.0075 would leave)
TIE_MARGIN = {"bfloat16": 0.0}
#: no training cell: no gradient is compared
GRAD_LEAVES = ()
#: rows at the end of a sequence whose logits `forward` computes
HEAD_ROWS = 2048
#: held experts whose products over a block of positions exist at a time
EXPERT_GROUP = 6
BANKS = ("we_gate", "we_up", "we_down")

ATTENTION = "attention"                                      # a layer of any other kind is a `mamba` one (families/granite_hybrid.py refuses the rest)


def init_weights(key: jax.Array, s: dict) -> dict:
    """The parameter tree in the layout tony_tpu/models/granite_hybrid.py reads:
    `layers` a list with one dict of leaves a layer, shaped by its mixer; the held
    experts' banks every layer's, stacked, drawn a layer at a time (the float32
    draw of every layer's bank at once is as large again as the weights).
    Truncated normal, fan-in scaled; norms at one; the router float32; A_log = log
    U(1, 16), dt_bias the inverse softplus of exp U(log 0.001, log 0.1), D = 1,
    float32. `w_in` is the projection's z | xBC, `w_dt` its dt columns. The tied
    embedding is drawn as a head is (fan-in d_model) over embedding_multiplier
    (ASSUMED embed_init): at the other families' fan-in of 1 the stream would be 12
    x the token's own embedding and the tied head would give that token a logit
    (12 |e|^2) that no layer and no rounding could move, and a comparison of
    chosen tokens would pass any precision."""
    d, v, dt, n = s["d_model"], s["vocab"], jnp.dtype(s["dtype"]), len(s["layer_types"])
    fe, fs, held, e = s["d_expert"], s["d_shared"], s["held"][1], s["num_experts"]
    ks = iter(jax.random.split(key, 8 + 16 * n))

    def draw(k, shape, fan_in, dtype=dt):
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32) * fan_in ** -0.5).astype(dtype)

    def dense(*shape, fan_in, dtype=dt):
        return draw(next(ks), shape, fan_in, dtype)

    def stack(*shape, fan_in):
        return jax.lax.map(lambda k: draw(k, shape, fan_in), jax.random.split(next(ks), n))

    def layer(kind):
        lp = {"mixer_norm": jnp.ones((d,), dt), "ffn_norm": jnp.ones((d,), dt), "router": dense(d, e, fan_in=d, dtype=jnp.float32),
              "ws_gate": dense(d, fs, fan_in=d), "ws_up": dense(d, fs, fan_in=d), "ws_down": dense(fs, d, fan_in=fs)}
        if kind == ATTENTION:
            q, kv = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
            return {**lp, "w_qkv": dense(d, q + 2 * kv, fan_in=d), "wo": dense(q, d, fan_in=q)}
        h, i, c = s["ssm_heads"], inner(s), channels(s)
        step = jnp.exp(jax.random.uniform(next(ks), (h,), jnp.float32, np.log(0.001), np.log(0.1)))
        return {**lp, "w_in": dense(d, i + c, fan_in=d), "w_dt": dense(d, h, fan_in=d),
                "conv": dense(s["conv_taps"], c, fan_in=s["conv_taps"]), "conv_bias": dense(c, fan_in=s["conv_taps"]),
                "A_log": jnp.log(jax.random.uniform(next(ks), (h,), jnp.float32, 1.0, 16.0)),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)), "D": jnp.ones((h,), jnp.float32),
                "y_norm": jnp.ones((i,), dt), "w_out": dense(i, d, fan_in=i)}

    return {"embed": dense(v, d, fan_in=s["embedding_multiplier"] ** 2 * d), "layers": [layer(kind) for kind in s["layer_types"]],
            "we_gate": stack(held, d, fe, fan_in=d), "we_up": stack(held, d, fe, fan_in=d), "we_down": stack(held, fe, d, fan_in=fe),
            "final_norm": jnp.ones((d,), dt)}


def inner(s: dict) -> int:
    """The state-space layers' inner width: every head's x, side by side."""
    return s["ssm_heads"] * s["ssm_head_dim"]


def channels(s: dict) -> int:
    """What the convolution runs over: x of every head, then B and C (one group)."""
    return inner(s) + 2 * s["ssm_state"]


# -- what the configuration's `assumed` states, one function each ---------------------------------

def _branch(x, y, s):
    """ASSUMED pre_norm_scaled_branch: a branch reads the normed stream and is added to the stream times residual_multiplier."""
    return x + s["residual_multiplier"] * y


def _in_proj(u, lp, prec):
    """ASSUMED in_proj_order z|xBC|dt: u [Q, D] -> (z [Q, I], xBC [Q, I + 2N], dt [Q, H]), no bias."""
    zx = _mm("td,dc->tc", u, lp["w_in"], prec)
    i = zx.shape[1] - lp["conv"].shape[1]
    return zx[:, :i], zx[:, i:], _mm("td,dh->th", u, lp["w_dt"], prec)


def _conv_silu(u, tail, w, b):
    """u [Q, C] after the inputs tail [taps - 1, C]: silu(sum_j w_j u_{t - (taps-1) + j} + b), and the new tail."""
    q = u.shape[0]
    xp = jnp.concatenate([tail, u])
    return jax.nn.silu(sum(w[j].astype(jnp.float32) * xp[j:j + q] for j in range(w.shape[0])) + b.astype(jnp.float32)), xp[q:]


def _steps(dt, lp):
    """ASSUMED no clamp on dt: dt [Q, H] -> (the step softplus(dt + dt_bias), the decay a = exp(-exp(A_log) step))."""
    step = jax.nn.softplus(dt + lp["dt_bias"].astype(jnp.float32))
    return step, jnp.exp(-jnp.exp(lp["A_log"].astype(jnp.float32)) * step)


def _position(h, inputs, d_skip, prec):
    """One position of the recurrence, every head: the state h [H, P, N] float32."""
    xt, bt, ct, step, a = inputs
    h = a[:, None, None] * h + _mm("hp,n->hpn", step[:, None] * xt, bt, prec)
    return h, _mm("hpn,n->hp", h, ct, prec) + d_skip[:, None] * xt


def _gate_then_norm(y, z, lp, s):
    """ASSUMED gate_then_rmsnorm_over_the_inner_width: y, z [Q, I]."""
    return _rms_norm(y * jax.nn.silu(z), lp["y_norm"], s["norm_eps"])


def _score_scale(s: dict) -> float:
    """`attention_multiplier`, in head_dim^-0.5's place."""
    return float(s["attention_multiplier"])


def _route(logits, s):
    """[T, E] float32 logits -> (gates [T, E]: the top_k largest LOGITS chosen, the
    softmax over those, zero elsewhere; chosen bool [T, E])."""
    order = jnp.argsort(-logits, axis=-1, stable=True)[:, :s["top_k"]]
    chosen = jnp.zeros(logits.shape, bool).at[jnp.arange(logits.shape[0])[:, None], order].set(True)
    return jax.nn.softmax(jnp.where(chosen, logits, -jnp.inf), axis=-1), chosen


# -- the layers -------------------------------------------------------------------------------------

def _blocks(t: int, q_block: int):
    return jnp.arange(t // q_block), lambda a: a.reshape(t // q_block, q_block, *a.shape[1:])


def _mamba_layer(x, lp, s, prec, q_block):
    """x [T, D] -> x + r (the state-space mixer's branch)."""
    t, h, p, n = x.shape[0], s["ssm_heads"], s["ssm_head_dim"], s["ssm_state"]
    _, split = _blocks(t, q_block)
    d_skip = lp["D"].astype(jnp.float32)

    def block(carry, xb):
        state, tail = carry
        u = _rms_norm(xb, lp["mixer_norm"], s["norm_eps"])
        z, xbc, dt = _in_proj(u, lp, prec)
        xbc, tail = _conv_silu(xbc, tail, lp["conv"], lp["conv_bias"])
        xs, b, c = xbc[:, :h * p].reshape(q_block, h, p), xbc[:, h * p:h * p + n], xbc[:, h * p + n:]
        state, y = jax.lax.scan(lambda st, pos: _position(st, pos, d_skip, prec), state, (xs, b, c, *_steps(dt, lp)))
        out = _mm("ti,id->td", _gate_then_norm(y.reshape(q_block, h * p), z, lp, s), lp["w_out"], prec)
        return (state, tail), _branch(xb, out, s)

    start = (jnp.zeros((h, p, n), jnp.float32), jnp.zeros((s["conv_taps"] - 1, channels(s)), jnp.float32))
    _, out = jax.lax.scan(block, start, split(x))
    return out.reshape(t, -1)


def _attention_layer(x, lp, s, prec, q_block):
    """x [T, D] -> x + r (causal softmax attention's branch); no rotary embedding."""
    t, h, hkv, dh = x.shape[0], s["heads"], s["kv_heads"], s["head_dim"]
    idx, split = _blocks(t, q_block)
    qw, kw = h * dh, hkv * dh

    def keys_values(xb):
        kv = _mm("td,dh->th", _rms_norm(xb, lp["mixer_norm"], s["norm_eps"]), lp["w_qkv"][:, qw:], prec)
        return kv[:, :kw].reshape(q_block, hkv, dh), kv[:, kw:].reshape(q_block, hkv, dh)

    k, v = jax.lax.map(keys_values, split(x))
    k, v = k.reshape(t, hkv, dh), v.reshape(t, hkv, dh)
    kpos = jnp.arange(t)

    def queries(args):
        i, xb = args
        u = _rms_norm(xb, lp["mixer_norm"], s["norm_eps"])
        q = _mm("td,dh->th", u, lp["w_qkv"][:, :qw], prec).reshape(q_block, hkv, h // hkv, dh)
        scores = _mm("qkgd,tkd->kgqt", q, k, prec) * _score_scale(s)
        seen = kpos[None, :] <= (i * q_block + jnp.arange(q_block))[:, None]
        pr = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        o = _mm("kgqt,tkd->qkgd", pr, v, prec).reshape(q_block, h * dh)
        return _branch(xb, _mm("th,hd->td", o, lp["wo"], prec), s)

    return jax.lax.map(queries, (idx, split(x))).reshape(t, -1)


def routed_ffn_and_slack(h, lp, banks, s, prec="f32", held=None, shared=True, layer=None):
    """h [T, D] (normed) -> (the layer's FFN output [T, D], Routed + Shared, for
    the share `held` = (first, count) (default: the configuration's), `banks`
    (we_gate, we_up, we_down) being those experts' (with `layer`: every layer's,
    stacked, and this the index of the layer's; a layer's slice of them would be
    a copy of 1 GB); `held_slack` [T], the choosing score sigmoid(logit)). Every
    held expert for every token, masked; EXPERT_GROUP experts' products exist at
    a time."""
    first, count = held = held or s["held"]
    logits = _mm("td,de->te", h, lp["router"], prec)
    w, chosen = _route(logits, s)
    w = w[:, first:first + count]                                        # what lands on an absent expert is left out
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
    return y, held_slack(jax.nn.sigmoid(logits), jnp.zeros((logits.shape[-1],), jnp.float32), chosen, held)


def _ffn(x, lp, banks, s, prec, q_block, layer):
    """x [T, D] -> (x + r FFN(norm(x)), `held_slack` [T]), a block of positions at a time."""
    _, split = _blocks(x.shape[0], q_block)

    def one(xb):
        y, slack = routed_ffn_and_slack(_rms_norm(xb, lp["ffn_norm"], s["norm_eps"]), lp, banks, s, prec, layer=layer)
        return _branch(xb, y, s), slack

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
    x, slack = params["embed"][tokens].astype(jnp.float32) * s["embedding_multiplier"], jnp.full((t,), jnp.inf)
    for l, (kind, lp) in enumerate(zip(s["layer_types"], params["layers"], strict=True)):
        mixer = _attention_layer if kind == ATTENTION else _mamba_layer
        x, layer_slack = _ffn(mixer(x, lp, s, prec, q_block), lp, banks, s, prec, q_block, l)
        slack = jnp.minimum(slack, layer_slack)
    return x, slack


def forward(params: dict, tokens: jax.Array, s: dict, prec: str = "f32", q_block: int = 512) -> jax.Array:
    """tokens [T] -> logits [T, V] float32 over the held rows of the tied
    embedding; rows before the last HEAD_ROWS are NaN: not computed, and never a
    row that agrees. At "f32", where the program computes in a dtype with a
    `TIE_MARGIN` above 0: a row of zeros at a position whose routing of a held
    expert lies within that margin of a tie in some layer."""
    x, slack = trunk(params, tokens, s, prec, q_block)
    rows = min(HEAD_ROWS, x.shape[0])
    y = _rms_norm(x[-rows:], params["final_norm"], s["norm_eps"])
    logits = _mm("td,vd->tv", y, params["embed"], prec) / s["logits_scaling"]
    margin = TIE_MARGIN.get(s["dtype"], 0.0) if prec == "f32" else 0.0
    if margin:
        logits = jnp.where((slack[-rows:] < margin)[:, None], 0.0, logits)
    return jnp.pad(logits, ((x.shape[0] - rows, 0), (0, 0)), constant_values=jnp.nan)
