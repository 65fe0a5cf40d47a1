"""The exaone_moe family's plain reference: K-EXAONE's forward pass in jax.numpy
and float32, matrix products at precision "highest".

No kernel, no cache, no chunk, no sorting of rows, one sequence at a time,
nothing shared with tony_tpu/. x is [T, D]; eps from the configuration.

  attention   h = rmsnorm(x, g_in); q = h Wq [T, H, dh], k = h Wk, v = h Wv
              [T, Hkv, dh], no biases; q, k = rmsnorm over each head's dh; on a
              sliding_attention layer q and k are rotated (rotate-half, theta
              from the configuration), on a full_attention layer they are not;
              scores q_i . k_j / sqrt(dh) for j <= i, and on a sliding layer
              i - j < window: A FULL SCORE MATRIX a block of queries, the
              window a mask; H / Hkv query heads a kv head; x + concat(heads) Wo.
  dense FFN   the first `dense_layers` layers: h = rmsnorm(x, g_post);
              x + (silu(h Wg) * (h Wu)) Wd.
  routed FFN  every other layer: s = sigmoid(h Wr) [T, E] in float32; C = the
              top_k largest of s + b (b chooses and does not weigh); w_e =
              routed_scale * s_e / sum over C of s; EVERY held expert is
              computed for every token and masked by `C and held`:
              x + sum_e w_e ffn_e(h) + ffn_shared(h). No capacity: nothing is dropped.
  the share   `held = (first, count)`: the experts this replica holds. The router
              and the normalisation are over all E; what the absent experts
              would add is left out, here as in the program.
  head        rmsnorm(x, g_f) W_head, not tied.
  prediction  `mtp_logits`: u_i = W_p [rmsnorm(x_i) ; rmsnorm(embed(t_{i+1}))],
              one full-attention routed layer over u, the trunk's final norm and head.

Departures from the published description, each an entry of the configuration's
`assumed` (the config's keys give sizes and switches, not equations): pre-norm
blocks; RMSNorm on q and k; the rotary embedding on sliding layers only; the
choosing bias; the prediction module's form. Each is one function here
(`_block_norms`, `_qk_norm`, `_positions`, `_choose`, `mtp_logits`) and one in the program.

Positions are processed in blocks of `q_block` wherever a temporary would grow
with the sequence (projections, scores, every expert for every token).

Precisions: "f32" is the reference. "fp8" is the control: the same mathematics
with both operands of every matrix product rounded to float8_e4m3, the nearest
precision below bf16.

Where the reference makes no statement (`TIE_MARGIN`, `held_slack`). Choosing
the top_k of 128 scores is a step function of the residual stream: where a held
expert's choosing score lies within a rounding error of the edge of the chosen
set, a program that computes in bfloat16 takes one side, this reference the
other, both are the model, and the two outputs differ by a whole expert's
contribution (measured on the chip, PERF.md section 6: with every row stated a
bfloat16 program's worst gap was 0.16-0.50 and the float8 control's 0.40-0.84,
so no limit could pass the one and fail the other). `forward` at "f32" therefore returns a row of zeros at a
position where, in some routed layer, a held expert's choosing score lies within
`TIE_MARGIN[dtype]` of that edge: a row of equal logits lies equally far from
every token, so the comparison reads no gap there, whatever was chosen. Every
other row is compared as tightly as a dense model's. A float32 program (the CPU
tests) has margin 0: every row is stated.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.scipy.special import ndtr, ndtri

CONTROL = "fp8"
#: by the dtype the program computes in: how near the edge of the chosen set (in units of the choosing score,
#: sigmoid + bias) a held expert may lie before `forward` stops stating that position's logits. bfloat16, at the
#: published widths on the chip (6 seeds x 4096 positions, PERF.md section 6): the program chose otherwise than
#: this reference at 153 positions of 24,576, whose slack was 0.0011 rms and 0.0037 at most; 0.0075 is twice
#: that largest one and leaves 54% of positions stated, on which the program's worst gap was 0.024 and the
#: float8 control's 0.30 to 0.61
TIE_MARGIN = {"bfloat16": 0.0075}
#: no training cell: no gradient is compared
GRAD_LEAVES = ()
BANKS = ("we_gate", "we_up", "we_down")


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 65536), seed // 65536)


def choosing_bias(key: jax.Array, layers: int, experts: int, held: int) -> jax.Array:
    """[layers, experts] float32: every chip's `held` experts get the SAME `held` values, in an order that
    the seed draws anew for each chip and layer. The values are the quantiles (i + 1/2) / held of what the
    bias was drawn from until PR 64, 0.1 x a normal truncated at two deviations, so an expert's bias
    is distributed as before; what no longer depends on the seed is the work. A router's rows are as good as
    random here, so how many of them reach an expert follows from its bias and the set of all biases, and
    with it how many of a chip's slabs a decode step reads: 16 biases drawn freely left this replica 12.2
    to 14.9 slabs a layer by the seed, and `serve_out_tok_s` followed them over 6% (PERF.md section 6,
    PR 64). Every chip of the deployment now carries the same load, which is what a trained router's
    bias is tuned for."""
    if experts % held:
        raise ValueError(f"{experts} experts do not divide into chips of {held}")
    q = (jnp.arange(held, dtype=jnp.float32) + 0.5) / held
    lo, hi = ndtr(-2.0), ndtr(2.0)
    values = 0.1 * ndtri(lo + q * (hi - lo))

    def layer(k):
        return jax.vmap(lambda chip: jax.random.permutation(chip, values))(jax.random.split(k, experts // held)).reshape(experts)

    return jax.lax.map(layer, jax.random.split(key, layers)).astype(jnp.float32)


def init_weights(key: jax.Array, s: dict) -> dict:
    """The parameter tree in the layout tony_tpu/models/exaone_moe.py reads:
    `dense` a list of the leading dense layers' leaves, `layers` the routed
    layers' leaves stacked, `mtp` the prediction modules'. Truncated normal,
    fan-in scaled; norms at one; the router float32 and its bias small and not
    zero (so that choosing by s + b and weighing by s can be told apart):
    `choosing_bias`, whose values are the same for every seed and only their
    order is the seed's. A stacked leaf is drawn a layer at a time: the float32
    draw of every layer's bank at once is as large again as the weights."""
    d, v, dh, dt = s["d_model"], s["vocab"], s["head_dim"], jnp.dtype(s["dtype"])
    q, kv, fe, held = s["heads"] * dh, s["kv_heads"] * dh, s["d_expert"], s["held"][1]
    ks = iter(jax.random.split(key, 8 + 8 * s["dense_layers"] + 16 * (1 + s["mtp_layers"])))

    def draw(k, shape, fan_in, dtype, scale):
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32) * (scale * fan_in ** -0.5)).astype(dtype)

    def dense(*shape, fan_in, dtype=dt, scale=1.0):
        return draw(next(ks), shape, fan_in, dtype, scale)

    def stack(n, *shape, fan_in, dtype=dt, scale=1.0):
        return jax.lax.map(lambda k: draw(k, shape, fan_in, dtype, scale), jax.random.split(next(ks), n))

    def attention(n=None):
        mk = dense if n is None else (lambda *shape, **kw: stack(n, *shape, **kw))
        lead = () if n is None else (n,)
        return {"attn_norm": jnp.ones(lead + (d,), dt), "mlp_norm": jnp.ones(lead + (d,), dt),
                "wq": mk(d, q, fan_in=d), "wk": mk(d, kv, fan_in=d), "wv": mk(d, kv, fan_in=d), "wo": mk(q, d, fan_in=q),
                "q_norm": jnp.ones(lead + (dh,), dt), "k_norm": jnp.ones(lead + (dh,), dt)}

    def routed(n):
        fs = fe * s["shared_experts"]
        return {**attention(n),
                "router": stack(n, d, s["num_experts"], fan_in=d, dtype=jnp.float32),
                "router_bias": choosing_bias(next(ks), n, s["num_experts"], held),
                "ws_gate": stack(n, d, fs, fan_in=d), "ws_up": stack(n, d, fs, fan_in=d), "ws_down": stack(n, fs, d, fan_in=fs),
                "we_gate": stack(n, held, d, fe, fan_in=d), "we_up": stack(n, held, d, fe, fan_in=d),
                "we_down": stack(n, held, fe, d, fan_in=fe)}

    params = {"embed": dense(v, d, fan_in=1.0),
              "dense": [{**attention(), "w_gate": dense(d, s["d_ff"], fan_in=d), "w_up": dense(d, s["d_ff"], fan_in=d),
                         "w_down": dense(s["d_ff"], d, fan_in=s["d_ff"])} for _ in range(s["dense_layers"])],
              "layers": routed(s["layers"] - s["dense_layers"]), "final_norm": jnp.ones((d,), dt),
              "lm_head": dense(d, v, fan_in=d)}
    if s["mtp_layers"]:
        m = s["mtp_layers"]
        params["mtp"] = {"proj": stack(m, 2 * d, d, fan_in=2 * d), "hidden_norm": jnp.ones((m, d), dt),
                         "embed_norm": jnp.ones((m, d), dt), "layers": routed(m)}
    return params


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


def _block_norms(x, lp, s):
    """ASSUMED pre-norm: a branch reads the normed residual stream, and is added to the stream itself."""
    return _rms_norm(x, lp, s["norm_eps"])


def _qk_norm(a, w, s):
    """ASSUMED (EXAONE 4.0): RMSNorm over each head's dh of q and k, before any rotation."""
    return _rms_norm(a, w, s["norm_eps"])


def _positions(a, pos, window, s):
    """ASSUMED (EXAONE 4.0's hybrid convention): a sliding layer rotates q and k
    (rotate-half, rope_type default), a full layer carries no positions."""
    if not window:
        return a
    dh = a.shape[-1]
    inv = 1.0 / (s["rope_theta"] ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.outer(pos.astype(jnp.float32), inv)[:, None, :]
    a1, a2 = jnp.split(a, 2, axis=-1)
    return jnp.concatenate([a1 * jnp.cos(ang) - a2 * jnp.sin(ang), a2 * jnp.cos(ang) + a1 * jnp.sin(ang)], -1)


def _choose(scores, bias, s):
    """ASSUMED (the lineage's e_score_correction_bias): [T, E] scores -> bool [T, E],
    the top_k largest of score + bias; n_group = topk_group = 1: one group, no limit."""
    order = jnp.argsort(-(scores + bias.astype(jnp.float32)), axis=-1, stable=True)[:, :s["top_k"]]
    return jnp.zeros(scores.shape, bool).at[jnp.arange(scores.shape[0])[:, None], order].set(True)


def _blocks(t: int, q_block: int):
    return jnp.arange(t // q_block), lambda a: a.reshape(t // q_block, q_block, *a.shape[1:])


def _swiglu(h, wg, wu, wd, prec):
    return _mm("tf,fd->td", jax.nn.silu(_mm("td,df->tf", h, wg, prec)) * _mm("td,df->tf", h, wu, prec), wd, prec)


def held_slack(scores, bias, chosen, held):
    """[T]: how far the nearest HELD expert's choosing score lies from the edge of
    the chosen set: a chosen one's above the best score left out, one left out's
    below the worst score chosen. Experts this replica does not hold add nothing
    here whichever side they fall, so their ties are nobody's business."""
    c = scores + bias.astype(jnp.float32)
    worst_in = jnp.min(jnp.where(chosen, c, jnp.inf), axis=-1, keepdims=True)
    best_out = jnp.max(jnp.where(chosen, -jnp.inf, c), axis=-1, keepdims=True)
    first, count = held
    return jnp.min(jnp.where(chosen, c - best_out, worst_in - c)[:, first:first + count], axis=-1)


def routed_ffn_and_slack(h, lp, s, prec="f32", held=None, shared=True):
    """h [T, D] (normed) -> (the routed layer's FFN output [T, D] for the share
    `held` = (first, count) (default: the configuration's), the banks in `lp`
    being those experts'; `held_slack` [T]). Every held expert for every token, masked."""
    first, count = held = held or s["held"]
    scores = jax.nn.sigmoid(_mm("td,de->te", h, lp["router"], prec))
    chosen = _choose(scores, lp["router_bias"], s)
    w = s["routed_scale"] * jnp.where(chosen, scores, 0.0) / jnp.sum(jnp.where(chosen, scores, 0.0), axis=-1, keepdims=True)
    w = w[:, first:first + count]                                        # what lands on an absent expert is left out
    g = jax.nn.silu(_mm("td,edf->tef", h, lp["we_gate"], prec)) * _mm("td,edf->tef", h, lp["we_up"], prec)
    y = jnp.einsum("ted,te->td", _mm("tef,efd->ted", g, lp["we_down"], prec), w, precision=jax.lax.Precision.HIGHEST)
    if shared:
        y = y + _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"], prec)
    return y, held_slack(scores, lp["router_bias"], chosen, held)


def routed_ffn(h, lp, s, prec="f32", held=None, shared=True):
    return routed_ffn_and_slack(h, lp, s, prec, held, shared)[0]


def _ffn(x, lp, s, prec, q_block):
    """x [T, D] -> (x + FFN(norm(x)), `held_slack` [T]: infinite for a dense layer), a block of positions at a time."""
    _, split = _blocks(x.shape[0], q_block)

    def one(xb):
        h = _block_norms(xb, lp["mlp_norm"], s)
        if "router" in lp:
            y, slack = routed_ffn_and_slack(h, lp, s, prec)
            return xb + y, slack
        return xb + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], prec), jnp.full(xb.shape[:1], jnp.inf)

    y, slack = jax.lax.map(one, split(x))
    return y.reshape(x.shape), slack.reshape(x.shape[0])


def _attention(x, lp, window, s, prec, q_block):
    """x [T, D] -> x + the attention branch; `window` 0 on a full layer."""
    t, h, hkv, dh = x.shape[0], s["heads"], s["kv_heads"], s["head_dim"]
    idx, split = _blocks(t, q_block)
    kpos = jnp.arange(t)

    def keys_values(args):
        i, xb = args
        y = _block_norms(xb, lp["attn_norm"], s)
        k = _qk_norm(_mm("td,dh->th", y, lp["wk"], prec).reshape(q_block, hkv, dh), lp["k_norm"], s)
        return _positions(k, i * q_block + jnp.arange(q_block), window, s), _mm("td,dh->th", y, lp["wv"], prec).reshape(
            q_block, hkv, dh)

    k, v = jax.lax.map(keys_values, (idx, split(x)))
    k, v = k.reshape(t, hkv, dh), v.reshape(t, hkv, dh)

    def queries(args):
        i, xb = args
        y = _block_norms(xb, lp["attn_norm"], s)
        qpos = i * q_block + jnp.arange(q_block)
        q = _qk_norm(_mm("td,dh->th", y, lp["wq"], prec).reshape(q_block, h, dh), lp["q_norm"], s)
        q = _positions(q, qpos, window, s).reshape(q_block, hkv, h // hkv, dh)
        scores = _mm("qkgd,tkd->kgqt", q, k, prec) * dh ** -0.5
        seen = kpos[None, :] <= qpos[:, None]
        if window:
            seen &= qpos[:, None] - kpos[None, :] < window             # itself and the window - 1 before it
        p = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        o = _mm("kgqt,tkd->qkgd", p, v, prec).reshape(q_block, h * dh)
        return xb + _mm("th,hd->td", o, lp["wo"], prec)

    return jax.lax.map(queries, (idx, split(x))).reshape(t, -1)


def _layer_of(stacked: dict, i: int) -> dict:
    return {k: v[i] for k, v in stacked.items()}


def trunk(params: dict, tokens: jax.Array, s: dict, prec: str = "f32", q_block: int = 256):
    """tokens [T] -> (the trunk after the last layer [T, D], float32, before the
    final norm; the smallest `held_slack` of the position's routed layers [T]).
    T must divide by q_block (pad at the end: a causal model's earlier
    positions do not see the padding)."""
    t = tokens.shape[0]
    q_block = min(q_block, t)
    if t % q_block:
        raise ValueError(f"{t} positions do not divide into blocks of {q_block}")
    x, slack = params["embed"][tokens].astype(jnp.float32), jnp.full((t,), jnp.inf)
    for l, window in enumerate(s["windows"]):
        lp = params["dense"][l] if l < s["dense_layers"] else _layer_of(params["layers"], l - s["dense_layers"])
        x, layer_slack = _ffn(_attention(x, lp, window, s, prec, q_block), lp, s, prec, q_block)
        slack = jnp.minimum(slack, layer_slack)
    return x, slack


def hidden(params: dict, tokens: jax.Array, s: dict, prec: str = "f32", q_block: int = 256) -> jax.Array:
    return trunk(params, tokens, s, prec, q_block)[0]


def head(params: dict, x: jax.Array, s: dict, prec: str = "f32") -> jax.Array:
    return _mm("td,dv->tv", _rms_norm(x, params["final_norm"], s["norm_eps"]), params["lm_head"], prec)


def forward(params: dict, tokens: jax.Array, s: dict, prec: str = "f32", q_block: int = 256) -> jax.Array:
    """tokens [T] -> logits [T, V] float32. At "f32", where the program computes
    in a dtype of `TIE_MARGIN`: a row of zeros at a position whose routing of a
    held expert lies within that margin of a tie (the module's docstring)."""
    x, slack = trunk(params, tokens, s, prec, q_block)
    logits = head(params, x, s, prec)
    margin = TIE_MARGIN.get(s["dtype"], 0.0) if prec == "f32" else 0.0
    return jnp.where((slack < margin)[:, None], 0.0, logits) if margin else logits


def mtp_logits(params: dict, x: jax.Array, tokens: jax.Array, s: dict, prec: str = "f32") -> jax.Array:
    """ASSUMED (the lineage's form; the config gives num_nextn_predict_layers and
    mtp_layer_types full): x [T, D] the trunk's rows (`hidden`), tokens [T] ->
    logits [modules, T - 1, V]; row i of module m predicts token i + 2 + m:
    u_i = W_p [rmsnorm(x_i) ; rmsnorm(embed(t_{i+1+m}))], x being the module
    before's rows for m > 0; one full-attention routed layer over u; the
    trunk's final norm and head. A module's last 1 + m rows read past the
    sequence's end (token 0 wrapped round) and are the caller's to drop."""
    t, mtp, out = tokens.shape[0], params["mtp"], []
    for m in range(s["mtp_layers"]):
        nxt = params["embed"][jnp.roll(tokens, -(1 + m))].astype(jnp.float32)
        both = jnp.concatenate([_rms_norm(x, mtp["hidden_norm"][m], s["norm_eps"]),
                                _rms_norm(nxt, mtp["embed_norm"][m], s["norm_eps"])], axis=-1)
        lp = _layer_of(mtp["layers"], m)
        x, _ = _ffn(_attention(_mm("te,ed->td", both, mtp["proj"][m], prec), lp, 0, s, prec, t), lp, s, prec, t)
        out.append(head(params, x, s, prec)[:t - 1])
    return jnp.stack(out)


def nll(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Per-position negative log-likelihood, float32."""
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
