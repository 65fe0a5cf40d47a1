"""The mistral4 family's plain reference: Mistral-Small-4's language model, forward
in jax.numpy and float32, matrix products at precision "highest".

No kernel, no cache, no chunk, no absorbed form, no sorting of rows, one sequence
at a time, nothing shared with tony_tpu/. x is [T, D]; every block is pre-norm:
x + attention(rmsnorm(x)), then x + ffn(rmsnorm(x)); eps from the configuration.

  latent attention (MLA), EXPANDED, every layer:
      cq = rmsnorm(h Wq_a)                                 the q latent, normed
      q  = cq Wq_b -> [T, H, nope + rope]; the rope part rotated
      kv = h Wkv_a -> [T, kv_rank + rope]; c = rmsnorm(kv[:, :kv_rank]);
      kr = the rope part rotated: ONE rope key a position, shared by the heads
      k[t, h] = [c_t W_uk[h] ; kr_t], v[t, h] = c_t W_uv[h]      keys and values BUILT from the latent
      q[t] *= 1 + beta ln(1 + floor(t / original_max))           (`_query_scale`)
      scores q . k * (nope + rope)^-0.5 * mscale(factor, mscale_all_dim)^2 over EVERY s <= t:
      AN EXPLICIT CAUSAL MASK over a full score matrix a block of queries; softmax in float32;
      concat_h(p_h V_h) W_o. No gate, no q/k head norm, no bias.
  rope      on the `rope` dims, INTERLEAVED pairs (2i, 2i + 1), theta from the configuration,
            under YaRN (`yarn_inv_freq`, HF's `_compute_yarn_parameters`): f_i = theta^(-2i/rope);
            low, high from beta_fast and beta_slow over original_max positions (floored, ceiled);
            ramp = clip((i - low) / (high - low), 0, 1); inv_freq = f (1 - ramp) + (f / factor) ramp;
            cos and sin times mscale(factor, mscale) / mscale(factor, mscale_all_dim).
  routed FFN every layer (`routed_ffn_and_slack`): logits h Wr [T, E] in float32, softmax over all E,
            the top_k largest chosen, renormalised to sum 1; EVERY held expert is computed for every
            token and masked by `chosen and held`: x + sum_e w_e ffn_e(h) + ffn_shared(h).
  the share `held = (first, count)`: the experts this replica holds. The router and the
            normalisation are over all E; what the absent experts would add is left out.
  head      rmsnorm(x) W_head, not tied; `forward` computes the last HEAD_ROWS rows.

Departures from the published description, each an entry of the configuration's
`assumed` and ONE function here and in the program: `_block_norm` (pre-norm),
`_latent_norm` (RMSNorm on both latents), `_route` (softmax, then top-k, then
renormalise), `_score_scale` (the mscale^2), `_query_scale`. Left out, as the
configuration notes: any prediction module, the vision tower.

Positions are processed in blocks of `q_block` wherever a temporary would grow
with the sequence, heads in groups of HEAD_GROUP. Only the rows that the last
HEAD_ROWS rows' logits depend on are computed: every layer's keys are every
position, so every layer but the last computes every row, and the last layer its
last HEAD_ROWS rows only (same blocks, same operands as the whole computation).

Precisions: "f32" is the reference. "fp8" is the control: both operands of every
matrix product rounded to float8_e4m3, the nearest precision below bf16.

Where the reference makes no statement: the routing ties, as
families/exaone_moe_reference.py states them (`TIE_MARGIN`, `held_slack`: a row of
zeros where a held expert's choosing score lies within the margin of the edge of
the chosen set in some layer). A softmax's order is its logits' order, so the
choosing score handed to `held_slack` is sigmoid(logit): the unit that family's
margin was measured in (a probability of 1/128 has no such unit: the margin would
swallow it), and the same chosen set.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from families.exaone_moe_reference import (  # the statement of a routed FFN's ties is that family's
    TIE_MARGIN, _mm, _rms_norm, _swiglu, held_slack, nll, seed_key)

__all__ = ["CONTROL", "GRAD_LEAVES", "HEAD_ROWS", "TIE_MARGIN", "forward", "init_weights", "nll", "seed_key"]

CONTROL = "fp8"
#: no training cell: no gradient is compared
GRAD_LEAVES = ()
#: rows at the end of a sequence whose logits `forward` computes (check.py reads 512 within the last 2047)
HEAD_ROWS = 2048
#: heads whose scores exist at a time ([heads, q_block, positions] in float32)
HEAD_GROUP = 2
#: held experts whose products over a block of positions exist at a time
EXPERT_GROUP = 8
BANKS = ("we_gate", "we_up", "we_down")


def init_weights(key: jax.Array, s: dict) -> dict:
    """The parameter tree in the layout tony_tpu/models/mistral4.py reads: every
    layer's leaves STACKED under `layers` (the held experts' banks among them:
    [layers, held, ...]). Truncated normal, fan-in scaled; norms at one; the
    router float32. A stacked leaf is drawn a layer at a time: the float32 draw
    of every layer's bank at once is as large again as the weights."""
    d, v, dt, n = s["d_model"], s["vocab"], jnp.dtype(s["dtype"]), s["layers"]
    h, rq, r, dn, dr, dv = s["heads"], s["q_rank"], s["kv_rank"], s["nope"], s["rope"], s["v_dim"]
    fe, held = s["d_expert"], s["held"][1]
    fs = fe * s["shared_experts"]
    ks = iter(jax.random.split(key, 24))

    def draw(k, shape, fan_in, dtype):
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32) * fan_in ** -0.5).astype(dtype)

    def dense(*shape, fan_in):
        return draw(next(ks), shape, fan_in, dt)

    def stack(*shape, fan_in, dtype=dt):
        return jax.lax.map(lambda k: draw(k, shape, fan_in, dtype), jax.random.split(next(ks), n))

    layers = {
        "attn_norm": jnp.ones((n, d), dt), "mlp_norm": jnp.ones((n, d), dt),
        "wq_a": stack(d, rq, fan_in=d), "q_a_norm": jnp.ones((n, rq), dt), "wq_b": stack(rq, h * (dn + dr), fan_in=rq),
        "wkv_a": stack(d, r + dr, fan_in=d), "kv_a_norm": jnp.ones((n, r), dt),
        "w_uk": stack(h, r, dn, fan_in=r), "w_uv": stack(h, r, dv, fan_in=r), "wo": stack(h * dv, d, fan_in=h * dv),
        "router": stack(d, s["num_experts"], fan_in=d, dtype=jnp.float32),
        "ws_gate": stack(d, fs, fan_in=d), "ws_up": stack(d, fs, fan_in=d), "ws_down": stack(fs, d, fan_in=fs),
        "we_gate": stack(held, d, fe, fan_in=d), "we_up": stack(held, d, fe, fan_in=d), "we_down": stack(held, fe, d, fan_in=fe),
    }
    return {"embed": dense(v, d, fan_in=1.0), "layers": layers, "final_norm": jnp.ones((d,), dt), "lm_head": dense(d, v, fan_in=d)}


# -- what the configuration's `assumed` states, one function each ---------------------------------

def _block_norm(x, w, s):
    """ASSUMED pre_norm: a branch reads the normed residual stream and is added to the stream itself."""
    return _rms_norm(x, w, s["norm_eps"])


def _latent_norm(a, w, s):
    """ASSUMED (the DeepSeek-V3 lineage's q_a_layernorm / kv_a_layernorm): RMSNorm over each latent, no rescale."""
    return _rms_norm(a, w, s["norm_eps"])


def mscale(scale: float, m: float) -> float:
    """YaRN's magnitude correction, the DeepSeek-V3 convention the configuration's keys come from."""
    return 1.0 if scale <= 1.0 else 0.1 * m * math.log(scale) + 1.0


def _score_scale(s: dict) -> float:
    """ASSUMED: (nope + rope)^-0.5 x mscale(factor, mscale_all_dim)^2."""
    return (s["nope"] + s["rope"]) ** -0.5 * mscale(s["yarn"][0], s["yarn"][5]) ** 2


def _query_scale(pos, s):
    """ASSUMED (`llama_4_scaling_beta`, as HF's Ministral family applies it): the query at position p
    times 1 + beta ln(1 + floor(p / original_max))."""
    return 1.0 + s["query_scale_beta"] * jnp.log1p(jnp.floor(pos.astype(jnp.float32) / float(s["yarn"][3])))


def _route(logits, s):
    """ASSUMED (norm_topk_prob true, scoring by softmax, one group): [T, E] float32 logits -> (weights
    [T, E]: the softmax over ALL E, its top_k largest kept and renormalised to sum 1, zero elsewhere;
    chosen bool [T, E])."""
    probs = jax.nn.softmax(logits, axis=-1)
    order = jnp.argsort(-probs, axis=-1, stable=True)[:, :s["top_k"]]
    chosen = jnp.zeros(probs.shape, bool).at[jnp.arange(probs.shape[0])[:, None], order].set(True)
    kept = jnp.where(chosen, probs, 0.0)
    return kept / jnp.sum(kept, axis=-1, keepdims=True), chosen


# -- the rope ---------------------------------------------------------------------------------------

def yarn_range(s: dict) -> tuple[int, int]:
    """(low, high): the pairs between which YaRN blends (HF's find_correction_range, truncated)."""
    dr, theta = s["rope"], s["rope_theta"]
    _, fast, slow, orig, _, _ = s["yarn"]
    at = lambda turns: dr * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))
    return max(math.floor(at(fast)), 0), min(math.ceil(at(slow)), dr - 1)


def yarn_inv_freq(s: dict) -> jax.Array:
    """[rope / 2] float32: pair i's angle a position."""
    dr, factor = s["rope"], s["yarn"][0]
    f = s["rope_theta"] ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    low, high = yarn_range(s)
    ramp = jnp.clip((jnp.arange(dr // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f * (1.0 - ramp) + (f / factor) * ramp


def _rope(a, pos, s):
    """a [T, ..., rope] at positions pos [T]: pair (2i, 2i + 1) turned by pos x inv_freq_i, in place."""
    factor, _, _, _, m, m_all = s["yarn"]
    ang = jnp.outer(pos.astype(jnp.float32), yarn_inv_freq(s)).reshape(pos.shape[0], *([1] * (a.ndim - 2)), s["rope"] // 2)
    cos, sin = (f(ang) * (mscale(factor, m) / mscale(factor, m_all)) for f in (jnp.cos, jnp.sin))
    even, odd = a[..., 0::2], a[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(a.shape)


# -- the layer --------------------------------------------------------------------------------------

def _blocks(t: int, q_block: int):
    return jnp.arange(t // q_block), lambda a: a.reshape(t // q_block, q_block, *a.shape[1:])


def _attention(x, lp, s, prec, q_block, first=0):
    """x [T, D] -> (x + the attention branch)[first * q_block:]: keys from every row, queries from block `first` on."""
    t, H, r, dn, dr, dv = x.shape[0], s["heads"], s["kv_rank"], s["nope"], s["rope"], s["v_dim"]
    idx, split = _blocks(t, q_block)
    group = min(HEAD_GROUP, H)
    kpos = jnp.arange(t)

    def keys(args):
        i, xb = args
        h = _block_norm(xb, lp["attn_norm"], s)
        kv = _mm("td,dk->tk", h, lp["wkv_a"], prec)
        return _latent_norm(kv[:, :r], lp["kv_a_norm"], s), _rope(kv[:, r:], i * q_block + jnp.arange(q_block), s)

    c, kr = (y.reshape(t, -1) for y in jax.lax.map(keys, (idx, split(x))))

    def queries(args):
        i, xb = args
        qpos = i * q_block + jnp.arange(q_block)
        h = _block_norm(xb, lp["attn_norm"], s)
        cq = _latent_norm(_mm("td,dr->tr", h, lp["wq_a"], prec), lp["q_a_norm"], s)
        q = _mm("tr,rh->th", cq, lp["wq_b"], prec).reshape(q_block, H, dn + dr)
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], qpos, s)], -1) * _query_scale(qpos, s)[:, None, None]
        seen = kpos[None, :] <= qpos[:, None]

        def heads(g):
            sl = lambda w: jax.lax.dynamic_slice_in_dim(w, g * group, group, axis=0)
            kn = _mm("sr,hrd->shd", c, sl(lp["w_uk"]), prec)                      # keys and values BUILT from the latent
            v = _mm("sr,hrd->shd", c, sl(lp["w_uv"]), prec)
            q_g = jax.lax.dynamic_slice_in_dim(q, g * group, group, axis=1)
            scores = (_mm("qhd,shd->hqs", q_g[..., :dn], kn, prec) + _mm("qhd,sd->hqs", q_g[..., dn:], kr, prec)) * _score_scale(s)
            p = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
            return _mm("hqs,shd->qhd", p, v, prec)

        o = jax.lax.map(heads, jnp.arange(H // group)).transpose(1, 0, 2, 3).reshape(q_block, H * dv)
        return xb + _mm("th,hd->td", o, lp["wo"], prec)

    return jax.lax.map(queries, (idx[first:], split(x)[first:])).reshape(t - first * q_block, -1)


def routed_ffn_and_slack(h, lp, s, prec="f32", held=None, shared=True, layer=None):
    """h [T, D] (normed) -> (the layer's FFN output [T, D] for the share `held` =
    (first, count) (default: the configuration's), the banks in `lp` being those
    experts' (with `layer`: every layer's, stacked, and this the index of the
    layer's; a layer's slice of them would be a copy of 1.6 GB); `held_slack`
    [T], the choosing score sigmoid(logit)). Every held expert for every token,
    masked; EXPERT_GROUP experts' products exist at a time (their float32
    operands, and at "fp8" the rounded ones, beside 9 GB of weights)."""
    first, count = held = held or s["held"]
    logits = _mm("td,de->te", h, lp["router"], prec)
    w, chosen = _route(logits, s)
    w = w[:, first:first + count]                                        # what lands on an absent expert is left out
    group = next(g for g in range(min(EXPERT_GROUP, count), 0, -1) if count % g == 0)

    def bank(name, g):
        b = lp[name]
        if layer is None:
            return jax.lax.dynamic_slice_in_dim(b, g * group, group, axis=0)
        return jax.lax.dynamic_slice(b, (layer, g * group, 0, 0), (1, group, *b.shape[2:]))[0]

    def experts(g):
        act = jax.nn.silu(_mm("td,edf->tef", h, bank("we_gate", g), prec)) * _mm("td,edf->tef", h, bank("we_up", g), prec)
        return jnp.einsum("ted,te->td", _mm("tef,efd->ted", act, bank("we_down", g), prec),
                          jax.lax.dynamic_slice_in_dim(w, g * group, group, axis=1), precision=jax.lax.Precision.HIGHEST)

    y = jax.lax.map(experts, jnp.arange(count // group)).sum(axis=0)
    if shared:
        y = y + _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"], prec)
    return y, held_slack(jax.nn.sigmoid(logits), jnp.zeros((logits.shape[-1],), jnp.float32), chosen, held)


def _ffn(x, lp, s, prec, q_block, layer=None):
    """x [T, D] -> (x + FFN(norm(x)), `held_slack` [T]), a block of positions at a time."""
    _, split = _blocks(x.shape[0], q_block)

    def one(xb):
        y, slack = routed_ffn_and_slack(_block_norm(xb, lp["mlp_norm"], s), lp, s, prec, layer=layer)
        return xb + y, slack

    y, slack = jax.lax.map(one, split(x))
    return y.reshape(x.shape), slack.reshape(x.shape[0])


def layer_params(params: dict, l: int, banks: bool = True) -> dict:
    """Layer l's leaves; without `banks`, the held experts' banks stay every layer's, stacked."""
    return {k: v if k in BANKS and not banks else v[l] for k, v in params["layers"].items()}


def trunk(params: dict, tokens: jax.Array, s: dict, prec: str = "f32", q_block: int = 1024, rows: int | None = None):
    """tokens [T] -> (the trunk after the last layer [T, D], float32, before the
    final norm; the smallest `held_slack` of the position's layers [T]). With
    `rows`, only the last `rows` rows are the trunk's: the last layer computes
    the blocks that hold them and no other (the rows before them keep what they
    came in with and nothing reads them). T must divide by q_block (pad at the
    end: a causal model's earlier positions do not see the padding). The FFN
    runs in blocks of at most 1024 positions, EXPERT_GROUP experts at a time:
    8 x 2048 floats a position."""
    t = tokens.shape[0]
    q_block = min(q_block, t)
    if t % q_block:
        raise ValueError(f"{t} positions do not divide into blocks of {q_block}")
    f_block = next(b for b in (1024, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1) if q_block % b == 0)
    x, slack = params["embed"][tokens].astype(jnp.float32), jnp.full((t,), jnp.inf)
    for l in range(s["layers"]):
        first = max(t - rows, 0) // q_block if rows is not None and l == s["layers"] - 1 else 0
        lp, lo = layer_params(params, l, banks=False), first * q_block
        y, layer_slack = _ffn(_attention(x, lp, s, prec, q_block, first), lp, s, prec, f_block, layer=l)
        x = jnp.concatenate([x[:lo], y])
        slack = jnp.minimum(slack, jnp.concatenate([jnp.full((lo,), jnp.inf), layer_slack]))
    return x, slack


def forward(params: dict, tokens: jax.Array, s: dict, prec: str = "f32", q_block: int = 512) -> jax.Array:
    """tokens [T] -> logits [T, V] float32; rows before the last HEAD_ROWS are NaN
    (not computed: 35k x 32,768 floats are 4.7 GB, and a comparison that reads one
    cannot pass). At "f32", where the program computes in a dtype of `TIE_MARGIN`:
    a row of zeros at a position whose routing of a held expert lies within that
    margin of a tie in some layer (families/exaone_moe_reference.py)."""
    rows = min(HEAD_ROWS, tokens.shape[0])
    x, slack = trunk(params, tokens, s, prec, q_block, rows)
    logits = _mm("td,dv->tv", _rms_norm(x[-rows:], params["final_norm"], s["norm_eps"]), params["lm_head"], prec)
    margin = TIE_MARGIN.get(s["dtype"], 0.0) if prec == "f32" else 0.0
    if margin:
        logits = jnp.where((slack[-rows:] < margin)[:, None], 0.0, logits)
    return jnp.pad(logits, ((x.shape[0] - rows, 0), (0, 0)), constant_values=jnp.nan)
