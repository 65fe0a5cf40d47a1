"""The dots3_note family's plain reference: the language model of dots3-note-prev,
forward in jax.numpy and float32, matrix products at precision "highest".

No kernel, no cache, no chunk, no absorbed form, no sorting of rows, one sequence
at a time, nothing shared with tony_tpu/. x is [T, D]; every block is pre-norm:
x + attention(rmsnorm(x)), then x + ffn(rmsnorm(x)).

  latent attention (MLA), EXPANDED, both kinds of layer, each with its own widths:
      cq = rmsnorm(h Wq_a) * sqrt(D / q_rank)            the q latent, normed and rescaled
      q  = cq Wq_b -> [T, H, nope + rope]; the rope part rotated (rotate-half)
      kv = h Wkv_a -> [T, kv_rank + rope]; c = rmsnorm(kv[:, :kv_rank]) * sqrt(D / kv_rank);
      kr = the rope part rotated: ONE rope key a position, shared by the heads
      k[t, h] = [c_t W_uk[h] ; kr_t], v[t, h] = c_t W_uv[h]      keys and values BUILT from the latent
      scores q . k * (nope + rope)^-0.5 over the positions the layer's mask lets through:
      AN EXPLICIT MASK over a full score matrix a block of queries
      o[t, h] * sigmoid(h W_g)[t, h] (the head-wise gate), then W_o
  full_attention     the mask is causal AND the indexer's choice: for query t and s <= t
      I[t, s] = sum_h w[t, h] relu(qI[t, h] . kI[s]),  qI = cq W_iq [T, Hi, di], kI =
      rmsnorm(h W_ik) [T, di], both rotated on their first `rope` dims, w = (h W_iw) *
      Hi^-0.5 * di^-0.5, all float32; position t reads the `index_topk` positions of
      largest I[t, .] (`lax.top_k`'s threshold; every s <= t while t < index_topk).
  sliding_attention  the mask is causal AND t - s < window (the window counts the position itself)
  dense FFN          the first `dense_layers` layers: x + (silu(h Wg) * (h Wu)) Wd
  routed FFN         every other layer: families/exaone_moe_reference.routed_ffn_and_slack
      (sigmoid scores in float32, the top_k of score + bias chosen, the chosen
      scores renormalised x routed_scale, a shared expert; every held expert for
      every token, masked; the share `held` of the experts, the rest left out)
  head               rmsnorm(x) W_head, not tied; `forward` computes the last HEAD_ROWS rows

Departures from the published description, each an entry of the configuration's
`assumed` and one function here and in the program: `_block_norm` (pre-norm),
`_latent` (RMSNorm on the latents, the rescale), `_rope` (rotate-half on the rope
dims only), `_scale`, `_gate`, `index_scores` (the indexer's form), `_window_mask`.
Left out, as the configuration notes: the indexer's Hadamard rotation (orthogonal:
it changes no float32 score) and its fp8 storage; the vision and audio towers.

Positions are processed in blocks of `q_block` wherever a temporary would grow
with the sequence, and a full layer's heads in groups of HEAD_GROUP (keys and
values are built a group at a time: 128 heads x 67k positions x 192 in float32
do not fit beside the weights); a sliding layer's block of queries is handed the
span of positions its window can reach, and masks inside it.

Only the rows that the last HEAD_ROWS rows' logits depend on are computed
(`first_blocks`): a full layer's keys are every position, so the layer below it
computes every row; a sliding layer's keys reach window - 1 back, so the layer
below it starts that far before its own first block of queries. Under the stack
full, full, sliding, sliding, sliding the second full layer and the routed FFNs
run over the last few thousand rows of 67k; each computed row is the row the
whole computation gives (same blocks, same operands). An uncomputed row keeps
the value it came in with; nothing computed reads one unmasked.

Precisions: "f32" is the reference. "fp8" is the control: both operands of every
matrix product rounded to float8_e4m3, the nearest precision below bf16.

Where the reference makes no statement: the routing ties of the routed FFN, as
families/exaone_moe_reference.py states them (`TIE_MARGIN`, `held_slack`: a row
of zeros where a held expert's choosing score lies within the margin of the edge
of the chosen set in some routed layer). The indexer's edge needs no such
statement at these weights (PERF.md section 4: one position of 2048 more or fewer
moves a head's output by its softmax weight, and the weights are flat).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from families.exaone_moe_reference import (  # the routed FFN and the statement of its ties are that family's
    TIE_MARGIN, _mm, _rms_norm, _swiglu, nll, routed_ffn_and_slack, seed_key)

__all__ = ["CONTROL", "GRAD_LEAVES", "HEAD_ROWS", "TIE_MARGIN", "forward", "hidden", "init_weights", "nll", "seed_key"]

CONTROL = "fp8"
#: no training cell: no gradient is compared
GRAD_LEAVES = ()
#: rows at the end of a sequence whose logits `forward` computes (check.py reads 512 within the last 2047)
HEAD_ROWS = 2048
#: heads whose keys and values a full layer builds at a time
HEAD_GROUP = 2
#: queries whose index scores exist at a time ([queries, 64 heads, positions] in float32)
INDEX_BLOCK = 32
FULL, SLIDING = "full_attention", "sliding_attention"
BANKS = ("we_gate", "we_up", "we_down")


def attention_sizes(s: dict, kind: str) -> dict:
    """The widths of one kind of layer: (heads, q_rank, kv_rank, nope, rope, v, theta)."""
    p = "" if kind == FULL else "swa_"
    return {k: s[p + k] for k in ("heads", "q_rank", "kv_rank", "nope", "rope", "v_dim", "rope_theta")}


def init_weights(key: jax.Array, s: dict) -> dict:
    """The parameter tree in the layout tony_tpu/models/dots3_note.py reads:
    `layers` a list with one dict of leaves a layer, shaped by its kind and by
    whether its FFN is dense; `banks` the routed layers' held experts STACKED
    [routed layers, held, ...] (the grouped product takes every layer's bank and
    a layer index). Truncated normal, fan-in scaled; norms at one; the router
    float32, its bias small and not zero. What reads a rescaled latent (Wq_b,
    W_iq, W_uk, W_uv) is drawn x sqrt(rank / D), so that queries, keys and values
    come out at unit scale as the other projections' do: the rescale is the
    model's, the scale of seeded weights is ours."""
    d, v, dt = s["d_model"], s["vocab"], jnp.dtype(s["dtype"])
    fe, held, n_routed = s["d_expert"], s["held"][1], s["layers"] - s["dense_layers"]
    ks = iter(jax.random.split(key, 8 + 24 * s["layers"]))

    def draw(k, shape, fan_in, dtype, scale):
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32) * (scale * fan_in ** -0.5)).astype(dtype)

    def dense(*shape, fan_in, dtype=dt, scale=1.0):
        return draw(next(ks), shape, fan_in, dtype, scale)

    def stack(n, *shape, fan_in):
        return jax.lax.map(lambda k: draw(k, shape, fan_in, dt, 1.0), jax.random.split(next(ks), n))

    def attention(kind):
        a = attention_sizes(s, kind)
        h, rq, r, dn, dr, dv = a["heads"], a["q_rank"], a["kv_rank"], a["nope"], a["rope"], a["v_dim"]
        lp = {"attn_norm": jnp.ones((d,), dt), "mlp_norm": jnp.ones((d,), dt),
              "wq_a": dense(d, rq, fan_in=d), "q_a_norm": jnp.ones((rq,), dt),
              "wq_b": dense(rq, h * (dn + dr), fan_in=rq, scale=(rq / d) ** 0.5),
              "wkv_a": dense(d, r + dr, fan_in=d), "kv_a_norm": jnp.ones((r,), dt),
              "w_uk": dense(h, r, dn, fan_in=r, scale=(r / d) ** 0.5), "w_uv": dense(h, r, dv, fan_in=r, scale=(r / d) ** 0.5),
              "wg": dense(d, h, fan_in=d), "wo": dense(h * dv, d, fan_in=h * dv)}
        if kind == FULL:
            hi, di = s["index_heads"], s["index_dim"]
            lp.update(idx_wq=dense(rq, hi * di, fan_in=rq, scale=(rq / d) ** 0.5), idx_wk=dense(d, di, fan_in=d),
                      idx_k_norm=jnp.ones((di,), dt), idx_ww=dense(d, hi, fan_in=d))
        return lp

    def ffn(l):
        if l < s["dense_layers"]:
            return {"w_gate": dense(d, s["d_ff"], fan_in=d), "w_up": dense(d, s["d_ff"], fan_in=d),
                    "w_down": dense(s["d_ff"], d, fan_in=s["d_ff"])}
        fs = fe * s["shared_experts"]
        return {"router": dense(d, s["num_experts"], fan_in=d, dtype=jnp.float32),
                "router_bias": dense(s["num_experts"], fan_in=1.0, dtype=jnp.float32, scale=0.1),
                "ws_gate": dense(d, fs, fan_in=d), "ws_up": dense(d, fs, fan_in=d), "ws_down": dense(fs, d, fan_in=fs)}

    return {"embed": dense(v, d, fan_in=1.0),
            "layers": [{**attention(kind), **ffn(l)} for l, kind in enumerate(s["kinds"])],
            "banks": {"we_gate": stack(n_routed, held, d, fe, fan_in=d), "we_up": stack(n_routed, held, d, fe, fan_in=d),
                      "we_down": stack(n_routed, held, fe, d, fan_in=fe)},
            "final_norm": jnp.ones((d,), dt), "lm_head": dense(d, v, fan_in=d)}


# -- what the configuration's `assumed` states, one function each ---------------------------------

def _block_norm(x, w, s):
    """ASSUMED pre_norm: a branch reads the normed residual stream and is added to the stream itself."""
    return _rms_norm(x, w, s["norm_eps"])


def _latent(a, w, s):
    """ASSUMED (the lineage's q_a_layernorm / kv_a_layernorm, and `apply_mla_qkv_lora_rescale` read as
    the Kimi/DeepSeek-lineage rescale of a low-rank path): RMSNorm over the latent, times sqrt(D / rank)."""
    return _rms_norm(a, w, s["norm_eps"]) * (s["d_model"] / a.shape[-1]) ** 0.5


def _rope(a, pos, theta):
    """ASSUMED rotate_half_on_rope_dims: a [T, ..., dr] rotated whole (the caller hands the rope dims only)."""
    dr = a.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr))
    ang = jnp.outer(pos.astype(jnp.float32), inv).reshape(pos.shape[0], *([1] * (a.ndim - 2)), dr // 2)
    a1, a2 = jnp.split(a, 2, axis=-1)
    return jnp.concatenate([a1 * jnp.cos(ang) - a2 * jnp.sin(ang), a2 * jnp.cos(ang) + a1 * jnp.sin(ang)], -1)


def _scale(a: dict) -> float:
    """ASSUMED: softmax scale (nope + rope)^-0.5 (192 and 256), no further factor (rope_scaling is null)."""
    return (a["nope"] + a["rope"]) ** -0.5


def _gate(o, h, lp, prec):
    """ASSUMED headwise: o [T, H, dv] x sigmoid(h W_g)[t, h], before W_o."""
    return o * jax.nn.sigmoid(_mm("td,dh->th", h, lp["wg"], prec))[:, :, None]


def _window_mask(qpos, kpos, window):
    """ASSUMED counts_itself: the window's 513 are the position and the 512 before it."""
    return (kpos[None, :] <= qpos[:, None]) & (qpos[:, None] - kpos[None, :] < window)


def index_queries(h, cq, pos, lp, s, prec):
    """(qI [T, Hi, di] rotated on its first `rope` dims, w [T, Hi] float32)."""
    hi, di, dr = s["index_heads"], s["index_dim"], s["rope"]
    q = _mm("tr,rh->th", cq, lp["idx_wq"], prec).reshape(-1, hi, di)
    q = jnp.concatenate([_rope(q[..., :dr], pos, s["rope_theta"]), q[..., dr:]], -1)
    return q, _mm("td,dh->th", h, lp["idx_ww"], prec) * (hi ** -0.5 * di ** -0.5)


def index_keys(h, pos, lp, s, prec):
    """kI [T, di]: one normed key a position, rotated on its first `rope` dims."""
    k = _rms_norm(_mm("td,dk->tk", h, lp["idx_wk"], prec), lp["idx_k_norm"], s["norm_eps"])
    return jnp.concatenate([_rope(k[:, :s["rope"]], pos, s["rope_theta"]), k[:, s["rope"]:]], -1)


def index_scores(qi, w, ki, prec):
    """ASSUMED (the DSA indexer's form): I[t, s] = sum_h w[t, h] relu(qI[t, h] . kI[s]), float32;
    INDEX_BLOCK queries at a time."""
    b = next(n for n in range(min(INDEX_BLOCK, qi.shape[0]), 0, -1) if qi.shape[0] % n == 0)

    def one(args):
        q, wb = args
        return jnp.einsum("ths,th->ts", jax.nn.relu(_mm("thd,sd->ths", q, ki, prec)), wb, precision=jax.lax.Precision.HIGHEST)

    return jax.lax.map(one, (qi.reshape(-1, b, *qi.shape[1:]), w.reshape(-1, b, w.shape[1]))).reshape(qi.shape[0], -1)


def chosen_mask(score, seen, topk):
    """score [Q, T], seen [Q, T] (causal) -> bool [Q, T]: the `topk` positions of largest
    score among those seen (all of them where fewer are seen). The threshold is
    `lax.top_k`'s last value; a score equal to it is read too."""
    masked = jnp.where(seen, score, -jnp.inf)
    kth = jax.lax.top_k(masked, min(topk, score.shape[-1]))[0][:, -1:]
    return seen & (masked >= kth)


def _blocks(t: int, q_block: int):
    return jnp.arange(t // q_block), lambda a: a.reshape(t // q_block, q_block, *a.shape[1:])


def first_blocks(kinds, t: int, q_block: int, window: int, rows: int) -> list[int]:
    """The first block of queries each layer computes, so that the last `rows` rows
    leave the last layer whole: from the top down, the layer under a full layer
    computes every row (its keys are every position), the layer under a sliding
    layer starts window - 1 rows before that layer's first block."""
    first, need = [0] * len(kinds), t - rows
    for l in reversed(range(len(kinds))):
        first[l] = max(need, 0) // q_block
        need = 0 if kinds[l] == FULL else first[l] * q_block - (window - 1)
    return first


def _attention(x, lp, kind, s, prec, q_block, first=0):
    """x [T, D] -> (x + the attention branch of a layer of `kind`)[first * q_block:]:
    keys from every row, queries from block `first` on."""
    a = attention_sizes(s, kind)
    t, H, r, dn, dr, dv = x.shape[0], a["heads"], a["kv_rank"], a["nope"], a["rope"], a["v_dim"]
    idx, split = _blocks(t, q_block)
    group = min(HEAD_GROUP, H)
    span = t if kind == FULL else min(t, q_block + s["window"])     # the positions a block of queries can reach

    def keys(args):
        i, xb = args
        pos = i * q_block + jnp.arange(q_block)
        h = _block_norm(xb, lp["attn_norm"], s)
        kv = _mm("td,dk->tk", h, lp["wkv_a"], prec)
        out = (_latent(kv[:, :r], lp["kv_a_norm"], s), _rope(kv[:, r:], pos, a["rope_theta"]))
        return out + ((index_keys(h, pos, lp, s, prec),) if kind == FULL else ())

    c, kr, *ki = (y.reshape(t, -1) for y in jax.lax.map(keys, (idx, split(x))))

    def queries(args):
        i, xb = args
        qpos = i * q_block + jnp.arange(q_block)
        h = _block_norm(xb, lp["attn_norm"], s)
        cq = _latent(_mm("td,dr->tr", h, lp["wq_a"], prec), lp["q_a_norm"], s)
        q = _mm("tr,rh->th", cq, lp["wq_b"], prec).reshape(q_block, H, dn + dr)
        qn, qr = q[..., :dn], _rope(q[..., dn:], qpos, a["rope_theta"])
        start = jnp.clip(i * q_block + q_block - span, 0, t - span)
        kpos = start + jnp.arange(span)
        c_b, kr_b = (jax.lax.dynamic_slice_in_dim(y, start, span) for y in (c, kr))
        if kind == FULL:
            qi, w = index_queries(h, cq, qpos, lp, s, prec)
            seen = chosen_mask(index_scores(qi, w, ki[0], prec), kpos[None, :] <= qpos[:, None], s["index_topk"])
        else:
            seen = _window_mask(qpos, kpos, s["window"])

        def heads(g):
            sl = lambda w: jax.lax.dynamic_slice_in_dim(w, g * group, group, axis=0)
            kn = _mm("sr,hrd->shd", c_b, sl(lp["w_uk"]), prec)                    # keys and values BUILT from the latent
            v = _mm("sr,hrd->shd", c_b, sl(lp["w_uv"]), prec)
            qn_g, qr_g = (jax.lax.dynamic_slice_in_dim(y, g * group, group, axis=1) for y in (qn, qr))
            scores = (_mm("qhd,shd->hqs", qn_g, kn, prec) + _mm("qhd,sd->hqs", qr_g, kr_b, prec)) * _scale(a)
            p = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
            return _mm("hqs,shd->qhd", p, v, prec)

        o = jax.lax.map(heads, jnp.arange(H // group)).transpose(1, 0, 2, 3).reshape(q_block, H, dv)
        return xb + _mm("th,hd->td", _gate(o, h, lp, prec).reshape(q_block, H * dv), lp["wo"], prec)

    return jax.lax.map(queries, (idx[first:], split(x)[first:])).reshape(t - first * q_block, -1)


def _ffn(x, lp, s, prec, q_block):
    """x [T, D] -> (x + FFN(norm(x)), `held_slack` [T]: infinite for a dense layer), a block of positions at a time."""
    _, split = _blocks(x.shape[0], q_block)

    def one(xb):
        h = _block_norm(xb, lp["mlp_norm"], s)
        if "router" in lp:
            y, slack = routed_ffn_and_slack(h, lp, s, prec)
            return xb + y, slack
        return xb + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], prec), jnp.full(xb.shape[:1], jnp.inf)

    y, slack = jax.lax.map(one, split(x))
    return y.reshape(x.shape), slack.reshape(x.shape[0])


def layer_params(params: dict, l: int, s: dict) -> dict:
    """Layer l's leaves, a routed layer's held experts' banks among them."""
    lp = params["layers"][l]
    return lp if l < s["dense_layers"] else {**lp, **{k: params["banks"][k][l - s["dense_layers"]] for k in BANKS}}


def trunk(params: dict, tokens: jax.Array, s: dict, prec: str = "f32", q_block: int = 1024, rows: int | None = None):
    """tokens [T] -> (the trunk after the last layer [T, D], float32, before the
    final norm; the smallest `held_slack` of the position's routed layers [T]).
    With `rows`, only the last `rows` rows are the trunk's (and only what they
    depend on is computed: `first_blocks`); the rows before them are not.
    T must divide by q_block (pad at the end: a causal model's earlier positions
    do not see the padding). The FFN runs in blocks of at most 256 positions:
    every held expert for every token is 32 x 1536 floats a position."""
    t = tokens.shape[0]
    q_block = min(q_block, t)
    if t % q_block:
        raise ValueError(f"{t} positions do not divide into blocks of {q_block}")
    f_block = next(b for b in (256, 128, 64, 32, 16, 8, 4, 2, 1) if q_block % b == 0)
    first = first_blocks(s["kinds"], t, q_block, s["window"], t if rows is None else rows)
    x, slack = params["embed"][tokens].astype(jnp.float32), jnp.full((t,), jnp.inf)
    for l, kind in enumerate(s["kinds"]):
        lp, lo = layer_params(params, l, s), first[l] * q_block
        y, layer_slack = _ffn(_attention(x, lp, kind, s, prec, q_block, first[l]), lp, s, prec, f_block)
        x = jnp.concatenate([x[:lo], y])
        slack = jnp.minimum(slack, jnp.concatenate([jnp.full((lo,), jnp.inf), layer_slack]))
    return x, slack


def hidden(params: dict, tokens: jax.Array, s: dict, prec: str = "f32", q_block: int = 1024) -> jax.Array:
    return trunk(params, tokens, s, prec, q_block)[0]


def forward(params: dict, tokens: jax.Array, s: dict, prec: str = "f32", q_block: int = 1024) -> jax.Array:
    """tokens [T] -> logits [T, V] float32; rows before the last HEAD_ROWS are NaN
    (not computed: 67k x 19,008 floats are 5 GB, and a comparison that reads one
    cannot pass). At "f32", where the program computes in a dtype of `TIE_MARGIN`:
    a row of zeros at a position whose routing of a held expert lies within that
    margin of a tie in some routed layer (families/exaone_moe_reference.py)."""
    rows = min(HEAD_ROWS, tokens.shape[0])
    x, slack = trunk(params, tokens, s, prec, q_block, rows)
    logits = _mm("td,dv->tv", _rms_norm(x[-rows:], params["final_norm"], s["norm_eps"]), params["lm_head"], prec)
    margin = TIE_MARGIN.get(s["dtype"], 0.0) if prec == "f32" else 0.0
    if margin:
        logits = jnp.where((slack[-rows:] < margin)[:, None], 0.0, logits)
    return jnp.pad(logits, ((x.shape[0] - rows, 0), (0, 0)), constant_values=jnp.nan)
