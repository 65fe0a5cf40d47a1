"""The minicpm_sala family's plain reference: the MiniCPM-SALA forward pass in
jax.numpy and float32, matrix products at precision "highest".

No kernel, no cache, no chunk, one sequence at a time, nothing shared with
tony_tpu/. The trunk is MiniCPM's (embeddings x scale_emb, each residual branch
x scale_depth / sqrt(published depth), logits from the final norm over
hidden_size / dim_model_base, SwiGLU). A layer's mixer is what `mixer_types`
says of it:

  lightning-attn  q, k, v per head; RMSNorm on q and k per head; rotary
                  embedding (rotate-half); then THE LITERAL RECURRENCE, a scan
                  over positions: S_t = lambda_h S_{t-1} + k_t^T v_t, o_t = q_t
                  S_t / sqrt(d); RMSNorm on each head's o; x sigmoid(W_g x); W_o.
  minicpm4        q, k, v; RMSNorm on q and k per head; no rotary embedding; A
                  FULL SCORE MATRIX a block of queries with the visible set as
                  a mask; x sigmoid(W_g x); W_o. The visible set of the query
                  at position t (context n = t + 1): every key up to t if n <=
                  dense_len; else block 0, the top-k blocks by score, and the
                  last `window` tokens. Score of a block: compressed keys are
                  means of `kernel` keys every `stride`, taken for the kernels
                  that end at or before t; softmax over them; a block takes
                  the largest weight among the kernels that overlap it, summed
                  over its kv group's query heads. One set a kv group.

What the published configuration does not give is read from the same `assumed`
keys as the program reads it (families/minicpm_sala.py: sizes).

Positions are processed in blocks of `q_block` wherever a temporary would grow
with the sequence (projections, FFN, scores), so that 50k positions fit beside
the weights; the recurrence runs position by position inside them.

`forward` returns logits [T, V] whose rows before the last HEAD_ROWS are NaN
(not computed, and a comparison that reads one cannot pass: NaN is under no
limit): at 50k positions x 73,448 tokens the full matrix is 15 GB, the serving check
(check.py: `_teacher_forced`) reads 512 rows that lie within the last 2047 of
the padded sequence it builds, and XLA computes only what is read of a padded
array but all of a matrix product. Sequences up to HEAD_ROWS get every row.

Precisions: "f32" is the reference. "fp8" is the control: the same mathematics
with both operands of every matrix product (the recurrence's outer product and
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

SPARSE, LINEAR = "minicpm4", "lightning-attn"


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 65536), seed // 65536)


def init_weights(key: jax.Array, s: dict) -> dict:
    """The parameter tree in the layout tony_tpu/models/minicpm_sala.py reads:
    `layers` is a list with one dict of leaves a layer, in order, shaped by the
    layer's kind (`mixer_types`). Truncated normal, fan-in scaled; norms at one."""
    d, f, v, dt = s["d_model"], s["d_ff"], s["vocab"], jnp.dtype(s["dtype"])
    ks = iter(jax.random.split(key, 3 + 9 * len(s["mixer_types"])))

    def dense(*shape, fan_in):
        return (jax.random.truncated_normal(next(ks), -2, 2, shape, jnp.float32) * fan_in ** -0.5).astype(dt)

    def layer(kind):
        sparse = kind == SPARSE
        hd = s["head_dim"] if sparse else s["lin_head_dim"]
        q = (s["heads"] if sparse else s["lin_heads"]) * hd
        kv = s["kv_heads"] * hd if sparse else q
        lp = {"attn_norm": jnp.ones((d,), dt), "mlp_norm": jnp.ones((d,), dt),
              "w_gate": dense(d, f, fan_in=d), "w_up": dense(d, f, fan_in=d), "w_down": dense(f, d, fan_in=f),
              "wq": dense(d, q, fan_in=d), "wk": dense(d, kv, fan_in=d), "wv": dense(d, kv, fan_in=d),
              "wg": dense(d, q, fan_in=d), "wo": dense(q, d, fan_in=q),
              "q_norm": jnp.ones((hd,), dt), "k_norm": jnp.ones((hd,), dt)}
        return lp if sparse else {**lp, "o_norm": jnp.ones((hd,), dt)}

    return {"embed": dense(v, d, fan_in=1.0), "layers": [layer(kind) for kind in s["mixer_types"]],
            "final_norm": jnp.ones((d,), dt), "lm_head": dense(d, v, fan_in=d)}


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


def _rope(x, positions, theta):
    """x [T, H, d] float32 at `positions` [T]; rotate-half."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.outer(positions.astype(jnp.float32), inv)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _blocks(t: int, q_block: int):
    return jnp.arange(t // q_block), lambda a: a.reshape(t // q_block, q_block, *a.shape[1:])


def _ffn(x, lp, s, prec, q_block, scale):
    """x [T, D] -> x + scale x FFN(norm(x)), a block of positions at a time (a
    branch is added to its block where it is made: one more [T, D] array a
    stage, at 50k positions, is 0.8 GB)."""
    _, split = _blocks(x.shape[0], q_block)

    def one(xb):
        h = _rms_norm(xb, lp["mlp_norm"], s["norm_eps"])
        g = jax.nn.silu(_mm("td,df->tf", h, lp["w_gate"], prec))
        return xb + scale * _mm("tf,fd->td", g * _mm("td,df->tf", h, lp["w_up"], prec), lp["w_down"], prec)

    return jax.lax.map(one, split(x)).reshape(x.shape)


def decay(s: dict) -> jax.Array:
    """lambda_h = exp(-2^(-8(h+1)/H)), h from 0: Lightning Attention's slopes."""
    h = jnp.arange(1, s["lin_heads"] + 1, dtype=jnp.float32)
    return jnp.exp(-jnp.exp2(-8.0 * h / s["lin_heads"]))


def _linear_layer(x, lp, s, prec, q_block, scale):
    """x [T, D] -> x + scale x the linear mixer's branch."""
    t, hl, dl = x.shape[0], s["lin_heads"], s["lin_head_dim"]
    idx, split = _blocks(t, q_block)
    lam = decay(s)[:, None, None]

    def block(state, args):
        i, xb = args
        h = _rms_norm(xb, lp["attn_norm"], s["norm_eps"])
        pos = i * q_block + jnp.arange(q_block)
        q = _rope(_rms_norm(_mm("td,dh->th", h, lp["wq"], prec).reshape(q_block, hl, dl), lp["q_norm"], s["norm_eps"]),
                  pos, s["rope_theta"])
        k = _rope(_rms_norm(_mm("td,dh->th", h, lp["wk"], prec).reshape(q_block, hl, dl), lp["k_norm"], s["norm_eps"]),
                  pos, s["rope_theta"])
        v = _mm("td,dh->th", h, lp["wv"], prec).reshape(q_block, hl, dl)

        def position(state, qkv):
            qt, kt, vt = qkv
            state = lam * state + _mm("hd,he->hde", kt, vt, prec)
            return state, _mm("hd,hde->he", qt, state, prec) * dl ** -0.5

        state, o = jax.lax.scan(position, state, (q, k, v))
        o = _rms_norm(o, lp["o_norm"], s["norm_eps"]).reshape(q_block, hl * dl)
        gate = jax.nn.sigmoid(_mm("td,dh->th", h, lp["wg"], prec))
        return state, xb + scale * _mm("th,hd->td", o * gate, lp["wo"], prec)

    _, out = jax.lax.scan(block, jnp.zeros((hl, dl, dl), jnp.float32), (idx, split(x)))
    return out.reshape(t, -1)


def compressed_keys(k: jax.Array, s: dict) -> jax.Array:
    """k [T, Hkv, d] -> [nK, Hkv, d]: entry j is the mean of k[stride*j : stride*j + kernel],
    for every j whose kernel lies inside the T positions."""
    kernel, stride = s["sparse_kernel_size"], s["sparse_kernel_stride"]
    n = (k.shape[0] - kernel) // stride + 1
    starts = jnp.arange(n) * stride
    return jnp.mean(k[starts[:, None] + jnp.arange(kernel)[None, :]], axis=1)


def chosen_blocks(q, kc, qpos, s: dict, prec: str) -> jax.Array:
    """q [Q, Hkv, G, d] at positions qpos [Q]; kc [nK, Hkv, d]. Returns bool
    [Q, Hkv, nB]: the blocks among the top-k for each query and kv group (block
    0 counted among them), nB = blocks up to the last compressed key's."""
    kernel, stride, block = s["sparse_kernel_size"], s["sparse_kernel_stride"], s["sparse_block_size"]
    n_k = kc.shape[0]
    n_b = (n_k * stride + kernel - stride + block - 1) // block
    scores = _mm("qkgd,jkd->qkgj", q, kc, prec) * q.shape[-1] ** -0.5
    ends = jnp.arange(n_k) * stride + kernel - 1
    done = ends[None, :] <= qpos[:, None]                                       # [Q, nK]
    p = jax.nn.softmax(jnp.where(done[:, None, None, :], scores, -jnp.inf), axis=-1)
    p = jnp.where(done[:, None, None, :], p, 0.0)
    # the kernels that overlap block b: a run of neighbours, first[b] .. last[b]
    b = np.arange(n_b)
    first = np.maximum(-(-(b * block - kernel + 1) // stride), 0)
    last = np.minimum(((b + 1) * block - 1) // stride, n_k - 1)
    run = first[:, None] + np.arange(int((last - first).max()) + 1)[None, :]    # [nB, widest run]
    inside = run <= last[:, None]
    run = np.minimum(run, n_k - 1)
    overlapping = jnp.asarray(inside) & done[:, run]                            # [Q, nB, run]: finished ones
    best = jnp.max(jnp.where(overlapping[:, None, None], p[..., run], 0.0), axis=-1)   # [Q, Hkv, G, nB]
    score = jnp.where(overlapping.any(-1)[:, None, :], best.sum(axis=2), -1.0)
    score = jnp.where((b < s["sparse_init_blocks"])[None, None, :], jnp.inf, score)
    order = jnp.argsort(-score, axis=-1, stable=True)[..., :min(s["sparse_topk"], n_b)]
    picked = jnp.zeros(score.shape, bool).at[
        jnp.arange(score.shape[0])[:, None, None], jnp.arange(score.shape[1])[None, :, None], order].set(True)
    return picked & (score >= 0)


def _sparse_layer(x, lp, s, prec, q_block, scale):
    """x [T, D] -> x + scale x the sparse mixer's branch."""
    t, h, hkv, dh = x.shape[0], s["heads"], s["kv_heads"], s["head_dim"]
    g, block = h // hkv, s["sparse_block_size"]
    idx, split = _blocks(t, q_block)

    def keys_values(xb):
        y = _rms_norm(xb, lp["attn_norm"], s["norm_eps"])
        k = _rms_norm(_mm("td,dh->th", y, lp["wk"], prec).reshape(q_block, hkv, dh), lp["k_norm"], s["norm_eps"])
        return k, _mm("td,dh->th", y, lp["wv"], prec).reshape(q_block, hkv, dh)

    k, v = jax.lax.map(keys_values, split(x))
    k, v = k.reshape(t, hkv, dh), v.reshape(t, hkv, dh)
    sparse_any = t > s["sparse_dense_len"] and t >= s["sparse_kernel_size"]
    kc = compressed_keys(k, s) if sparse_any else None
    kpos = jnp.arange(t)

    def queries(args):
        i, xb = args
        y = _rms_norm(xb, lp["attn_norm"], s["norm_eps"])
        q = _rms_norm(_mm("td,dh->th", y, lp["wq"], prec).reshape(q_block, hkv, g, dh), lp["q_norm"], s["norm_eps"])
        qpos = i * q_block + jnp.arange(q_block)
        scores = _mm("qkgd,tkd->kgqt", q, k, prec) * dh ** -0.5
        seen = jnp.broadcast_to((kpos[None, :] <= qpos[:, None])[None], (hkv, q_block, t))
        if sparse_any:
            picked = chosen_blocks(q, kc, qpos, s, prec)                        # [Q, Hkv, nB]
            by_block = jnp.repeat(picked, block, axis=-1)
            by_block = jnp.pad(by_block, ((0, 0), (0, 0), (0, max(t - by_block.shape[-1], 0))))[..., :t]
            near = kpos[None, :] > qpos[:, None] - s["sparse_window"]
            dense = (qpos + 1 <= s["sparse_dense_len"])[:, None]
            seen &= (by_block | (near | dense)[:, None, :]).transpose(1, 0, 2)
        p = jax.nn.softmax(jnp.where(seen[:, None], scores, -jnp.inf), axis=-1)
        o = _mm("kgqt,tkd->qkgd", p, v, prec).reshape(q_block, h * dh)
        gate = jax.nn.sigmoid(_mm("td,dh->th", y, lp["wg"], prec))
        return xb + scale * _mm("th,hd->td", o * gate, lp["wo"], prec)

    return jax.lax.map(queries, (idx, split(x))).reshape(t, -1)


def hidden(params: dict, tokens: jax.Array, s: dict, prec: str = "f32", q_block: int = 64) -> jax.Array:
    """tokens [T] -> the trunk after the last layer [T, D], float32 (before the
    final norm). T must divide by q_block (pad at the end: a causal model's
    earlier positions do not see the padding)."""
    t = tokens.shape[0]
    q_block = min(q_block, t)
    if t % q_block:
        raise ValueError(f"{t} positions do not divide into blocks of {q_block}")
    scale = s["scale_depth"] / s["layers_published"] ** 0.5
    x = params["embed"][tokens].astype(jnp.float32) * s["scale_emb"]
    for kind, lp in zip(s["mixer_types"], params["layers"], strict=True):
        mixer = _sparse_layer if kind == SPARSE else _linear_layer
        x = _ffn(mixer(x, lp, s, prec, q_block, scale), lp, s, prec, q_block, scale)
    return x


def forward(params: dict, tokens: jax.Array, s: dict, prec: str = "f32", q_block: int = 64) -> jax.Array:
    """tokens [T] -> logits [T, V] float32; rows before the last HEAD_ROWS are
    NaN: not computed (this file's head), and never a row that agrees."""
    x = hidden(params, tokens, s, prec, q_block)
    rows = min(HEAD_ROWS, x.shape[0])
    y = _rms_norm(x[-rows:], params["final_norm"], s["norm_eps"]) / (s["d_model"] / s["dim_model_base"])
    return jnp.pad(_mm("td,dv->tv", y, params["lm_head"], prec), ((x.shape[0] - rows, 0), (0, 0)),
                   constant_values=jnp.nan)


def nll(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Per-position negative log-likelihood, float32."""
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
