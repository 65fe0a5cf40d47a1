"""The phi4_flash family's plain reference: Phi-4-mini-flash-reasoning's forward pass
(SambaY with differential attention) in jax.numpy and float32, matrix products at
precision "highest".

No kernel, no cache, no chunked form of the recurrence, no row skipped, one sequence
at a time, nothing shared with tony_tpu/. x is [T, D], M = memory_layer:

  x0 = embed[token]
  every layer i (`_block`): x = x + Mixer_i(LN(x)); x = x + FFN(LN'(x)); LN with weight and bias (`_layer_norm`);
      FFN(h) = (up silu(gate)) W_down, [gate | up] = h W_gu (`_swiglu`)
  logits = LN(x_L) embed^T                                (tied; `_head`)

  Mixer_i (`kind`): mamba for even i <= M, window for odd i < M, full for i = M + 1, gmu for even i >= M + 2,
      cross for odd i >= M + 3.
  mamba   [x | z] = u W_in; x = silu(conv(x) + b), causal and depthwise over `conv_taps` inputs (`_conv_silu`);
          [d | B | C] = x W_x; dt = softplus(d W_dt + b_dt) [E]; A = -exp(A_log) [E, N] (`_steps`); then THE LITERAL
          RECURRENCE, a scan over positions with a float32 state [E, N], zero at position 0: h = exp(dt A) h + (dt
          x) B^T; y = h C + D x (`_position`); out (y silu(z)) W_out. At layer M, y (before the gate) is the MEMORY
          of its position.
  gmu     (m silu(u W_g)) W_o, m the memory of the same position (`_gmu`).
  full, window   [q | k | v] = u W_qkv + b, H / Hkv / Hkv heads of dh, no position term. Stripes (`_stripes`): q1
          the even query heads, q2 the odd; k1, v1 the even kv heads, k2, v2 the odd; query head a of a stripe reads
          kv head a // (H / Hkv) of it. Vp = [v1 | v2]. A1 = softmax(q1 k1^T / sqrt(dh) + mask) Vp, A2 likewise from
          q2, k2, BOTH MAPS WHOLE under the causal mask (window: the `window` newest keys, a query's own among them:
          `_mask`); lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0, lam0 = 0.8 - 0.6 exp(-0.3 i) (`_lambda`); o =
          rmsnorm over 2 dh of (A1 - lam A2), times a weight and (1 - lam0) (`_sub_norm`); row a of 2 dh is laid back
          as heads 2a, 2a + 1; out o W_o + b_o.
  cross   q = u W_q + b only; k, v are layer M + 1's, of positions <= t; the same form with its own lambdas, norm
          and W_o.

What the published configuration does not give is the configuration's `assumed`
(families/phi4_flash.py: sizes), each choice one function here and one in the
program.

Positions are processed in blocks of `q_block` wherever a temporary would grow with
the sequence (projections, FFN, scores), and the head a slice of the vocabulary at
a time; the recurrence runs position by position inside the blocks, the
convolution's last inputs carried from block to block. EVERY layer runs on EVERY
row: the program's prefill sends one row of a chunk through the layers above M + 1,
and this is what proves that exact. `forward` returns logits [T, V] whose rows
before the last HEAD_ROWS are NaN (not computed, and a comparison that reads one
cannot pass: NaN is under no limit).

Precisions: "f32" is the reference. "fp8" is the control: the same mathematics with
both operands of every matrix product (the recurrence's write and read among them)
rounded to float8_e4m3, the nearest precision below bf16. Every position is
stated: there is no routing here, and so no tie.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from families.exaone_moe_reference import _mm, nll, seed_key

__all__ = ["CONTROL", "GRAD_LEAVES", "HEAD_ROWS", "forward", "init_weights", "nll", "seed_key"]

CONTROL = "fp8"
#: no training cell: no gradient is compared
GRAD_LEAVES = ()
#: rows at the end of a sequence whose logits `forward` computes
HEAD_ROWS = 2048
#: slices of the vocabulary's rows the embedding is drawn in and the head is multiplied in
VOCAB_SLICES = 8

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"


def kind(i: int, s: dict) -> str:
    """ASSUMED layer_kinds: what mixer layer i runs (M = memory_layer, the mamba layer whose scan is the memory)."""
    m = s["memory_layer"]
    if i <= m:
        return WINDOW if i % 2 else MAMBA
    if i == m + 1:
        return FULL
    return CROSS if i % 2 else GMU


def layer_params(params: dict, i: int, s: dict) -> dict:
    """Layer i's leaves out of the tree the program reads: `trunk` the (mamba, window) periods below M stacked, then
    `memory`, `full`, then `cross` the (gmu, cross) periods stacked."""
    m = s["memory_layer"]
    if i == m:
        return params["memory"]
    if i == m + 1:
        return params["full"]
    stack, j = (params["trunk"], i // 2) if i < m else (params["cross"], (i - m - 2) // 2)
    return jax.tree.map(lambda a: a[j], stack[kind(i, s)])


def init_weights(key: jax.Array, s: dict) -> dict:
    """The parameter tree in the layout tony_tpu/models/phi4_flash.py reads. ASSUMED seeded_draws: matrices truncated
    normal over their fan-in; norms at one; every bias truncated normal x 0.02 (so that a bias left out shows); A_log =
    log(1 .. N) along the state, dt_bias the inverse softplus of exp U(log 0.001, log 0.1), D = 1, the four lambda
    vectors N(0, 0.1), all float32; the embedding over a fan-in of d_model, drawn VOCAB_SLICES rows' slices at a time,
    so that the tied logits are of size one."""
    d, v, dt, f, e, n, r = s["d_model"], s["vocab"], jnp.dtype(s["dtype"]), s["d_ff"], s["d_inner"], s["ssm_state"], s["dt_rank"]
    dh = s["head_dim"]
    q, kv = s["heads"] * dh, s["kv_heads"] * dh
    below, above = s["memory_layer"] // 2, (s["layers"] - s["memory_layer"] - 2) // 2
    ks = iter(jax.random.split(key, 2 + 24 * s["layers"]))

    def dense(*shape, fan_in):
        return (jax.random.truncated_normal(next(ks), -2, 2, shape, jnp.float32) * fan_in ** -0.5).astype(dt)

    def bias(*shape):
        return (jax.random.truncated_normal(next(ks), -2, 2, shape, jnp.float32) * 0.02).astype(dt)

    def block():
        return {"norm": jnp.ones((d,), dt), "norm_b": bias(d), "ffn_norm": jnp.ones((d,), dt), "ffn_norm_b": bias(d),
                "w_gu": dense(d, 2 * f, fan_in=d), "w_down": dense(f, d, fan_in=f)}

    def lambdas():
        return {name: 0.1 * jax.random.normal(next(ks), (dh,), jnp.float32) for name in ("lq1", "lk1", "lq2", "lk2")}

    def mamba():
        step = jnp.exp(jax.random.uniform(next(ks), (e,), jnp.float32, np.log(0.001), np.log(0.1)))
        return {**block(), "w_in": dense(d, 2 * e, fan_in=d), "conv": dense(s["conv_taps"], e, fan_in=s["conv_taps"]), "conv_bias": bias(e),
                "w_x": dense(e, r + 2 * n, fan_in=e), "w_dt": dense(r, e, fan_in=r), "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), (e, n)), "D": jnp.ones((e,), jnp.float32),
                "w_out": dense(e, d, fan_in=e)}

    def attention():
        return {**block(), "w_qkv": dense(d, q + 2 * kv, fan_in=d), "b_qkv": bias(q + 2 * kv), "wo": dense(q, d, fan_in=q), "bo": bias(d),
                **lambdas(), "sub_norm": jnp.ones((2 * dh,), dt)}

    def gmu():
        return {**block(), "w_g": dense(d, e, fan_in=d), "w_o": dense(e, d, fan_in=e)}

    def cross():
        return {**block(), "w_q": dense(d, q, fan_in=d), "b_q": bias(q), "wo": dense(q, d, fan_in=q), "bo": bias(d),
                **lambdas(), "sub_norm": jnp.ones((2 * dh,), dt)}

    def stack(layer, count):
        return jax.tree.map(lambda *a: jnp.stack(a), *[layer() for _ in range(count)])

    slices = VOCAB_SLICES if v % VOCAB_SLICES == 0 else 1
    embed = jax.lax.map(lambda k: (jax.random.truncated_normal(k, -2, 2, (v // slices, d), jnp.float32) * d ** -0.5).astype(dt),
                        jax.random.split(next(ks), slices)).reshape(v, d)
    return {"embed": embed, "trunk": {MAMBA: stack(mamba, below), WINDOW: stack(attention, below)}, "memory": mamba(), "full": attention(),
            "cross": {GMU: stack(gmu, above), CROSS: stack(cross, above)}, "final_norm": jnp.ones((d,), dt), "final_norm_b": bias(d)}


# -- what the configuration's `assumed` states, one function each ---------------------------------

def _layer_norm(x, w, b, s):
    """ASSUMED norm: LayerNorm over the width, with a weight and a bias."""
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + s["norm_eps"]) * w.astype(jnp.float32) + b.astype(jnp.float32)


def _conv_silu(u, tail, w, b):
    """ASSUMED mamba_sizes (conv bias): u [Q, E] after the inputs tail [taps - 1, E]: silu(sum_j w_j u_{t - (taps-1) + j} + b), and the new tail."""
    q = u.shape[0]
    xp = jnp.concatenate([tail, u])
    return jax.nn.silu(sum(w[j].astype(jnp.float32) * xp[j:j + q] for j in range(w.shape[0])) + b.astype(jnp.float32)), xp[q:]


def _steps(x, lp, s, prec):
    """ASSUMED mamba_sizes (dt rank, no projection bias): x [Q, E] after the convolution -> (dt [Q, E] = softplus(d W_dt +
    b_dt), B, C [Q, N]) with [d | B | C] = x W_x."""
    r, n = s["dt_rank"], s["ssm_state"]
    dbc = _mm("te,ec->tc", x, lp["w_x"], prec)
    return jax.nn.softplus(_mm("tr,re->te", dbc[:, :r], lp["w_dt"], prec) + lp["dt_bias"].astype(jnp.float32)), dbc[:, r:r + n], dbc[:, r + n:]


def _position(h, inputs, a, d_skip, prec):
    """ASSUMED state_dtype float32: one position of the recurrence, the state h [E, N]; a = -exp(A_log) [E, N]."""
    xt, dtt, bt, ct = inputs
    h = jnp.exp(dtt[:, None] * a) * h + _mm("e,n->en", dtt * xt, bt, prec)
    return h, _mm("en,n->e", h, ct, prec) + d_skip * xt


def _gmu(u, m, lp, prec):
    """ASSUMED layer_kinds (gmu): the memory of the rows' own positions, gated by the token."""
    return _mm("te,ed->td", m * jax.nn.silu(_mm("td,de->te", u, lp["w_g"], prec)), lp["w_o"], prec)


def _stripes(a):
    """ASSUMED differential_form (stripes): heads [.., H, dh] -> (the even heads, the odd heads)."""
    return a[..., 0::2, :], a[..., 1::2, :]


def _lambda(lp, i):
    """ASSUMED differential_form (lam0): (lam, lam0) of layer i."""
    f = lambda name: lp[name].astype(jnp.float32)
    lam0 = 0.8 - 0.6 * np.exp(-0.3 * i)
    return jnp.exp(jnp.sum(f("lq1") * f("lk1"))) - jnp.exp(jnp.sum(f("lq2") * f("lk2"))) + lam0, lam0


def _sub_norm(d, lp, lam0, s):
    """ASSUMED differential_form (sub-norm): RMSNorm over a pair-row's 2 dh with a weight, times (1 - lam0)."""
    return d * jax.lax.rsqrt(jnp.mean(d * d, axis=-1, keepdims=True) + s["norm_eps"]) * lp["sub_norm"].astype(jnp.float32) * (1.0 - lam0)


def _mask(qpos, kpos, window: int):
    """ASSUMED window_edge, no position term: causal; a window layer's query sees the `window` newest keys, its own among them."""
    seen = kpos[None, :] <= qpos[:, None]
    return seen & (kpos[None, :] > qpos[:, None] - window) if window else seen


def _swiglu(n, lp, s, prec):
    gu = _mm("td,df->tf", n, lp["w_gu"], prec)
    return _mm("tf,fd->td", gu[:, s["d_ff"]:] * jax.nn.silu(gu[:, :s["d_ff"]]), lp["w_down"], prec)


# -- the layers -------------------------------------------------------------------------------------

def _blocks(t: int, q_block: int):
    return jnp.arange(t // q_block), lambda a: a.reshape(t // q_block, q_block, *a.shape[1:])


def _normed(xb, lp, s):
    return _layer_norm(xb, lp["norm"], lp["norm_b"], s)


def _mamba(x, lp, s, prec, q_block):
    """x [T, D] -> (the mixer's branch [T, D], the scan's y [T, E]: the memory, where this is layer M)."""
    t, e, n = x.shape[0], s["d_inner"], s["ssm_state"]
    _, split = _blocks(t, q_block)
    a, d_skip = -jnp.exp(lp["A_log"].astype(jnp.float32)), lp["D"].astype(jnp.float32)

    def block(carry, xb):
        state, tail = carry
        xz = _mm("td,dc->tc", _normed(xb, lp, s), lp["w_in"], prec)
        xs, tail = _conv_silu(xz[:, :e], tail, lp["conv"], lp["conv_bias"])
        dt, b, c = _steps(xs, lp, s, prec)
        state, y = jax.lax.scan(lambda h, pos: _position(h, pos, a, d_skip, prec), state, (xs, dt, b, c))
        return (state, tail), (_mm("te,ed->td", y * jax.nn.silu(xz[:, e:]), lp["w_out"], prec), y)

    start = (jnp.zeros((e, n), jnp.float32), jnp.zeros((s["conv_taps"] - 1, e), jnp.float32))
    _, (out, y) = jax.lax.scan(block, start, split(x))
    return out.reshape(t, -1), y.reshape(t, e)


def _keys_values(x, lp, s, prec, q_block):
    """x [T, D] -> the layer's k, v [T, Hkv, dh] of every position."""
    t, hkv, dh = x.shape[0], s["kv_heads"], s["head_dim"]
    qw = s["heads"] * dh
    _, split = _blocks(t, q_block)

    def one(xb):
        kv = _mm("td,dh->th", _normed(xb, lp, s), lp["w_qkv"][:, qw:], prec) + lp["b_qkv"][qw:].astype(jnp.float32)
        return kv[:, :hkv * dh].reshape(q_block, hkv, dh), kv[:, hkv * dh:].reshape(q_block, hkv, dh)

    k, v = jax.lax.map(one, split(x))
    return k.reshape(t, hkv, dh), v.reshape(t, hkv, dh)


def _attention(x, lp, i, k, v, window, s, prec, q_block):
    """x [T, D] -> the differential attention branch [T, D] of layer i over the keys and values k, v [T, Hkv, dh] (its
    own, or layer M + 1's for a cross layer), both maps whole."""
    t, h, hkv, dh = x.shape[0], s["heads"], s["kv_heads"], s["head_dim"]
    idx, split = _blocks(t, q_block)
    (k1, k2), (v1, v2) = _stripes(k), _stripes(v)
    vp = jnp.concatenate([v1, v2], axis=-1)                                 # [T, Hkv / 2, 2 dh]
    lam, lam0 = _lambda(lp, i)
    kpos = jnp.arange(t)
    w_q, b_q = (lp["w_q"], lp["b_q"]) if "w_q" in lp else (lp["w_qkv"][:, :h * dh], lp["b_qkv"][:h * dh])

    def queries(args):
        j, xb = args
        seen = _mask(j * q_block + jnp.arange(q_block), kpos, window)[None, None]
        q = (_mm("td,dh->th", _normed(xb, lp, s), w_q, prec) + b_q.astype(jnp.float32)).reshape(q_block, h, dh)

        def a_map(qs, ks):
            qs = qs.reshape(q_block, hkv // 2, h // hkv, dh)                # query head a of the stripe reads kv head a // (H / Hkv)
            pr = jax.nn.softmax(jnp.where(seen, _mm("qkgd,tkd->kgqt", qs, ks, prec) * dh ** -0.5, -jnp.inf), axis=-1)
            return _mm("kgqt,tkd->qkgd", pr, vp, prec).reshape(q_block, h // 2, 2 * dh)

        q1, q2 = _stripes(q)
        o = _sub_norm(a_map(q1, k1) - lam * a_map(q2, k2), lp, lam0, s)     # row a of 2 dh: heads 2a, 2a + 1
        return _mm("th,hd->td", o.reshape(q_block, h * dh), lp["wo"], prec) + lp["bo"].astype(jnp.float32)

    return jax.lax.map(queries, (idx, split(x))).reshape(t, -1)


def _ffn(h, lp, s, prec, q_block):
    """h [T, D] -> h + FFN(LN'(h)), a block of positions at a time."""
    _, split = _blocks(h.shape[0], q_block)
    return jax.lax.map(lambda hb: hb + _swiglu(_layer_norm(hb, lp["ffn_norm"], lp["ffn_norm_b"], s), lp, s, prec), split(h)).reshape(h.shape)


def _gmu_layer(x, m, lp, s, prec, q_block):
    _, split = _blocks(x.shape[0], q_block)
    return jax.lax.map(lambda a: _gmu(_normed(a[0], lp, s), a[1], lp, prec), (split(x), split(m))).reshape(x.shape)


def trunk(params: dict, tokens: jax.Array, s: dict, prec: str = "f32", q_block: int = 256) -> jax.Array:
    """tokens [T] -> the rows after the last layer [T, D], float32, before the final
    norm: every layer on every row. T must divide by q_block (pad at the end: a
    causal model's earlier positions do not see the padding)."""
    t = tokens.shape[0]
    q_block = min(q_block, t)
    if t % q_block:
        raise ValueError(f"{t} positions do not divide into blocks of {q_block}")
    x = params["embed"][tokens].astype(jnp.float32)
    memory = shared = None
    for i in range(s["layers"]):
        lp, what = layer_params(params, i, s), kind(i, s)
        if what == MAMBA:
            out, y = _mamba(x, lp, s, prec, q_block)
            memory = y if i == s["memory_layer"] else memory
        elif what == GMU:
            out = _gmu_layer(x, memory, lp, s, prec, q_block)
        elif what == CROSS:
            out = _attention(x, lp, i, *shared, 0, s, prec, q_block)
        else:
            own = _keys_values(x, lp, s, prec, q_block)
            shared = own if what == FULL else shared
            out = _attention(x, lp, i, *own, s["window"] if what == WINDOW else 0, s, prec, q_block)
        x = _ffn(x + out, lp, s, prec, q_block)
    return x


def forward(params: dict, tokens: jax.Array, s: dict, prec: str = "f32", q_block: int = 256) -> jax.Array:
    """tokens [T] -> logits [T, V] float32 over the embedding transposed; rows
    before the last HEAD_ROWS are NaN: not computed, and never a row that agrees."""
    x = trunk(params, tokens, s, prec, q_block)
    t, v = x.shape[0], s["vocab"]
    rows = min(HEAD_ROWS, t)
    y = _layer_norm(x[-rows:], params["final_norm"], params["final_norm_b"], s)
    n = VOCAB_SLICES if v % VOCAB_SLICES == 0 else 1
    head = params["embed"].reshape(n, v // n, -1)

    def a_slice(i, logits):
        part = _mm("td,vd->tv", y, jax.lax.dynamic_index_in_dim(head, i, keepdims=False), prec)
        return jax.lax.dynamic_update_slice(logits, part, (0, i * (v // n)))

    # the rows before are padding the caller's slice never reads: under one jit the pad and the slice fuse, and the
    # [T, V] array (27 GB at 34k positions) is never made
    return jnp.pad(jax.lax.fori_loop(0, n, a_slice, jnp.zeros((rows, v), jnp.float32)), ((t - rows, 0), (0, 0)), constant_values=jnp.nan)
