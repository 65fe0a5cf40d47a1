"""The gated delta rule (`ops/delta_rule.py`), the short convolution and the chunk attention over staged keys
on the CPU under the interpreter: the rule's chunk and step forms against the position-at-a-time
recurrence, the convolution chunked against whole, the chunk attention against causal attention. No model
is built here: the family's program is held to its reference in tests/test_olmo_hybrid.py, its files to the
harness in tests/test_olmo_hybrid_family.py (one subject a file, so that `--dist loadfile` can run them
side by side).

Tolerances. The rule's forms differ from the recurrence in the order of their sums: 2e-5 of the largest
output is ten times what was seen (2e-6) with keys at random, and 5e-4 with keys nearly parallel and beta
near 2, where every row of a block corrects every other (1e-4 seen; the recurrence itself is that
sensitive there). A state kept in bfloat16 moves the same outputs by 1e-2 and fails both
(`test_a_bfloat16_state_fails_the_tolerance`).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


# -- the rule: chunk and step against the recurrence a position at a time -----------------------------
def _rule_inputs(seed, H, T, dk, dv, beta_shift=0.0, decay=(-7.0, -3.0), parallel=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v = (jax.random.normal(ks[i], (H, T, d)) for i, d in enumerate((dk, dk, dv)))
    if parallel:
        k = jnp.abs(k) + 2.0                                      # every key within a few degrees of every other
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[3], (H, T)) + beta_shift)
    g = -jnp.exp(jax.random.uniform(ks[4], (H, T), minval=decay[0], maxval=decay[1]))
    return q, k, v, g, beta, jax.random.normal(ks[5], (H, dk, dv))


RULE_CASES = {
    "random-keys": (dict(), 2e-5),
    "beta-near-0": (dict(beta_shift=-6.0), 2e-5),
    "beta-near-2-keys-nearly-parallel": (dict(beta_shift=6.0, parallel=True), 5e-4),
    "strong-decay": (dict(decay=(0.0, 5.0)), 2e-5),               # g down to -148 a token: a state wiped at a token
    "weak-decay": (dict(decay=(-12.0, -9.0)), 2e-5),
}


#: heads, positions, block. A program of the blocked rule holds the most heads up to `CHUNK_HEADS` (4) that divide H: 1, 2,
#: 3 (six heads: two programs), ONE of thirteen (a prime over the bound: the one-head program), 3 of thirty, 4 of 64
CHUNKS = {"two-heads": (2, 48, 16), "one-head": (1, 48, 16), "six-heads-in-two-programs": (6, 48, 16), "thirteen-heads-a-program-each": (13, 48, 16),
          "thirty-heads-by-three": (30, 32, 16), "sixty-four-heads-by-four": (64, 32, 16), "three-heads-in-blocks-of-64": (3, 128, 64)}


@pytest.mark.parametrize("chunk", list(CHUNKS))
@pytest.mark.parametrize("case", list(RULE_CASES))
def test_the_chunk_form_is_the_recurrence(interpreted, case, chunk):
    """Positions in blocks (a block's edge inside the chunk), then the same with
    eleven fewer of them counting (a padded last chunk): outputs and state."""
    from tony_tpu.ops import delta_rule as D

    kw, tol = RULE_CASES[case]
    H, T, block = CHUNKS[chunk]
    q, k, v, g, beta, s0 = _rule_inputs(3, H, T, 8, 16, **kw)
    want_o, want_s = D.gated_delta_scan(q, k, v, g, beta, s0)
    o, s = D.gated_delta_chunk(q, k, v, g, beta, s0, block=block)
    scale = float(jnp.abs(want_o).max())
    assert float(jnp.abs(o - want_o).max()) < tol * scale and float(jnp.abs(s - want_s).max()) < tol * float(jnp.abs(want_s).max())
    valid = T - 11
    o, s = D.gated_delta_chunk(q, k, v, g, beta, s0, jnp.int32(valid), block=block)
    _, want_s = D.gated_delta_scan(*(a[:, :valid] for a in (q, k, v, g, beta)), s0)
    assert float(jnp.abs(o[:, :valid] - want_o[:, :valid]).max()) < tol * scale
    assert float(jnp.abs(s - want_s).max()) < tol * float(jnp.abs(want_s).max())


@pytest.mark.parametrize("heads", [2, 6], ids=["two-heads-a-program", "two-programs-of-three-heads"])
@pytest.mark.parametrize("case", ["beta-near-2-keys-nearly-parallel", "strong-decay", "weak-decay"])
@pytest.mark.parametrize("cut", [16, 32])
def test_a_chunk_boundary_inside_a_prompt_carries_the_state(interpreted, cut, case, heads):
    """Two chunks, the second from the first's state: the one recurrence."""
    from tony_tpu.ops import delta_rule as D

    kw, tol = RULE_CASES[case]
    *x, s0 = _rule_inputs(4, heads, 64, 8, 16, **kw)
    want_o, want_s = D.gated_delta_scan(*x, s0)
    first, mid = D.gated_delta_chunk(*(a[:, :cut] for a in x), s0, block=16)
    second, s = D.gated_delta_chunk(*(a[:, cut:] for a in x), mid, block=16)
    assert float(jnp.abs(jnp.concatenate([first, second], axis=1) - want_o).max()) < tol * float(jnp.abs(want_o).max())
    assert float(jnp.abs(s - want_s).max()) < tol * float(jnp.abs(want_s).max())


@pytest.mark.parametrize("shape", [(4, 128, 64), (3, 48, 16)], ids=["four-heads-of-two-blocks", "three-heads-in-blocks-of-16"])
@pytest.mark.parametrize("case", ["beta-near-2-keys-nearly-parallel", "strong-decay"])
def test_a_program_of_several_heads_is_its_heads_one_at_a_time(interpreted, case, shape):
    """ONE program of all the heads against the same inputs a head at a time (the
    one-head program, the parent's grid): the same operations a head in the same
    order, so the outputs and the states are equal BIT FOR BIT, not within a tolerance."""
    from tony_tpu.ops import delta_rule as D

    H, T, block = shape
    args = _rule_inputs(7, H, T, 8, 16, **RULE_CASES[case][0])
    o, s = D.gated_delta_chunk(*args, jnp.int32(T - 5), block=block)
    alone = [D.gated_delta_chunk(*(a[n:n + 1] for a in args), jnp.int32(T - 5), block=block) for n in range(H)]
    assert bool((o == jnp.concatenate([a for a, _ in alone])).all()) and bool((s == jnp.concatenate([b for _, b in alone])).all())


@pytest.mark.parametrize("case", ["random-keys", "beta-near-2-keys-nearly-parallel", "strong-decay"])
def test_the_step_form_is_the_recurrence(interpreted, case):
    """Three slots, each at another position of the sequence with another state, four steps on."""
    from tony_tpu.ops import delta_rule as D

    kw, tol = RULE_CASES[case]
    q, k, v, g, beta, s0 = _rule_inputs(5, 4, 12, 8, 16, **kw)
    at = np.array([0, 3, 7])
    state = jnp.stack([s0 * (1 + i) for i in range(3)])
    want = [D.gated_delta_scan(*(a[:, p:p + 4] for a in (q, k, v, g, beta)), state[i]) for i, p in enumerate(at)]
    for j in range(4):
        o, state = D.gated_delta_step(*(a[:, at + j].swapaxes(0, 1) for a in (q, k, v, g, beta)), state)
        for i in range(3):
            assert float(jnp.abs(o[i] - want[i][0][:, j]).max()) < tol * float(jnp.abs(want[i][0]).max())
    assert all(float(jnp.abs(state[i] - want[i][1]).max()) < tol * float(jnp.abs(want[i][1]).max()) for i in range(3))


def test_a_bfloat16_state_fails_the_tolerance(interpreted):
    """What the tolerances above are tight enough to tell: the recurrence with its
    state rounded to bfloat16 after every position is 1e-2 off, 500 tolerances."""
    from tony_tpu.ops import delta_rule as D

    q, k, v, g, beta, s0 = _rule_inputs(3, 2, 48, 8, 16)
    want, _ = D.gated_delta_scan(q, k, v, g, beta, s0)
    state, outs = s0, []
    for t in range(48):
        o, state = D.gated_delta_scan(*(a[:, t:t + 1] for a in (q, k, v, g, beta)), state)
        state = state.astype(jnp.bfloat16).astype(jnp.float32)
        outs.append(o)
    assert float(jnp.abs(jnp.concatenate(outs, 1) - want).max()) > 100 * 2e-5 * float(jnp.abs(want).max())


@pytest.mark.parametrize("rows,valid", [(32, None), (64, None), (64, 41), (512, 300)],
                         ids=["one-tile", "two-chunks", "a-padded-chunk", "tiles-of-256-rows"])
def test_the_convolution_in_chunks_is_the_whole_one(interpreted, rows, valid):
    """A sequence convolved whole against the same in two chunks with the tail
    carried, and the steps that follow; `valid` short of the second chunk: the
    tail is the last three inputs that count."""
    from tony_tpu.ops import delta_rule as D

    C, half = 128, rows // 2
    ks = jax.random.split(jax.random.PRNGKey(rows), 2)
    u, w = jax.random.normal(ks[0], (rows + 2, C)), jax.random.normal(ks[1], (4, C))
    xp = jnp.concatenate([jnp.zeros((3, C)), u])
    acc = sum(w[j] * xp[j:j + rows + 2] for j in range(4))
    want = acc * jax.nn.sigmoid(acc)
    first, tail = D.short_conv_chunk(u[:half], jnp.zeros((3, C)), w)
    second, tail = D.short_conv_chunk(u[half:rows], tail, w, None if valid is None else jnp.int32(valid - half))
    n = rows if valid is None else valid
    assert float(jnp.abs(jnp.concatenate([first, second])[:n] - want[:n]).max()) < 1e-5
    assert np.array_equal(np.asarray(tail), np.asarray(u[n - 3:n]))
    tails = jnp.stack([tail, tail])
    for j in range(2):                                                                 # decode goes on from the tail
        y, tails = D.short_conv_step(jnp.stack([u[n + j], u[n + j]]), tails, w)
        if valid is None:
            assert float(jnp.abs(y[1] - want[n + j]).max()) < 1e-5


@pytest.mark.parametrize("pos0", [0, 16, 48, 96], ids=["first-chunk", "a-tile-in", "across-tiles", "last-tiles"])
def test_the_chunk_attention_is_causal_attention_over_the_staged_keys(interpreted, pos0):
    """32 queries at pos0.. against 128 staged keys in tiles of 32 (q blocks of
    16): whole tiles before the diagonal unmasked, the diagonal's by position,
    tiles past a q block's last row not computed (they hold 1e4)."""
    from tony_tpu.ops.attention import chunk_prefill_attention

    H, T, d, Tk = 3, 32, 16, 128
    ks = jax.random.split(jax.random.PRNGKey(pos0), 3)
    q, k, v = jax.random.normal(ks[0], (H, T, d)), jax.random.normal(ks[1], (H, Tk, d)), jax.random.normal(ks[2], (H, Tk, d))
    live = (jnp.arange(Tk) < pos0 + T)[None, :, None]
    o = chunk_prefill_attention(q, jnp.where(live, k, 1e4), jnp.where(live, v, 1e4), jnp.int32(pos0), jnp.int32(pos0 + T),
                                block_q=16, block_k=32)
    s = jnp.einsum("htd,hkd->htk", q, k) * d ** -0.5
    mask = jnp.arange(Tk)[None, :] <= (pos0 + jnp.arange(T))[:, None]
    want = jnp.einsum("htk,hkd->htd", jax.nn.softmax(jnp.where(mask, s, -1e30), -1), v)
    assert float(jnp.abs(o - want).max()) < 2e-6
    # the same from a request's whole staging [L, 1, Hkv, Tk, d] and a layer's index: no layer's slice is handed in
    stage = lambda a: jnp.stack([jnp.full_like(a, 1e4), jnp.where(live, a, 1e4)])[:, None]
    staged = chunk_prefill_attention(q, stage(k), stage(v), jnp.int32(pos0), jnp.int32(pos0 + T), jnp.int32(1), block_q=16, block_k=32)
    assert np.array_equal(np.asarray(staged), np.asarray(o))
