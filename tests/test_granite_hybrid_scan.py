"""The state-space scan (`ops/ssd.py`, B and C in one group) and the short convolution with a bias on the CPU
under the interpreter: the scan's chunk and step forms against the position-at-a-time recurrence, the
convolution against the literal one. No model is built here: the family's program is held to its
reference in tests/test_granite_hybrid.py, its files to the harness in tests/test_granite_hybrid_family.py
(one subject a file, so that `--dist loadfile` can run them side by side).

Tolerances. The chunk form differs from the recurrence in the order of its sums and in taking a decay as
exp of a difference of running sums: 2e-5 of the largest output is ten times what was seen (3e-6) whatever
the decays; the step form is the recurrence's own arithmetic (0 seen). A state kept in bfloat16 moves the
same outputs by 1e-2 and fails (`test_a_bfloat16_state_fails_the_tolerance`).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


# -- the scan: chunk and step against the recurrence a position at a time ----------------------------
def _scan_inputs(seed, T, H, P, N, decay=(-7.0, -3.0), dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (T, H, P)).astype(dtype)
    B, C = jax.random.normal(ks[1], (T, N)).astype(dtype), jax.random.normal(ks[2], (T, N)).astype(dtype)
    dt = jnp.exp(jax.random.uniform(ks[3], (T, H), minval=np.log(0.001), maxval=np.log(0.5)))
    g = -jnp.exp(jax.random.uniform(ks[4], (T, H), minval=decay[0], maxval=decay[1]))
    return x, dt, g, B, C, jax.random.normal(ks[5], (H,)), jax.random.normal(ks[6], (H, P, N))


SCAN_CASES = {
    "random-decays": dict(),
    "decays-near-0": dict(decay=(0.0, 5.0)),                      # g down to -148 a token: a state wiped at a token
    "decays-near-1": dict(decay=(-12.0, -9.0)),
    "every-rate-at-once": dict(decay=(-12.0, 5.0)),
}
SHAPES = {"four-blocks": (64, 4, 8, 16, 16), "heads-of-64-over-128": (32, 2, 64, 128, 16), "an-odd-head-count": (48, 3, 8, 16, 16),
          "one-block": (16, 4, 32, 16, 128)}


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("case", list(SCAN_CASES))
def test_the_chunk_form_is_the_recurrence(interpreted, case, shape):
    from tony_tpu.ops import ssd

    T, H, P, N, block = SHAPES[shape]
    args = _scan_inputs(1, T, H, P, N, **SCAN_CASES[case])
    want, state = ssd.ssd_scan(*args)
    got, new = ssd.ssd_chunk(*args[:6], ssd.lanes(args[6]), block=block)
    assert jnp.abs(got - want).max() < 2e-5 * jnp.abs(want).max() and jnp.abs(new - ssd.lanes(state)).max() < 2e-5 * jnp.abs(state).max()


@pytest.mark.parametrize("valid", [1, 11, 16, 37, 64])
def test_a_padded_chunks_state_stops_at_valid(interpreted, valid):
    """Rows past `valid` neither decay nor write: the state is the recurrence's
    after `valid` positions, and the rows before it read what they read unpadded."""
    from tony_tpu.ops import ssd

    args = _scan_inputs(2, 64, 4, 8, 16, decay=(-4.0, 2.0))
    want, state = ssd.ssd_scan(*(a[:valid] for a in args[:5]), *args[5:])
    got, new = ssd.ssd_chunk(*args[:6], ssd.lanes(args[6]), jnp.int32(valid), block=16)
    assert jnp.abs(got[:valid] - want).max() < 2e-5 * jnp.abs(want).max() and jnp.abs(new - ssd.lanes(state)).max() < 2e-5 * jnp.abs(state).max()


@pytest.mark.parametrize("cut", [16, 32, 48])
def test_a_chunk_boundary_inside_a_prompt_carries_the_state(interpreted, cut):
    """Two chunks, the second from the first's state: the one recurrence."""
    from tony_tpu.ops import ssd

    args = _scan_inputs(3, 64, 4, 8, 16)
    want, state = ssd.ssd_scan(*args)
    first, mid = ssd.ssd_chunk(*(a[:cut] for a in args[:5]), args[5], ssd.lanes(args[6]), block=16)
    second, new = ssd.ssd_chunk(*(a[cut:] for a in args[:5]), args[5], mid, block=16)
    got = jnp.concatenate([first, second])
    assert jnp.abs(got - want).max() < 2e-5 * jnp.abs(want).max() and jnp.abs(new - ssd.lanes(state)).max() < 2e-5 * jnp.abs(state).max()


@pytest.mark.parametrize("case", list(SCAN_CASES))
def test_the_step_form_is_the_recurrence(interpreted, case):
    """One position a slot, each slot its own state: `ssd_scan` over one position."""
    from tony_tpu.ops import ssd

    S, H, P, N = 3, 4, 8, 16
    x, dt, g, B, C, D, _ = _scan_inputs(4, S, H, P, N, **SCAN_CASES[case])
    state = jax.random.normal(jax.random.PRNGKey(9), (S, H, P, N))
    got, new = ssd.ssd_step(x, dt, g, B, C, D, ssd.lanes(state))
    for s in range(S):
        want, after = ssd.ssd_scan(x[s:s + 1], dt[s:s + 1], g[s:s + 1], B[s:s + 1], C[s:s + 1], D, state[s])
        assert jnp.abs(got[s] - want[0]).max() < 1e-6 * jnp.abs(want).max() and jnp.abs(new[s] - ssd.lanes(after)).max() < 1e-6


def test_a_bfloat16_state_fails_the_tolerance(interpreted):
    """What the float32 state is for: the same chunk from a state rounded to
    bfloat16 at every block's edge lies a thousand tolerances away."""
    from tony_tpu.ops import ssd

    args = _scan_inputs(5, 64, 4, 8, 16, decay=(-9.0, -6.0))
    want, _ = ssd.ssd_scan(*args)
    state, rows = ssd.lanes(args[6]), []
    for at in range(0, 64, 16):
        y, state = ssd.ssd_chunk(*(a[at:at + 16] for a in args[:5]), args[5], state.astype(jnp.bfloat16).astype(jnp.float32), block=16)
        rows.append(y)
    assert jnp.abs(jnp.concatenate(rows) - want).max() > 1e-3 * jnp.abs(want).max()


# -- the convolution with a bias ------------------------------------------------------------------------
def _literal_conv(u, tail, w, b):
    xp = np.concatenate([np.asarray(tail, np.float64), np.asarray(u, np.float64)])
    acc = sum(np.asarray(w, np.float64)[j] * xp[j:j + u.shape[0]] for j in range(w.shape[0])) + np.asarray(b, np.float64)
    return acc / (1 + np.exp(-acc))


@pytest.mark.parametrize("rows,valid", [(16, None), (32, 32), (32, 19), (64, 2)])
def test_the_convolution_with_a_bias_is_the_literal_one(interpreted, rows, valid):
    from tony_tpu.ops.delta_rule import short_conv_chunk, short_conv_step

    ks = jax.random.split(jax.random.PRNGKey(rows), 4)
    C, taps = 160, 4
    u, tail = jax.random.normal(ks[0], (rows, C)), jax.random.normal(ks[1], (taps - 1, C))
    w, b = jax.random.normal(ks[2], (taps, C)), jax.random.normal(ks[3], (C,))
    y, kept = short_conv_chunk(u, tail, w, None if valid is None else jnp.int32(valid), b)
    assert np.abs(np.asarray(y) - _literal_conv(u, tail, w, b)).max() < 1e-5
    upto = rows if valid is None else valid
    assert np.array_equal(np.asarray(kept), np.concatenate([np.asarray(tail), np.asarray(u)[:upto]])[-(taps - 1):])
    # a position at a time from the same tail: the step form, slot by slot
    t, out = jnp.stack([tail, tail]), []
    for i in range(4):
        o, t = short_conv_step(jnp.stack([u[i], u[i]]), t, w, b)
        out.append(o[1])
    assert np.abs(np.asarray(jnp.stack(out)) - _literal_conv(u, tail, w, b)[:4]).max() < 1e-5
    assert np.abs(np.asarray(short_conv_chunk(u, tail, w)[0]) - _literal_conv(u, tail, w, 0 * b)).max() < 1e-5   # and without one, as before
