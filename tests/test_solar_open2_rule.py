"""The channel-gated delta rule (`ops/kda.py`) on the CPU under the interpreter: the chunk and step forms
against the position-at-a-time recurrence, shape by shape and decay by decay (a chunk's padding, a prompt's
chunk boundaries and a program of several heads are tests/test_solar_open2_rule_programs.py).
No model is built here: the family's program is held to its reference in tests/test_solar_open2.py, its
files to the harness in tests/test_solar_open2_family.py (one subject a file, so that `--dist loadfile`
can run them side by side).

Tolerances. The chunk form differs from the recurrence in the order of its sums and in splitting a pair's
decay in two factors: 2e-5 of the largest output is ten times what was seen (1.5e-6 at most, whatever the
decays and with beta at 2); the step form is the recurrence's own arithmetic (1e-6 of a state of size 4).
A state kept in bfloat16 moves the same outputs by 1e-3 and fails.
"""
import jax
import jax.numpy as jnp
import pytest


# -- the rule: chunk and step against the recurrence a position at a time ---------------------------
def _rule_inputs(seed, H, T, dk, dv, case, dtype=jnp.float32):
    """q, k as the program makes them (SiLU outputs, L2-normed, q scaled), v SiLU outputs, a state of unit scale."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    l2 = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = (l2(jax.nn.silu(jax.random.normal(ks[0], (H, T, dk)))) * dk ** -0.5).astype(dtype)
    k = l2(jax.nn.silu(jax.random.normal(ks[1], (H, T, dk)))).astype(dtype)
    v = jax.nn.silu(jax.random.normal(ks[2], (H, T, dv))).astype(dtype)
    if case == "strong-beside-weak":
        # -20 a position on the even channels, -0.01 on their neighbours: exp(-G_j) would overflow after four positions
        g = jnp.where((jnp.arange(dk) % 2 == 0)[None, None, :], -20.0, -0.01) * jax.random.uniform(ks[3], (H, T, dk), minval=0.9, maxval=1.1)
    else:
        # "every-rate-at-once": a channel's own rate changes from position to position, -25 here, -0.0009 there
        low, high = (-7.0, 3.2) if case == "every-rate-at-once" else (-7.0, -3.0)
        g = -jnp.exp(jax.random.uniform(ks[3], (H, T, dk), minval=low, maxval=high))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (H, T)) + (5.0 if case == "beta-near-2" else 0.0))
    return q, k, v, g, beta, jax.random.normal(ks[5], (H, dk, dv))


RULE_CASES = ["weak-forgetting", "strong-beside-weak", "every-rate-at-once", "beta-near-2"]
#: heads, positions, d_k, d_v. A program of the blocked rule holds the most heads up to `delta_rule.CHUNK_HEADS` (4) that divide
#: H: 1, 2, 3 (six heads: two programs), ONE of thirteen (a prime over the bound: the one-head program), 3 of thirty, 4 of 64
SHAPES = {"two-blocks": (2, 128, 16, 32), "the-served-head": (1, 64, 128, 128), "a-short-block": (3, 16, 8, 8),
          "six-heads-in-two-programs": (6, 128, 8, 16), "thirteen-heads-a-program-each": (13, 64, 8, 8),
          "thirty-heads-by-three": (30, 32, 8, 8), "sixty-four-heads-by-four": (64, 32, 8, 8)}


def _close(got, want, tol=2e-5):
    return float(jnp.abs(got - want).max()) < tol * float(jnp.abs(want).max())


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("case", RULE_CASES)
def test_the_chunk_form_is_the_recurrence(interpreted, case, shape):
    from tony_tpu.ops import kda

    args = _rule_inputs(1, *SHAPES[shape], case)
    want, state = kda.kda_scan(*args)
    got, new = kda.kda_chunk(*args)
    assert _close(got, want) and _close(new, state) and bool(jnp.isfinite(got).all())


@pytest.mark.parametrize("heads", [4, 64], ids=["four-heads-a-program", "two-programs-of-32-heads"])
@pytest.mark.parametrize("case", RULE_CASES)
def test_the_step_form_is_the_recurrence(interpreted, case, heads):
    """One position a slot, each slot its own state: `kda_scan` over one position."""
    from tony_tpu.ops import kda

    S, dk, dv = 3, 16, 32
    q, k, v, g, beta, _ = _rule_inputs(4, S, heads, dk, dv, case)       # [S, H, ...]: a slot where a head's positions were
    state = jax.random.normal(jax.random.PRNGKey(9), (S, heads, dk, dv))
    got, new = kda.kda_step(q, k, v, g, beta, state)
    for s in range(S):
        want, after = kda.kda_scan(*(a[s][:, None] for a in (q, k, v, g, beta)), state[s])
        assert float(jnp.abs(got[s] - want[:, 0]).max()) < 1e-6 * float(jnp.abs(state).max()) and float(jnp.abs(new[s] - after).max()) < 2e-6


def test_a_bfloat16_state_fails_the_tolerance(interpreted):
    """What the float32 state is for: the same chunks from a state rounded to
    bfloat16 at every chunk's edge lie fifty tolerances away."""
    from tony_tpu.ops import kda

    args = _rule_inputs(5, 2, 256, 16, 32, "weak-forgetting")
    want, _ = kda.kda_scan(*args)
    state, rows = args[5], []
    for at in range(0, 256, 64):
        o, state = kda.kda_chunk(*(a[:, at:at + 64] for a in args[:5]), state.astype(jnp.bfloat16).astype(jnp.float32))
        rows.append(o)
    assert float(jnp.abs(jnp.concatenate(rows, axis=1) - want).max()) > 1e-3 * float(jnp.abs(want).max())


def test_a_chunk_that_is_no_power_of_two_is_refused(interpreted):
    from tony_tpu.ops import kda

    with pytest.raises(ValueError, match="power of two"):
        kda.kda_chunk(*_rule_inputs(6, 1, 48, 8, 8, "weak-forgetting"))
