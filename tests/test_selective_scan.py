"""The selective scan's kernels (ops/selective_scan.py) under the interpreter against the literal recurrence, a
`lax.scan` a position: a decay that is a channel's AND a state index's, walked inside the kernel with the state in
`[N, E]` lanes. The family that runs them is tests/test_phi4_flash.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.ops import selective_scan as SS


@pytest.fixture(scope="module", autouse=True)
def _interpreted(interpreted):
    pass


def _inputs(seed, T, E, N, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (T, E), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (T, E), jnp.float32) - 3.0)
    A = -jnp.exp(jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (E, N)) + 0.1 * jax.random.normal(ks[2], (E, N)))
    B, C = (jax.random.normal(k, (T, N), jnp.float32).astype(dtype) for k in ks[3:5])
    return x, dt, A, B, C, 1.0 + 0.1 * jax.random.normal(ks[5], (E,), jnp.float32)


@pytest.mark.parametrize("T,E,block", [(64, 256, 32), (32, 128, 256), (48, 640, 16), (16, 96, 8)],
                         ids=["two-blocks", "one-short-block", "a-span-of-128-lanes-five-times", "channels-that-are-no-tile"])
def test_the_chunk_form_is_the_literal_recurrence(T, E, block):
    N = 16
    x, dt, A, B, C, D = _inputs(1, T, E, N)
    state = jax.random.normal(jax.random.PRNGKey(9), (E, N), jnp.float32)          # a carried state, not zeros
    want_y, want_s = SS.selective_scan(x, dt, A, B, C, D, state)
    y, s = SS.selective_chunk(x, dt, A.T, B, C, D, state.T, block=block)
    assert y.dtype == jnp.float32 and s.shape == (N, E)
    assert np.abs(np.asarray(y) - np.asarray(want_y)).max() < 2e-5 * np.abs(np.asarray(want_y)).max()
    assert np.abs(np.asarray(s).T - np.asarray(want_s)).max() < 2e-5 * np.abs(np.asarray(want_s)).max()


@pytest.mark.parametrize("valid", [1, 19, 32, 45], ids=["one-row", "inside-the-first-block", "a-whole-block", "inside-the-second-block"])
def test_a_padded_last_chunk_stops_the_state_at_its_last_real_row(valid):
    """Positions past `valid` neither decay nor write: the state is the literal recurrence's after `valid` rows,
    and the rows before `valid` read as they do unpadded."""
    T, E, N = 64, 256, 16
    x, dt, A, B, C, D = _inputs(2, T, E, N)
    state = jax.random.normal(jax.random.PRNGKey(8), (E, N), jnp.float32)
    want_y, want_s = SS.selective_scan(x[:valid], dt[:valid], A, B[:valid], C[:valid], D, state)
    y, s = SS.selective_chunk(x, dt, A.T, B, C, D, state.T, jnp.int32(valid), block=32)
    assert np.abs(np.asarray(y)[:valid] - np.asarray(want_y)).max() < 2e-5 * np.abs(np.asarray(want_y)).max()
    assert np.abs(np.asarray(s).T - np.asarray(want_s)).max() < 2e-5 * np.abs(np.asarray(want_s)).max()


def test_two_chunks_carry_the_state_between_them():
    T, E, N = 64, 128, 16
    x, dt, A, B, C, D = _inputs(3, T, E, N)
    zero = jnp.zeros((N, E), jnp.float32)
    whole_y, whole_s = SS.selective_chunk(x, dt, A.T, B, C, D, zero, block=32)
    y0, s0 = SS.selective_chunk(x[:32], dt[:32], A.T, B[:32], C[:32], D, zero, block=32)
    y1, s1 = SS.selective_chunk(x[32:], dt[32:], A.T, B[32:], C[32:], D, s0, block=32)
    assert np.abs(np.concatenate([np.asarray(y0), np.asarray(y1)]) - np.asarray(whole_y)).max() < 1e-5
    assert np.abs(np.asarray(s1) - np.asarray(whole_s)).max() < 1e-5


def test_bfloat16_inputs_give_a_float32_output_and_state():
    T, E, N = 32, 128, 16
    x, dt, A, B, C, D = _inputs(4, T, E, N, jnp.bfloat16)
    want_y, want_s = SS.selective_scan(x, dt, A, B, C, D, jnp.zeros((E, N)))
    y, s = SS.selective_chunk(x, dt, A.T, B, C, D, jnp.zeros((N, E)), block=16)
    assert y.dtype == s.dtype == jnp.float32
    assert np.abs(np.asarray(y) - np.asarray(want_y)).max() < 2e-5 * np.abs(np.asarray(want_y)).max()
    assert np.abs(np.asarray(s).T - np.asarray(want_s)).max() < 2e-5


@pytest.mark.parametrize("S,E", [(3, 256), (2, 640), (4, 96)], ids=["a-span", "five-spans-of-128", "channels-that-are-no-tile"])
def test_the_step_form_is_one_position_of_the_recurrence_a_slot(S, E):
    N = 16
    x, dt, A, B, C, D = _inputs(5, S, E, N)
    state = jax.random.normal(jax.random.PRNGKey(7), (S, E, N), jnp.float32)
    want = [SS.selective_scan(x[s:s + 1], dt[s:s + 1], A, B[s:s + 1], C[s:s + 1], D, state[s]) for s in range(S)]
    y, new = SS.selective_step(x, dt, A.T, B, C, D, jnp.swapaxes(state, 1, 2))
    assert y.shape == (S, E) and new.shape == (S, N, E)
    for s, (want_y, want_s) in enumerate(want):
        assert np.abs(np.asarray(y)[s] - np.asarray(want_y)[0]).max() < 1e-5
        assert np.abs(np.asarray(new)[s].T - np.asarray(want_s)).max() < 1e-5


def test_steps_after_a_chunk_continue_it():
    """Prefill then decode: the chunk's state handed to the step form walks on as the literal recurrence does."""
    T, E, N = 40, 128, 16
    x, dt, A, B, C, D = _inputs(6, T, E, N)
    want_y, _ = SS.selective_scan(x, dt, A, B, C, D, jnp.zeros((E, N)))
    _, s = SS.selective_chunk(x[:32], dt[:32], A.T, B[:32], C[:32], D, jnp.zeros((N, E)), block=32)
    state = s[None]
    for t in range(32, T):
        y, state = SS.selective_step(x[t:t + 1], dt[t:t + 1], A.T, B[t:t + 1], C[t:t + 1], D, state)
        assert np.abs(np.asarray(y)[0] - np.asarray(want_y)[t]).max() < 1e-5


def test_a_chunk_that_does_not_divide_into_blocks_is_refused():
    x, dt, A, B, C, D = _inputs(7, 40, 128, 16)
    with pytest.raises(ValueError, match="blocks"):
        SS.selective_chunk(x, dt, A.T, B, C, D, jnp.zeros((16, 128)), block=32)


def test_nothing_of_the_size_of_the_staged_scan_is_made():
    """What the kernel is for: no array of T x E x N exists in the jitted chunk form (an associative scan's would)."""
    T, E, N = 64, 256, 16
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in ((T, E), (T, E), (N, E), (T, N), (T, N), (E,), (N, E))]
    jaxpr = jax.make_jaxpr(lambda *a: SS.selective_chunk(*a, block=32))(*args)
    sizes = [np.prod(v.aval.shape) for eqn in jaxpr.jaxpr.eqns for v in eqn.outvars if hasattr(v.aval, "shape")]
    assert max(sizes) < T * E * N
