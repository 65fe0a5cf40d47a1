"""The state-space scan with B and C in GROUPS (`ops/ssd.py`) on the CPU under the interpreter: the chunk and
step forms against the position-at-a-time recurrence, a head reading its own group's B and C, what is
refused. No model is built here: the family's program is held to its reference in tests/test_falcon_h1.py,
its files to the harness in tests/test_falcon_h1_family.py (one subject a file, so that `--dist loadfile`
can run them side by side).

Tolerances. The chunk form differs from the recurrence in the order of its sums and in taking a decay as
exp of a difference of running sums: 2e-5 of the largest output, as for one group
(tests/test_granite_hybrid_scan.py; 4e-7 seen); the step form is the recurrence's own arithmetic.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


# -- the scan with B/C groups: chunk and step against the recurrence a position at a time -----------
def _scan_inputs(seed, T, H, P, N, G, decay=(-7.0, -3.0)):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (T, H, P))
    B, C = jax.random.normal(ks[1], (T, G, N)), jax.random.normal(ks[2], (T, G, N))
    dt = jnp.exp(jax.random.uniform(ks[3], (T, H), minval=np.log(0.001), maxval=np.log(0.5)))
    g = -jnp.exp(jax.random.uniform(ks[4], (T, H), minval=decay[0], maxval=decay[1]))
    return x, dt, g, B, C, jax.random.normal(ks[5], (H,)), jax.random.normal(ks[6], (H, P, N))


#: (T, heads, head width, state, groups, block)
GROUPED = {"two-groups-of-two": (64, 4, 8, 16, 2, 16), "heads-of-128-over-256": (32, 4, 128, 256, 2, 16),
           "the-published-heads-and-groups": (16, 32, 128, 256, 2, 16), "three-groups-of-an-odd-count": (48, 9, 8, 16, 3, 16),
           "a-group-a-head": (32, 4, 32, 16, 4, 16)}
DECAYS = {"random-decays": (-7.0, -3.0), "every-rate-at-once": (-12.0, 5.0)}


def _close(got, want, state, new, ssd):
    return jnp.abs(got - want).max() < 2e-5 * jnp.abs(want).max() and jnp.abs(new - ssd.lanes(state)).max() < 2e-5 * jnp.abs(state).max()


@pytest.mark.parametrize("decay", list(DECAYS))
@pytest.mark.parametrize("shape", list(GROUPED))
def test_the_grouped_chunk_form_is_the_recurrence(interpreted, shape, decay):
    from tony_tpu.ops import ssd

    T, H, P, N, G, block = GROUPED[shape]
    args = _scan_inputs(1, T, H, P, N, G, DECAYS[decay])
    want, state = ssd.ssd_scan(*args)
    got, new = ssd.ssd_chunk(*args[:6], ssd.lanes(args[6]), block=block)
    assert _close(got, want, state, new, ssd)


@pytest.mark.parametrize("valid", [1, 19, 32, 45])
def test_a_padded_grouped_chunks_state_stops_at_valid(interpreted, valid):
    """The padded last chunk of a prompt, at a state of 256 and heads of 128 in two groups."""
    from tony_tpu.ops import ssd

    args = _scan_inputs(2, 48, 4, 128, 256, 2, (-4.0, 2.0))
    want, state = ssd.ssd_scan(*(a[:valid] for a in args[:5]), *args[5:])
    got, new = ssd.ssd_chunk(*args[:6], ssd.lanes(args[6]), jnp.int32(valid), block=16)
    assert _close(got[:valid], want, state, new, ssd)


@pytest.mark.parametrize("shape", list(GROUPED))
def test_the_grouped_step_form_is_the_recurrence(interpreted, shape):
    """One position a slot, each slot its own state: `ssd_scan` over one position."""
    from tony_tpu.ops import ssd

    _, H, P, N, G, _ = GROUPED[shape]
    S = 3
    x, dt, g, B, C, D, _ = _scan_inputs(4, S, H, P, N, G)
    state = jax.random.normal(jax.random.PRNGKey(9), (S, H, P, N))
    got, new = ssd.ssd_step(x, dt, g, B, C, D, ssd.lanes(state))
    for s in range(S):
        want, after = ssd.ssd_scan(x[s:s + 1], dt[s:s + 1], g[s:s + 1], B[s:s + 1], C[s:s + 1], D, state[s])
        assert jnp.abs(got[s] - want[0]).max() < 1e-6 * jnp.abs(want).max() and jnp.abs(new[s] - ssd.lanes(after)).max() < 1e-6


@pytest.mark.parametrize("form", ["scan", "chunk", "step"])
def test_a_head_reads_its_own_groups_b_and_c(interpreted, form):
    """Head h of H reads pair h // (H / G): heads and groups mirrored together give the mirrored result, the
    groups mirrored alone another one, and one pair given twice is the one-group call."""
    from tony_tpu.ops import ssd

    T, H, P, N = 16, 4, 8, 16
    x, dt, g, B, C, D, state = _scan_inputs(6, T, H, P, N, 2)

    def run(x, dt, g, D, state, B, C):
        if form == "scan":
            return ssd.ssd_scan(x, dt, g, B, C, D, state)[0]
        if form == "chunk":
            return ssd.ssd_chunk(x, dt, g, B, C, D, ssd.lanes(state), block=16)[0]
        return ssd.ssd_step(x, dt, g, B, C, D, jnp.broadcast_to(ssd.lanes(state), (T, N, H * P)))[0]    # every row a slot from the one state

    heads = (x, dt, g, D, state)
    got = run(*heads, B, C)
    mirrored = run(x[:, ::-1], dt[:, ::-1], g[:, ::-1], D[::-1], state[::-1], B[:, ::-1], C[:, ::-1])
    assert jnp.abs(mirrored[:, ::-1] - got).max() < 2e-5 * jnp.abs(got).max()
    assert jnp.abs(run(*heads, B[:, ::-1], C[:, ::-1]) - got).max() > 0.1 * jnp.abs(got).max()
    twice = lambda a: jnp.stack([a[:, 0], a[:, 0]], axis=1)
    assert jnp.abs(run(*heads, twice(B), twice(C)) - run(*heads, B[:, 0], C[:, 0])).max() < 2e-5 * jnp.abs(got).max()


def test_groups_that_do_not_divide_the_heads_are_refused(interpreted):
    from tony_tpu.ops import ssd

    x, dt, g, B, C, D, state = _scan_inputs(7, 16, 4, 8, 16, 3)
    with pytest.raises(ValueError, match="3 groups"):
        ssd.ssd_chunk(x, dt, g, B, C, D, ssd.lanes(state))
    with pytest.raises(ValueError, match="3 groups"):
        ssd.ssd_step(x, dt, g, B, C, D, jnp.broadcast_to(ssd.lanes(state), (16, 16, 32)))


@pytest.mark.parametrize("H,P,N,G,heads", [(128, 64, 128, 1, 16), (32, 128, 256, 2, 16), (4, 32, 16, 2, 2), (9, 8, 16, 3, 3), (4, 8, 16, 1, 4)],
                         ids=["granite-as-it-was", "falcon-h1-34b", "tiny-falcon-h1", "an-odd-count-in-three-groups", "all-heads-in-one-program"])
def test_a_programs_heads_lie_in_one_group(H, P, N, G, heads):
    """What a program of `ssd_chunk` holds: whole heads of ONE B/C group (its heads divide a group's)."""
    from tony_tpu.ops import ssd

    hb = ssd._group(H // G, P, ssd.HEADS)
    assert hb == heads and (H // G) % hb == 0
