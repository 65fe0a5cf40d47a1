"""The channel-gated delta rule's chunk form (`ops/kda.kda_chunk`) at a chunk's edges and across its programs,
under the interpreter: the heads a program holds, a program of several heads against its heads one at a
time, a padded chunk, a chunk boundary inside a prompt. That the chunk and step forms ARE the recurrence,
shape by shape and decay by decay, is tests/test_solar_open2_rule.py (its tolerances hold here too: 2e-5 of
the largest output).
"""
import jax.numpy as jnp
import pytest

from test_solar_open2_rule import _close, _rule_inputs


@pytest.mark.parametrize("heads,held", [(1, 1), (6, 3), (13, 1), (30, 3), (64, 4), (128, 4)])
def test_a_program_holds_the_most_heads_that_divide_and_fit(heads, held):
    """`hb` follows the input's shape: the divisors of H under the kernel's bound, and
    the bytes a head's blocks, states and live values take against the chip's VMEM
    (at 384 x 384 a head's states alone are 2.4 MB in the pipeline's buffers: two heads fit, not four)."""
    from tony_tpu.ops import delta_rule

    assert delta_rule._chunk_heads(heads, 64, 128, 128, 2) == held
    assert delta_rule._chunk_heads(heads, 64, 384, 384, 2) == min(held, 2 if heads % 2 == 0 else 1)


@pytest.mark.parametrize("shape", [(4, 128, 16, 32), (3, 64, 8, 8)], ids=["four-heads-of-two-blocks", "three-heads-of-one-block"])
@pytest.mark.parametrize("case", ["strong-beside-weak", "beta-near-2"])
def test_a_program_of_several_heads_is_its_heads_one_at_a_time(interpreted, case, shape):
    """ONE program of all the heads against the same inputs a head at a time (the
    one-head program, the parent's grid): the same operations a head in the same
    order, so the outputs and the states are equal BIT FOR BIT, not within a tolerance."""
    from tony_tpu.ops import kda

    args = _rule_inputs(7, *shape, case)
    got, new = kda.kda_chunk(*args, jnp.int32(shape[1] - 5))
    alone = [kda.kda_chunk(*(a[n:n + 1] for a in args), jnp.int32(shape[1] - 5)) for n in range(shape[0])]
    assert bool((got == jnp.concatenate([o for o, _ in alone])).all()) and bool((new == jnp.concatenate([s for _, s in alone])).all())


@pytest.mark.parametrize("heads", [2, 6], ids=["two-heads-a-program", "two-programs-of-three-heads"])
@pytest.mark.parametrize("case", ["strong-beside-weak", "beta-near-2"])
@pytest.mark.parametrize("valid", [1, 11, 64, 75, 128])
def test_a_padded_chunks_state_stops_at_valid(interpreted, valid, case, heads):
    """Rows past `valid` neither decay nor write: the state is the recurrence's
    after `valid` positions, and the rows before it read what they read unpadded."""
    from tony_tpu.ops import kda

    args = _rule_inputs(2, heads, 128, 16, 32, case)
    want, state = kda.kda_scan(*(a[:, :valid] for a in args[:5]), args[5])
    got, new = kda.kda_chunk(*args, jnp.int32(valid))
    assert _close(got[:, :valid], want) and _close(new, state)


@pytest.mark.parametrize("heads", [2, 6], ids=["two-heads-a-program", "two-programs-of-three-heads"])
@pytest.mark.parametrize("case", ["strong-beside-weak", "every-rate-at-once"])
@pytest.mark.parametrize("cut", [64, 128])
def test_a_chunk_boundary_inside_a_prompt_carries_the_state(interpreted, cut, case, heads):
    """Two chunks, the second from the first's state: the one recurrence."""
    from tony_tpu.ops import kda

    args = _rule_inputs(3, heads, 192, 16, 32, case)
    want, state = kda.kda_scan(*args)
    first, mid = kda.kda_chunk(*(a[:, :cut] for a in args[:5]), args[5])
    second, new = kda.kda_chunk(*(a[:, cut:] for a in args[:5]), mid)
    assert _close(jnp.concatenate([first, second], axis=1), want) and _close(new, state)
