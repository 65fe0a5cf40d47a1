"""The remat ladder (ops/attention.REMAT_LADDER): what each rung saves is what
its replay would have produced, what it costs is reckoned from shapes, and the
train loop's choice of a rung follows the device's limit and the compiler's
report (train/trainer.choose_remat_rung)."""

import dataclasses as dc
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from tony_tpu.models import llama
from tony_tpu.ops import attention as A
from tony_tpu.ops import layers as L
from tony_tpu.train import loop as loop_module
from tony_tpu.train.trainer import REMAT_MARGIN, choose_remat_rung

TINY = dc.replace(llama.LLAMA_TINY, remat=True, max_seq=64)


@functools.cache
def _tiny(dtype="bfloat16"):
    cfg = dc.replace(TINY, dtype=dtype)
    return llama.init(jax.random.PRNGKey(0), cfg), llama.synthetic_batch(jax.random.PRNGKey(1), 2, 64, cfg)


@functools.cache
def _loss_and_grads(policy, dtype):
    params, batch = _tiny(dtype)
    cfg = dc.replace(TINY, remat_policy=policy, dtype=dtype)
    return jax.jit(jax.value_and_grad(lambda p: llama.loss_fn(p, batch, cfg)[0]))(params)


@pytest.mark.parametrize("rung", range(1, len(A.REMAT_LADDER)))
def test_a_rung_saves_what_its_replay_would_have_produced(rung):
    # reference attention: the matmul names are exercised without the Pallas
    # interpreter (the flash names: test_flash_remat_policy_grads_match_full).
    # Exactly equal in float32. In bfloat16 XLA keeps a fusion's intermediates
    # wide (xla_allow_excess_precision), so a value that is saved is rounded
    # where its replay inside a fusion is not: equal to rounding there.
    loss, grads = _loss_and_grads(A.REMAT_LADDER[rung], "float32")
    full_loss, full_grads = _loss_and_grads("full", "float32")
    assert loss == full_loss
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(full_grads)):
        assert jnp.array_equal(got, want)
    loss, grads = _loss_and_grads(A.REMAT_LADDER[rung], "bfloat16")
    full_loss, full_grads = _loss_and_grads("full", "bfloat16")
    assert abs(float(loss) - float(full_loss)) < 1e-3
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(full_grads)):
        assert jnp.allclose(got.astype(jnp.float32), want.astype(jnp.float32), atol=2e-3)


def test_the_ladder_is_cumulative_and_flash_and_full_are_rungs_of_it():
    assert A.REMAT_LADDER[0] == ()
    for lower, higher in zip(A.REMAT_LADDER, A.REMAT_LADDER[1:]):
        assert higher[:len(lower)] == lower and len(higher) > len(lower)
    assert A.REMAT_LADDER[-1][-4:] == ("attn_res", "attn_qkv", "ffn_gate", "ffn_up")
    with pytest.raises(ValueError, match="remat_policy"):
        A.remat_block(lambda c, x: (c, None), True, "flush")


def _lowered_grad_text(policy):
    params, batch = _tiny()
    cfg = dc.replace(TINY, remat_policy=policy)
    return jax.jit(jax.grad(lambda p: llama.loss_fn(p, batch, cfg)[0])).lower(params).as_text()


def test_auto_outside_the_loop_and_the_empty_rung_lower_to_fulls_text(monkeypatch):
    full = _lowered_grad_text("full")
    assert _lowered_grad_text("auto") == full
    assert _lowered_grad_text(()) == full
    assert llama.LlamaConfig().remat_policy == "auto"
    # and a name is an identity: the program with no name in it is the same
    # program (the lowering numbers its private functions, nothing else moves)
    monkeypatch.setattr(llama, "checkpoint_name", lambda x, name: x)
    monkeypatch.setattr(L, "checkpoint_name", lambda x, name: x)
    numbered = functools.partial(re.sub, r"@([A-Za-z_][\w.]*?)_\d+\b", r"@\1")
    assert numbered(_lowered_grad_text("full")) == numbered(full)


def test_saved_bytes_are_reckoned_from_the_named_values_shapes_at_the_cells_shape():
    # mistral-7b.train_8k: 4 layers of 7B widths, 2 x 8192 tokens a chip
    cfg = llama.LlamaConfig(
        vocab_size=32_000, n_layers=4, max_seq=8192, sliding_window=4096,
        attn_impl="flash", remat_policy=A.REMAT_LADDER[-1])
    B, T, D, F, H, Hkv, Dh = 2, 8192, cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    params = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    batch = {"tokens": jax.ShapeDtypeStruct((B, T + 1), jnp.int32)}
    named = A.named_bytes(functools.partial(llama.loss_fn, cfg=cfg), params, batch, has_aux=True)

    def nbytes(*shapes, dtype=cfg.jdtype):
        return sum(s.size * s.dtype.itemsize for s in (jax.eval_shape(lambda: jnp.zeros(sh, dtype)) for sh in shapes))

    a_layer = {name: b // cfg.n_layers for name, b in named.items()}
    assert a_layer == {
        "flash_o": nbytes((B, H, T, Dh)),
        "flash_lse": nbytes((B, H, T), dtype=jnp.float32),  # one lane of the kernel's, not its padded eight
        "attn_res": nbytes((B, T, D)),
        "attn_qkv": nbytes((B, H, T, Dh), (B, Hkv, T, Dh), (B, Hkv, T, Dh)),
        "ffn_gate": nbytes((B, T, F)),
        "ffn_up": nbytes((B, T, F)),
    }
    steps = [sum(a_layer.get(n, 0) for n in rung) for rung in A.REMAT_LADDER]
    assert [round((b - a) / 1e6) for a, b in zip(steps, steps[1:])] == [136, 134, 201, 470, 470]


GB = 10 ** 9
SAVED = [0, 1 * GB, 2 * GB, 4 * GB]
LIMIT = 16 * GB
BUDGET = int(LIMIT * (1 - REMAT_MARGIN))


def _compiler(held_really, fails=()):
    """A made-up compiler: rung i's step peaks at held_really + SAVED[i]."""
    calls = []

    def compile_rung(i):
        calls.append(i)
        if i in fails:
            raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm")
        return f"step@{i}", held_really + SAVED[i]

    return compile_rung, calls


@pytest.mark.parametrize("case, held, really, fails, limit, want, tried", [
    ("fits: the top rung, one compile", 8 * GB, 8 * GB, (), LIMIT, 3, [3]),
    ("a report over the limit: one rung down", 10 * GB, 12 * GB, (), LIMIT, 2, [3, 2]),
    ("a compile out of memory: one rung down", 10 * GB, 10 * GB, (3,), LIMIT, 2, [3, 2]),
    ("a report with room: up to what it leaves room for", 14 * GB, 10 * GB, (), LIMIT, 3, [1, 3]),
    ("room by the report, none by the compile: back to what fitted", 14 * GB, 10 * GB, (3,), LIMIT, 2, [1, 3, 2]),
    ("nothing fits: rung 0 runs all the same", 20 * GB, 20 * GB, (), LIMIT, 0, [0]),
    ("no bytes_limit: full, nothing compiled", 8 * GB, 8 * GB, (), None, 0, []),
])
def test_the_chooser_on_made_up_numbers(case, held, really, fails, limit, want, tried):
    compile_rung, calls = _compiler(really, fails)
    rung, executable, why = choose_remat_rung(SAVED, limit, held, compile_rung)
    assert (rung, calls) == (want, tried), why
    assert executable == (f"step@{want}" if limit else None)
    # the same inputs, the same rung: every process of a gang chooses alike
    again, _ = _compiler(really, fails)
    assert choose_remat_rung(SAVED, limit, held, again)[::2] == (rung, why)


def test_the_chooser_hands_on_what_is_not_a_lack_of_memory():
    def compile_rung(i):
        raise jax.errors.JaxRuntimeError("INTERNAL: Mosaic failed to compile")

    with pytest.raises(jax.errors.JaxRuntimeError, match="Mosaic"):
        choose_remat_rung(SAVED, LIMIT, 8 * GB, compile_rung)


class _Device:
    def __init__(self, limit):
        self.limit = limit

    def memory_stats(self):
        return {"bytes_limit": self.limit} if self.limit else None


_LOOP = loop_module.LoopConfig(steps=3, batch_size=4, seq_len=64, log_every=3, prefetch_depth=0)


@functools.cache
def _trained_at_full():
    return loop_module._run_lm_training(llama, dc.replace(TINY, remat_policy="full"), _LOOP, None)


@pytest.mark.parametrize("limit, says", [
    (None, "saves nothing (0.00 GB a device, 0.00 GB free before, rung 0 of 5; the device reports no bytes_limit)"),
    (10 ** 12, "the top rung fits"),
])
def test_the_loop_trains_the_same_at_the_rung_it_chooses(monkeypatch, limit, says):
    # the loop's own path: LLAMA_TINY with remat on and the default policy
    # ("auto"), on a device that does or does not report a limit
    assert TINY.remat_policy == "auto"
    said = []
    monkeypatch.setattr(loop_module.obs_logging, "info", lambda msg, **kw: said.append(msg))
    monkeypatch.setattr(loop_module.jax, "local_devices", lambda: [_Device(limit)])
    got = loop_module._run_lm_training(llama, TINY, _LOOP, None)
    line = [m for m in said if m.startswith("[train] remat: ")]
    assert len(line) == 1 and says in line[0], said
    assert (loop_module._REMAT_SAVED_BYTES.value() > 0) == bool(limit)
    want = _trained_at_full()
    assert got["loss"] == want["loss"] and got["grad_norm"] == want["grad_norm"]
