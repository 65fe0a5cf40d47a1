"""The remat ladder (ops/attention.REMAT_LADDER): what each rung saves is what
its replay would have produced, what it costs is reckoned from shapes, and the
train loop's choice of a rung follows the device's limit and the compiler's
report (train/trainer.choose_remat_rung). Since PR 56 a rung can name values
that wait in the host's pinned memory (ops/attention.Rung.host, scan_blocks)
and the ladder the chooser climbs has such rungs (trainer.remat_candidates)."""

import dataclasses as dc
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.ad_checkpoint import checkpoint_name

from tony_tpu.models import llama
from tony_tpu.parallel import MeshSpec
from tony_tpu.ops import attention as A
from tony_tpu.ops import layers as L
from tony_tpu.train import loop as loop_module
from tony_tpu.train.trainer import (
    HOST_LINK_BYTES_PER_S, HOST_LINK_SHARE, REMAT_MARGIN, choose_remat_rung, remat_candidates)

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


@pytest.mark.parametrize("case, policy", [
    ("q, k and v wait on the host beside flash's outputs", A.Rung(A.REMAT_LADDER[1], host=("attn_qkv",))),
    ("q, k and v wait on the host under the top rung", A.Rung(tuple(n for n in A.REMAT_LADDER[-1] if n != "attn_qkv"), host=("attn_qkv",))),
    ("the gate product waits on the host", A.Rung(A.REMAT_LADDER[3], host=("ffn_gate",))),
    ("values of three widths wait on the host", A.Rung((), host=("attn_qkv", "attn_res", "ffn_up"))),
    ("everything the ladder names waits on the host", A.Rung((), host=A.REMAT_LADDER[-1])),
    ("a host part of names the block does not hold", A.Rung(A.REMAT_LADDER[2], host=("moe_gemm",))),
])
def test_a_rung_with_a_host_part_trains_as_full_does(case, policy):
    # a value read back from the host is the value its replay would have
    # produced: float32, to the last bit
    loss, grads = _loss_and_grads(policy, "float32")
    full_loss, full_grads = _loss_and_grads("full", "float32")
    assert loss == full_loss
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(full_grads)):
        assert jnp.array_equal(got, want)


def test_the_moe_family_takes_a_host_part_too():
    # mixtral.hidden_states runs the same scan_blocks, its carry a pair (the
    # stream and the routers' sums): float32, to the last bit
    from tony_tpu.models import mixtral

    cfg = dc.replace(mixtral.MIXTRAL_TINY, remat=True, dtype="float32")
    params = mixtral.init(jax.random.PRNGKey(0), cfg)
    batch = mixtral.synthetic_batch(jax.random.PRNGKey(1), 2, 32, cfg)

    def loss_and_grads(policy):
        at = dc.replace(cfg, remat_policy=policy)
        return jax.jit(jax.value_and_grad(lambda p: mixtral.loss_fn(p, batch, at)[0]))(params)

    (loss, grads), (full_loss, full_grads) = loss_and_grads(A.Rung(A.REMAT_LADDER[1], A.HOST_NAMES)), loss_and_grads("full")
    assert loss == full_loss
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(full_grads)):
        assert jnp.array_equal(got, want)


def _block_program(policy):
    """(lowered text, jaxpr) of the gradient of three toy blocks under ``policy``."""
    def block(x, w):
        return jnp.tanh(checkpoint_name(x @ w, "ffn_gate")) @ w.T, None

    def loss(x, ws):
        return A.scan_blocks(block, x, ws, True, policy)[0].sum()

    grad, args = jax.grad(loss, argnums=1), (jnp.ones((4, 8)), jnp.ones((3, 8, 8)))
    return jax.jit(grad).lower(*args).as_text(), str(jax.make_jaxpr(grad)(*args))


@pytest.mark.parametrize("case, policy, same_as, host", [
    ("a tuple of names builds what it built", ("ffn_gate",), ("ffn_gate",), False),
    ("a Rung of the same names and no more builds the same", A.Rung(("ffn_gate",)), ("ffn_gate",), False),
    ("an empty Rung is full", A.Rung(()), "full", False),
    ("a host part keeps the value in the host's memory", A.Rung((), host=("ffn_gate",)), None, True),
    ("a host part beside names the block does not hold", A.Rung(("attn_res",), host=("ffn_gate",)), None, True),
    ("a host part of a name the block does not hold keeps nothing there", A.Rung(("ffn_gate",), host=("attn_qkv",)), None, False),
])
def test_what_a_rung_builds(case, policy, same_as, host):
    text, jaxpr = _block_program(policy)
    if same_as is not None:
        assert text == _block_program(same_as)[0]
    # the value kept for the backward lives in the host's memory space, all
    # layers' of it, and comes back a layer at a time
    assert ("f32<host>[3,4,8]" in jaxpr) == host
    assert ("f32<host>[4,8]" in jaxpr) == host


@pytest.mark.parametrize("remat", [True, False])
@pytest.mark.parametrize("policy", [(), A.Rung((), host=("ffn_gate",))])
def test_blocks_hand_back_what_a_scan_hands_back(remat, policy):
    # carry and stacked ys, as jax.lax.scan gives them, a tree of them too,
    # and their gradients
    def block(x, w):
        x = jnp.tanh(checkpoint_name(x @ w, "ffn_gate"))
        return x, {"mean": x.mean(), "row": x[0]}

    def loss(x, ws, scan):
        x, ys = scan(x, ws)
        return x.sum() + ys["mean"].sum() + (ys["row"] ** 2).sum()

    x, ws = jnp.ones((4, 8)), jnp.linspace(-1, 1, 3 * 8 * 8).reshape(3, 8, 8)
    ours = functools.partial(A.scan_blocks, block, remat=remat, policy=policy)
    theirs = functools.partial(jax.lax.scan, block)
    got, want = ours(x, ws), theirs(x, ws)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and jnp.array_equal(g, w)
    for g, w in zip(jax.grad(loss, (0, 1))(x, ws, ours), jax.grad(loss, (0, 1))(x, ws, theirs)):
        assert jnp.allclose(g, w, rtol=1e-6, atol=1e-6)


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


@pytest.mark.parametrize("really, fails, want", [
    (8 * GB, (), 3), (11 * GB, (), 3), (12 * GB, (), 2), (13 * GB, (), 2), (14 * GB, (), 1), (10 * GB, (3,), 2),
])
@pytest.mark.parametrize("first_guess", [0, 1, 2, 3])
def test_the_chooser_reaches_the_same_rung_from_any_first_guess(first_guess, really, fails, want):
    # the first reckoning only decides what is compiled first: the report
    # decides where the climb ends (a guess that leans high costs a load
    # from the cache, never the rung)
    held = BUDGET - SAVED[first_guess]
    compile_rung, calls = _compiler(really, fails)
    rung, _, why = choose_remat_rung(SAVED, LIMIT, held, compile_rung)
    assert calls[0] == first_guess and rung == want, (calls, why)


#: mistral-7b.train_8k's names, bytes a device and step over its 4 layers
NAMED = {"flash_o": 537 * 10 ** 6, "flash_lse": 8 * 10 ** 6, "attn_res": 537 * 10 ** 6,
         "attn_qkv": 805 * 10 ** 6, "ffn_gate": 1879 * 10 ** 6, "ffn_up": 1879 * 10 ** 6}
TODAY = [(rung, (), sum(NAMED.get(n, 0) for n in rung)) for rung in A.REMAT_LADDER]
STEP_S = 0.55  # its flops a device over the peak


def _without(rung, *names):
    return tuple(n for n in rung if n not in names)


@pytest.mark.parametrize("case, named, host_names, step_seconds, want", [
    ("no name may wait on the host: today's rungs", NAMED, (), STEP_S, TODAY),
    ("no peak to reckon a step with: today's rungs", NAMED, A.HOST_NAMES, None, TODAY),
    ("the link refuses: the bytes take more than their share of the step", NAMED, A.HOST_NAMES,
     NAMED["attn_qkv"] / HOST_LINK_BYTES_PER_S / HOST_LINK_SHARE * 0.99, TODAY),
    ("the FFN's products together are refused at the step that q, k and v fit under", NAMED,
     ("ffn_gate", "ffn_up"), STEP_S, TODAY),
    ("a family whose block does not hold the name: its rungs", {"flash_o": 5, "ffn_up": 7}, A.HOST_NAMES, STEP_S,
     [((), (), 0), (A.REMAT_LADDER[1], (), 5), (A.REMAT_LADDER[5], (), 12)]),
    ("a block that names nothing: one rung", {}, A.HOST_NAMES, STEP_S, TODAY[:1]),
])
def test_the_ladder_without_a_host_part_is_todays(case, named, host_names, step_seconds, want):
    assert remat_candidates(A.REMAT_LADDER, named, 4, host_names, step_seconds) == want


def test_the_ladder_with_its_host_parts():
    got = remat_candidates(A.REMAT_LADDER, NAMED, 4, A.HOST_NAMES, STEP_S)
    in_flight = NAMED["attn_qkv"] // 4
    # every rung of today's stands, in order, and bytes rise all the way up
    assert [t for t in got if not t[1]] == TODAY
    assert [b for _, _, b in got] == sorted(b for _, _, b in got)
    # below each, the same names with q, k and v on the host for a layer's bytes
    # (bound to no place on the ladder: under rungs that do not reach them too)
    for rung, _, held in TODAY:
        saved = _without(rung, "attn_qkv")
        assert (saved, ("attn_qkv",), sum(NAMED.get(n, 0) for n in saved) + in_flight) in got
    assert len(got) == len(TODAY) + 5  # rungs 2 and 3 keep the same on the host
    # what train_8k reaches: rung 4's names, q, k and v on the host, under rung 4
    at = got.index(TODAY[4])
    assert got[at - 1] == (_without(A.REMAT_LADDER[4], "attn_qkv"), ("attn_qkv",), TODAY[4][2] - NAMED["attn_qkv"] + in_flight)
    assert got[at - 2] == TODAY[3]


def _ladder_compiler(ladder, held_really):
    calls = []

    def compile_rung(i):
        calls.append(i)
        return ladder[i][:2], held_really + ladder[i][2]

    return compile_rung, calls


@pytest.mark.parametrize("case, host_names, step_seconds, want", [
    ("the device refuses rung 4 and the link allows: rung 4's names with q, k and v on the host",
     A.HOST_NAMES, STEP_S, (_without(A.REMAT_LADDER[4], "attn_qkv"), ("attn_qkv",))),
    ("the link's reckoning refuses: today's rung", A.HOST_NAMES, STEP_S / 10, (A.REMAT_LADDER[3], ())),
    ("no pinned_host: today's rung", (), STEP_S, (A.REMAT_LADDER[3], ())),
])
@pytest.mark.parametrize("first_guess", ["low", "right", "high"])
def test_the_chooser_on_train_8ks_numbers(case, host_names, step_seconds, want, first_guess):
    # 12.49 GB at rung 0 by the compiler's report, 15.89 GB of budget: rung 4
    # (16.26) does not fit, rung 4 less q, k and v (15.66) does
    ladder = remat_candidates(A.REMAT_LADDER, NAMED, 4, host_names, step_seconds)
    limit, really = 16_900 * 10 ** 6, 12_490 * 10 ** 6
    held = {"low": 15 * GB, "right": really + 100 * 10 ** 6, "high": 8 * GB}[first_guess]
    compile_rung, calls = _ladder_compiler(ladder, really)
    rung, executable, why = choose_remat_rung([b for _, _, b in ladder], limit, held, compile_rung)
    assert executable == want == ladder[rung][:2], why
    # the first rung tried is the one that runs where the reckoning can tell
    assert first_guess != "right" or calls == [rung]


def test_the_chooser_hands_on_what_is_not_a_lack_of_memory():
    def compile_rung(i):
        raise jax.errors.JaxRuntimeError("INTERNAL: Mosaic failed to compile")

    with pytest.raises(jax.errors.JaxRuntimeError, match="Mosaic"):
        choose_remat_rung(SAVED, LIMIT, 8 * GB, compile_rung)


class _Memory:
    def __init__(self, kind):
        self.kind = kind


class _Device:
    def __init__(self, limit, memories=("device", "pinned_host", "unpinned_host")):
        self.limit, self.memories = limit, memories

    def memory_stats(self):
        return {"bytes_limit": self.limit} if self.limit else None

    def addressable_memories(self):
        return [_Memory(kind) for kind in self.memories]


_LOOP = loop_module.LoopConfig(steps=3, batch_size=4, seq_len=64, log_every=3, prefetch_depth=0)


@functools.cache
def _trained_at_full():
    return loop_module._run_lm_training(llama, dc.replace(TINY, remat_policy="full"), _LOOP, None)


def _first_rung_with_a_host_part(saved, limit, held, compile_rung):
    # nothing saved, q, k and v on the host: rung 2 of TINY's ladder (a layer
    # of its two holds as much of them as both hold of rung 1's attn_res)
    return 2, compile_rung(2)[0], "forced"


@pytest.mark.parametrize("case, device, mesh, chooser, says, on_host", [
    ("no limit", _Device(None), None, None,
     "saves nothing (0.00 GB a device, 0.00 GB free before, rung 0 of 5; the device reports no bytes_limit)", False),
    ("no pinned_host: today's rungs", _Device(10 ** 12, ("device",)), MeshSpec(data=8), None,
     "rung 4 of 4; the top rung fits", False),
    ("the parameters sharded (fsdp=8 by MeshSpec.auto): today's rungs", _Device(10 ** 12), None, None,
     "rung 4 of 4; the top rung fits", False),
    ("the parameters whole on every device: rungs with host parts, and the top one has none",
     _Device(10 ** 12), MeshSpec(data=8), None, "rung 8 of 8; the top rung fits", False),
    ("nothing fits: rung 0", _Device(1), MeshSpec(data=8), None, "saves nothing (0.00 GB a device", False),
    ("a rung with a host part", _Device(10 ** 12), MeshSpec(data=8), _first_rung_with_a_host_part,
     "saves nothing; attn_qkv waits on the host (0.00 GB) (0.00 GB a device", True),
])
def test_the_loop_trains_the_same_at_the_rung_it_chooses(monkeypatch, case, device, mesh, chooser, says, on_host):
    # the loop's own path: LLAMA_TINY with remat on and the default policy
    # ("auto"), on a device that does or does not report a limit and a
    # pinned_host memory; the 8 virtual devices are fsdp=8 by MeshSpec.auto,
    # or data=8 where handed
    assert TINY.remat_policy == "auto"
    said = []
    monkeypatch.setattr(loop_module.obs_logging, "info", lambda msg, **kw: said.append(msg))
    monkeypatch.setattr(loop_module.jax, "local_devices", lambda: [device])
    monkeypatch.setattr(loop_module, "_peak_flops", lambda: 1e9)
    if mesh is not None:
        monkeypatch.setattr(loop_module.MeshSpec, "auto", classmethod(lambda cls, **kw: mesh))
    if chooser is not None:
        monkeypatch.setattr(loop_module, "choose_remat_rung", chooser)
    got = loop_module._run_lm_training(llama, TINY, _LOOP, None)
    line = [m for m in said if m.startswith("[train] remat: ")]
    assert len(line) == 1 and says in line[0], said
    assert ("on the host" in line[0]) == on_host == (loop_module._REMAT_OFFLOADED_BYTES.value() > 0)
    assert (loop_module._REMAT_SAVED_BYTES.value() > 0) == ("top rung" in says)
    want = _trained_at_full()
    assert got["loss"] == want["loss"] and got["grad_norm"] == want["grad_norm"]
