"""Context parallelism as the chip compiles it: the XLA ``ppermute`` ring and
Ulysses, each against ``attention_reference`` on the virtual CPU mesh
(forward, backward, eight devices), a Llama train step under ``context=4``
against the same step with no mesh, and what ``llama._attention`` refuses.
Plain XLA throughout: no Pallas kernel, no interpreter.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from tony_tpu.models import llama
from tony_tpu.ops.attention import attention_reference, repeat_kv
from tony_tpu.parallel import MeshSpec
from tony_tpu.parallel.context import ring_attention, ulysses_attention

IMPLS = {"ring": ring_attention, "ulysses": ulysses_attention}
SEQ = P(None, None, "context", None)


def _qkv(H, Hkv, T, B=2, D=16, seed=3):
    """GQA inputs with the KV heads broadcast to H before the context axis,
    as ``llama._attention`` does for the ring (and for Ulysses when Hkv does
    not divide by the context degree)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, T, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, T, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, T, D), jnp.float32)
    return q, repeat_kv(k, H // Hkv), repeat_kv(v, H // Hkv)


def _sharded(impl, causal, n, out_specs=SEQ, reduce=None):
    fn = functools.partial(IMPLS[impl], axis_name="context", causal=causal)
    if reduce is not None:
        attn = fn
        fn = lambda q, k, v: jax.lax.psum(reduce(attn(q, k, v)), "context")  # noqa: E731
    return shard_map(
        fn, mesh=MeshSpec(context=n).build(devices=jax.devices()[:n]),
        in_specs=(SEQ, SEQ, SEQ), out_specs=out_specs,
        axis_names={"context"}, check_vma=False,
    )


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "noncausal"])
@pytest.mark.parametrize("impl", list(IMPLS))
def test_forward_matches_reference_gqa_4way(impl, causal):
    q, k, v = _qkv(H=4, Hkv=2, T=64)
    got = jax.jit(_sharded(impl, causal, 4))(q, k, v)
    want = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("impl", list(IMPLS))
def test_forward_matches_reference_eight_devices(impl):
    q, k, v = _qkv(H=8, Hkv=2, T=128)
    got = jax.jit(_sharded(impl, True, 8))(q, k, v)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "noncausal"])
@pytest.mark.parametrize("impl", list(IMPLS))
def test_backward_matches_reference(impl, causal):
    q, k, v = _qkv(H=4, Hkv=2, T=64)
    w = jnp.arange(q.shape[-1], dtype=jnp.float32) / q.shape[-1]

    def scalar(out):
        return (out * w).sum()

    got = jax.jit(jax.grad(_sharded(impl, causal, 4, out_specs=P(), reduce=scalar), argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(lambda q, k, v: scalar(attention_reference(q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-4, err_msg=name)


def test_llama_train_step_xla_ring_matches_no_mesh():
    cfg = dataclasses.replace(llama.LLAMA_TINY, cp_impl="xla", dtype="float32")
    params = llama.init(jax.random.PRNGKey(0), cfg)
    batch = llama.synthetic_batch(jax.random.PRNGKey(1), 2, 32, cfg)

    def loss_and_grads(mesh, p):
        return jax.jit(jax.value_and_grad(lambda p: llama.loss_fn(p, batch, cfg, mesh)[0]))(p)

    want_loss, want = loss_and_grads(None, params)
    mesh = MeshSpec(context=4, data=2).build()
    sharded = jax.device_put(params, llama.sharding_rules(cfg).sharding_tree(params, mesh))
    got_loss, got = loss_and_grads(mesh, sharded)
    assert abs(float(got_loss) - float(want_loss)) < 1e-4
    for (path, a), b in zip(jax.tree.leaves_with_path(jax.device_get(got)), jax.tree.leaves(jax.device_get(want))):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-3 * np.abs(b).max(), err_msg=jax.tree_util.keystr(path))


def _attend(cfg, mesh, segment_ids=None):
    q = jnp.zeros((2, cfg.n_heads, 16, 16))
    kv = jnp.zeros((2, cfg.n_kv_heads, 16, 16))
    return llama._attention(q, kv, kv, cfg, mesh, segment_ids=segment_ids)


@pytest.mark.parametrize("value", ["pallas", "ring"])
def test_cp_impl_refuses_what_it_does_not_have(value):
    cfg = dataclasses.replace(llama.LLAMA_TINY, cp_impl=value)
    with pytest.raises(ValueError, match="cp_impl must be 'xla' or 'ulysses'"):
        _attend(cfg, None)


@pytest.mark.parametrize("what", ["segment_ids", "sliding_window"])
@pytest.mark.parametrize("cp_impl", ["xla", "ulysses"])
def test_context_axis_refuses_packing_and_window(cp_impl, what):
    cfg = dataclasses.replace(llama.LLAMA_TINY, cp_impl=cp_impl)
    mesh = MeshSpec(context=4, data=2).build()
    seg = None
    if what == "segment_ids":
        seg = jnp.ones((2, 16), jnp.int32)
    else:
        cfg = dataclasses.replace(cfg, sliding_window=8)
    with pytest.raises(ValueError, match=f"context parallelism does not compose with .*{what}"):
        _attend(cfg, mesh, segment_ids=seg)
    # the same inputs are served where no context axis is real
    out = _attend(cfg, MeshSpec(data=8).build(), segment_ids=seg)
    assert out.shape == (2, cfg.n_heads, 16, 16)
