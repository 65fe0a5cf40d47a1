"""Repro: Pallas TPU-interpret collective kernels wedge at n-of-n devices.

tony-tpu's remote-DMA ring attention kernel (tony_tpu/ops/ring.py) under
shard_map over ALL virtual CPU devices deadlocks in interpret mode when the
mesh occupies every device in the process and per-shard work spans multiple
tiles; the IDENTICAL program over n of 2n devices completes. Observed on
single-core hosts (nproc=1) with jax 0.8.x — the interpret emulation
appears to starve for executor threads when every device in the process is
simultaneously parked inside one collective kernel.

    python pallas_interpret_collective_starvation.py 8 16   # passes
    timeout 300 python pallas_interpret_collective_starvation.py 8 8  # wedges

Because of this, the 8-way ring parity test runs in a subprocess with spare
devices (tests/test_ring_pallas.py::
test_pallas_ring_backward_eight_devices_multi_tile) — this file is the
linked standalone demonstration that the wedge tracks the device/mesh
ratio, not the kernel protocol (which passes every parity test at 4-of-8
and 8-of-16, race detection on).
"""

import functools
import os
import sys

MESH_N = int(sys.argv[1]) if len(sys.argv) > 1 else 8
DEVICES = int(sys.argv[2]) if len(sys.argv) > 2 else 2 * MESH_N

# force the CPU platform + virtual device count BEFORE the backend initializes
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + f" --xla_force_host_platform_device_count={DEVICES}"
    ).strip()

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from tony_tpu.ops.ring import ring_attention_pallas


def main() -> None:
    from jax.experimental.pallas import tpu as pltpu

    devs = jax.devices()
    print(f"devices={len(devs)} mesh={MESH_N} "
          f"({'n-of-n: expect WEDGE' if len(devs) == MESH_N else 'spare devices: expect OK'})",
          flush=True)
    mesh = Mesh(np.array(devs[:MESH_N]), ("context",))
    B, H, Hkv, D = 1, 4, 2, 64
    T = MESH_N * 256  # 256-row shards → multiple tiles per device
    ks = [jax.random.fold_in(jax.random.PRNGKey(3), i) for i in range(3)]
    q = jax.random.normal(ks[0], (B, H, T, D), jnp.float32) * 0.5
    k = jax.random.normal(ks[1], (B, Hkv, T, D), jnp.float32) * 0.5
    v = jax.random.normal(ks[2], (B, Hkv, T, D), jnp.float32) * 0.5
    spec = P(None, None, "context", None)
    ring = jax.jit(
        jax.shard_map(
            functools.partial(
                ring_attention_pallas, axis_name="context", causal=True,
                interpret=pltpu.InterpretParams(detect_races=True),
            ),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            axis_names={"context"}, check_vma=False,
        )
    )
    out = ring(q, k, v)
    jax.block_until_ready(out)
    print("OK", float(jnp.abs(out).sum()), flush=True)


if __name__ == "__main__":
    main()
