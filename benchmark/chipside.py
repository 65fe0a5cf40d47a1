"""What only the process that holds the chip can report: device facts and memory."""

from __future__ import annotations

import json
import os


def write_json(path: str, obj) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


class MemoryPeak:
    """Peak bytes on the fullest chip, sampled. The TPU runtime keeps a
    program's temporaries in a region it reserves at the bottom of memory,
    apart from the allocator's buffers, so what is occupied at one moment is
    bytes_in_use + bytes_reserved; the allocator's own peak may lie higher
    (temporaries while the weights are drawn) and is taken too."""

    def __init__(self):
        self.peak = 0

    def sample(self) -> int:
        import jax

        for d in jax.local_devices():
            st = d.memory_stats() or {}
            self.peak = max(self.peak, int(st.get("peak_bytes_in_use", 0)),
                            int(st.get("bytes_in_use", 0)) + int(st.get("bytes_reserved", 0)))
        return self.peak


def seed_weights(sizes: dict, seed: int, shardings=None):
    """The seed's weights from the family's reference, made on the device in
    one jitted call. The key is an argument of it: one program for every seed,
    so the compile cache holds it after the first run (22-29 s to compile at
    7B width, 0.04 s to run). `shardings`: abstract tree -> its shardings."""
    import jax

    import families

    R = families.reference(sizes)
    key = R.seed_key(seed)
    draw = lambda k: R.init_weights(k, sizes)  # a lambda as before: its name is in the compile cache's key
    return jax.jit(draw, out_shardings=shardings and shardings(jax.eval_shape(draw, key)))(key)


def sharded_weights(module, cfg, mesh, sizes: dict, seed: int):
    """The same weights laid out as the loop lays them out (the model's own
    sharding rules)."""
    return seed_weights(sizes, seed, lambda abstract: module.sharding_rules(cfg).sharding_tree(abstract, mesh))
