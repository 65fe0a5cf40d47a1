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


def sharded_weights(module, cfg, mesh, key, sizes):
    """The seed's weights laid out as the loop lays them out (the model's own
    sharding rules), made on the device in one jitted call. The key is an
    argument: one program for every seed, kept in the compile cache."""
    import jax

    import reference

    abstract = jax.eval_shape(lambda k: reference.init_weights(k, sizes), key)
    shardings = module.sharding_rules(cfg).sharding_tree(abstract, mesh)
    return jax.jit(lambda k: reference.init_weights(k, sizes), out_shardings=shardings)(key)
