"""Framework runtime adapters (reference tony-core runtime/ analog).

``get_runtime(config)`` selects the adapter from
``tony.application.framework``; ``init_distributed()`` is the user-side helper
that consumes the env contract the JaxRuntime injects, and
``enable_compile_cache()`` is what every process that will compile calls first.
"""

from __future__ import annotations

import os

from tony_tpu import constants
from tony_tpu.runtime.base import Framework, FrameworkRuntime, get_runtime  # noqa: F401


def init_distributed() -> None:
    """Join the job's jax.distributed process group from injected env.

    Called at the top of TPU-native user programs (the analog of user TF code
    reading TF_CONFIG). No-op for single-process jobs or when the contract env
    is absent, so the same script runs under `tony submit` and bare python.
    """
    coord = os.environ.get(constants.ENV_JAX_COORDINATOR)
    n = int(os.environ.get(constants.ENV_JAX_NUM_PROCESSES, "1"))
    if not coord or n <= 1:
        return
    import jax

    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=n,
        process_id=int(os.environ[constants.ENV_JAX_PROCESS_ID]),
    )


def device_facts() -> dict:
    """The device as JAX reports it, in the shape every report of this repo
    uses (the train loop's log, the server's /stats, chip_smoke.py)."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "local": len(jax.local_devices())}


#: where compiled programs are kept when the caller does not say: one fixed
#: path inside the checkout (the path is part of the cache key, so a directory
#: named after a pid, a time or a temporary file would never hit)
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` places it from outside (jax reads the
    variable itself; the AM and executor hand it to the child unchanged) and
    then no other directory is set in code. Unset, the cache lives at
    :data:`DEFAULT_COMPILE_CACHE_DIR` — except on the CPU backend, where only
    the caller's variable turns it on ("" is returned): XLA:CPU logs a
    machine-feature error for every entry it reads back, and the CPU suite's
    compiles are seconds, not the chip's minutes.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not path and jax.default_backend() != "cpu":
        path = DEFAULT_COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
