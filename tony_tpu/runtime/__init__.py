"""Framework runtime adapters (reference tony-core runtime/ analog).

``get_runtime(config)`` selects the adapter from
``tony.application.framework``; ``init_distributed()`` is the user-side helper
that consumes the env contract the JaxRuntime injects, and
``enable_compile_cache()`` is what every process that will compile calls
first: it also starts the count of every compile by its source
(``tony_compile_seconds_total{stage}``, ``tony_compiles_total{source}``).
"""

from __future__ import annotations

import os
import threading
import time

from tony_tpu import constants
from tony_tpu.obs import metrics as obs_metrics
from tony_tpu.obs import trace as obs_trace
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

    _count_compiles()
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not path and jax.default_backend() != "cpu":
        path = DEFAULT_COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path


#: jax.monitoring's duration events -> the counter's ``stage``. Tracing and
#: lowering run in Python whatever the persistent cache holds; `backend` is
#: the XLA compile itself, `cache_load` reading a compiled program back
_COMPILE_STAGE = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
_compile_state = threading.local()  # what a compile event in flight on this thread has to know of the one before
_compile_counting = False


def _count_compiles() -> None:
    """Register the jax.monitoring listeners, once a process. Always on: a
    callback or three a compile.

    `backend_compile_duration` is timed around `compile_or_get_cached`, so on a
    hit of the persistent cache it contains the retrieval that
    `cache_retrieval_time_sec` reported a moment before, on the same thread:
    `backend` counts the duration less that retrieval, and the hit counts under
    `source="cache"` only. `jaxpr_trace_duration` fires for every jit traced
    inside another's trace as well (a train step's trace holds dozens), each
    inside its outer's duration: only the outermost is counted, told by the
    scalar event that opens every timed block. `fun_name` stays off the
    counters (unbounded) and goes on the span: with a tracer on, every counted
    event also writes one backdated `runtime.compile` span (`stage`, `fun_name`)."""
    global _compile_counting
    if _compile_counting:
        return
    _compile_counting = True
    import jax.monitoring

    seconds = obs_metrics.counter(
        "tony_compile_seconds_total",
        "seconds this process spent bringing programs to the device, by stage: trace (to jaxprs, outermost "
        "traces), lower (to MLIR), backend (the XLA compile, less a cache read inside it), cache_load "
        "(reading a compiled program from the persistent cache)", labelnames=("stage",))
    compiles = obs_metrics.counter(
        "tony_compiles_total",
        "programs brought to the device, by where the executable came from: backend "
        "(compiled) or cache (read from the persistent cache)", labelnames=("source",))
    st = _compile_state

    def span(stage: str, start_s: float, end_s: float, fun_name: str) -> None:
        obs_trace.end_manual(
            obs_trace.start_manual("runtime.compile", start_s=start_s, stage=stage, fun_name=fun_name),
            end_s=end_s)

    def on_open(event: str, _value: float, **kw) -> None:
        if _COMPILE_STAGE.get(event) == "trace":
            st.depth = getattr(st, "depth", 0) + 1

    def on_duration(event: str, duration: float, **kw) -> None:
        stage = _COMPILE_STAGE.get(event)
        if stage is None:
            return
        if stage == "trace":
            st.depth = max(getattr(st, "depth", 1) - 1, 0)
            if st.depth:
                return  # inside an outer trace, whose duration holds this one
        elif stage == "cache_load":
            now = time.time()
            st.hit = (now - duration, duration)
            compiles.inc(source="cache")
            span(stage, now - duration, now, "")  # the event has no time span: it ends at this callback
        elif stage == "backend":
            hit = st.__dict__.pop("hit", None)
            st.cut = hit[0] if hit else None
            if hit is None:
                compiles.inc(source="backend")
            else:
                duration = max(duration - hit[1], 0.0)
        seconds.inc(duration, stage=stage)

    def on_time_span(event: str, start_s: float, end_s: float, **kw) -> None:
        stage = _COMPILE_STAGE.get(event)
        if stage is None or (stage == "trace" and getattr(st, "depth", 0)):
            return
        if stage == "backend":  # on a hit, up to where the read began: the rest is the cache_load span
            cut = st.__dict__.pop("cut", None)
            if cut is not None:
                end_s = max(start_s, min(end_s, cut))
        span(stage, start_s, end_s, str(kw.get("fun_name", "")))

    jax.monitoring.register_scalar_listener(on_open)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_time_span_listener(on_time_span)
