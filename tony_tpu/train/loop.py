"""Reusable training loop: what a user program run by `tony submit` calls.

The analog of the reference's example training scripts' shared structure
(tony-examples, SURVEY.md §2.3) promoted into the framework: join the gang
(init_distributed), build the mesh from the env/args, shard-init the model,
step with throughput metrics, checkpoint on an interval, resume after a gang
restart.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import sys
import time
from dataclasses import dataclass

import jax

from tony_tpu import constants
from tony_tpu.obs import logging as obs_logging
from tony_tpu.obs import metrics as obs_metrics
from tony_tpu.obs import startup as obs_startup
from tony_tpu.obs import trace as obs_trace
from tony_tpu.ops.attention import HOST_NAMES, REMAT_LADDER, Rung, named_bytes
from tony_tpu.parallel import MeshSpec
from tony_tpu.runtime import device_facts, enable_compile_cache, init_distributed
from tony_tpu.train.checkpoint import UrgentSaveSignal, restore_or_init
from tony_tpu.train.input_pipeline import InputPipeline
from tony_tpu.train.metrics import detect_peak_flops, flops_per_token_for_batch
from tony_tpu.train.profiling import StepProfiler
from tony_tpu.train.trainer import (
    OptimizerConfig,
    REMAT_MARGIN,
    Throughput,
    choose_remat_rung,
    make_pp_train_step,
    make_train_step,
    remat_candidates,
    sharded_init,
)

_FIRST_STEP_SECONDS = obs_metrics.gauge(
    "tony_train_first_step_seconds",
    "wall time of the first executed step (XLA compile + first run)")
_REMAT_SAVED_BYTES = obs_metrics.gauge(
    "tony_train_remat_saved_bytes",
    "what the decoder blocks keep of the forward for the backward, bytes a "
    "device and step (0: every layer's forward runs again in its backward)")
_REMAT_OFFLOADED_BYTES = obs_metrics.gauge(
    "tony_train_remat_offloaded_bytes",
    "what of that waits in the host's pinned memory and not on the device, "
    "bytes a device and step (0: nothing, or a policy other than auto)")
_STEP_SECONDS = obs_metrics.histogram(
    "tony_train_step_seconds",
    "mean per-step wall time, sampled once per logging window")


@dataclass(frozen=True)
class LoopConfig:
    steps: int = 100
    #: LR-schedule horizon; 0 → ``steps``. Set it when a run will be
    #: extended (or was cut short) so warmup/decay stay anchored to the
    #: FULL plan — otherwise a 4-step run resumed to 8 decays twice as fast
    #: over its first half as the uninterrupted 8-step run did
    schedule_steps: int = 0
    #: GLOBAL batch rows per step — constant across gang sizes. Each of the
    #: K gang processes contributes ``batch_size // K`` rows, so an elastic
    #: restart onto a smaller gang keeps the optimization trajectory AND
    #: the data-replay contract (global-order draw) intact.
    batch_size: int = 8
    seq_len: int = 512
    log_every: int = 10
    checkpoint_dir: str = ""
    checkpoint_every: int = 0
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    model_axis: int = 1
    context_axis: int = 1
    expert_axis: int = 1
    stage_axis: int = 1        # >1: pipeline parallelism (1F1B schedule)
    pp_microbatches: int = 4   # microbatches per 1F1B step (batch must divide)
    pp_chunks: int = 1         # >1: interleaved virtual stages per device
    data_dir: str = ""  # dir of *.tonytok shards; empty → synthetic batches
    data_seed: int = 0  # window-draw seed; FIXED across restarts (replay)
    #: input-pipeline lookahead: batch N+1 is assembled (loader read /
    #: synthetic draw + device transfer) on a background thread while the
    #: device runs step N (train/input_pipeline.py). -1 → the executor's
    #: tony.train.prefetch-depth (TONY_PREFETCH_DEPTH env; 2 outside a
    #: container); 0 → synchronous per-step assembly (the legacy path).
    prefetch_depth: int = -1


def _drop_train_metrics(line: dict) -> None:
    """Atomically publish the latest step report to the path the executor
    advertised (ENV_TRAIN_METRICS_FILE) — the metrics push loop attaches
    it to this task's heartbeat metrics so the AM/portal see training
    progress (loss/tokens_per_sec/mfu), not just host counters. No-op
    outside a tony container; never raises."""
    path = os.environ.get(constants.ENV_TRAIN_METRICS_FILE)
    if not path:
        return
    try:
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(line, f)
        os.replace(tmp, path)
    except OSError:
        pass


def _drop_obs_metrics() -> None:
    """Atomically publish this child's non-empty metrics-registry snapshot
    next to the step report (<train-metrics-file>.obs): the executor merges
    it into its push_metrics piggyback so checkpoint/step-time instruments
    reach the AM's get_metrics and the portal's /metrics. No-op outside a
    tony container; never raises."""
    path = os.environ.get(constants.ENV_TRAIN_METRICS_FILE)
    if not path:
        return
    snap = [m for m in obs_metrics.REGISTRY.snapshot() if m["samples"]]
    if not snap:
        return
    try:
        tmp = path + ".obs.tmp"
        with open(tmp, "w") as f:
            json.dump(snap, f)
        os.replace(tmp, path + ".obs")
    except OSError:
        pass


def _step_memory(executable) -> int | None:
    """A compiled step's peak bytes on one device by the compiler's own
    report, arguments included. Not arguments + outputs + temporaries -
    aliased where the peak is given: the TPU compiler's temporaries are its
    whole heap, fragmentation and all (19.1 GB for a step that runs in 14.5:
    PERF.md section 6, PR 47)."""
    m = executable.memory_analysis()
    if m is None:
        return None
    return getattr(m, "peak_memory_in_bytes", 0) or (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _peak_flops() -> float | None:
    """The chip's published bf16 peak; None on the CPU, which has none."""
    return None if jax.default_backend() == "cpu" else detect_peak_flops()


def _auto_remat_step(model_module, model_cfg, mesh, opt):
    """The train step of ``remat_policy="auto"``: the blocks save for their
    backward the highest rung of ops/attention.REMAT_LADDER that the device's
    memory holds (train/trainer.choose_remat_rung), chosen at the first call,
    when the state and a batch are there to lower the step with. What runs
    from then on is the executable the choice compiled, not a second trace.

    Where the backend has a ``pinned_host`` memory the ladder has host parts
    (train/trainer.remat_candidates): ops/attention.HOST_NAMES may wait in
    the host's memory for a layer's bytes on the device."""

    def step_at(saved, host=()):
        cfg = dataclasses.replace(model_cfg, remat_policy=Rung(saved, host) if host else saved)
        return make_train_step(functools.partial(model_module.loss_fn, cfg=cfg, mesh=mesh), opt)

    def say(names, host, saved, on_host, free, rung, n, why):
        _REMAT_SAVED_BYTES.set(saved)
        _REMAT_OFFLOADED_BYTES.set(on_host)
        waits = f"; {', '.join(host)} wait{'s' if len(host) == 1 else ''} on the host ({on_host / 1e9:.2f} GB)" if host else ""
        obs_logging.info(
            f"[train] remat: saves {', '.join(names) or 'nothing'}{waits} ({saved / 1e9:.2f} GB a device, "
            f"{free / 1e9:.2f} GB free before, rung {rung} of {n}; {why})")

    limits = [(d.memory_stats() or {}).get("bytes_limit") for d in jax.local_devices()]
    if not all(limits):
        say((), (), 0, 0, 0, 0, len(REMAT_LADDER) - 1, "the device reports no bytes_limit")
        return step_at(REMAT_LADDER[0])
    limit = min(limits)
    host_names = HOST_NAMES if all(
        any(m.kind == "pinned_host" for m in d.addressable_memories()) for d in jax.local_devices()) else ()

    def choose(state, batch):
        # one device's share of the batch, through the model with no mesh:
        # the named values at the shapes a device holds them in
        rows = mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1)
        context = mesh.shape.get("context", 1)
        shape = jax.ShapeDtypeStruct
        share = {k: shape((max(v.shape[0] // rows, 1), (v.shape[1] - 1) // context + 1), v.dtype)
                 for k, v in batch.items()}
        named = named_bytes(
            functools.partial(model_module.loss_fn, mesh=None,
                              cfg=dataclasses.replace(model_cfg, remat_policy=REMAT_LADDER[-1])),
            jax.tree.map(lambda p: shape(p.shape, p.dtype), state.params), share, has_aux=True)

        def on_fullest_device(tree) -> int:
            held: dict[int, int] = {}
            for leaf in jax.tree.leaves(tree):
                for shard in leaf.addressable_shards:
                    held[shard.device.id] = held.get(shard.device.id, 0) + shard.data.nbytes
            return max(held.values(), default=0)

        # rung 0 before any compile: the state, gradients the size of the
        # parameters, the batch, and one block's backward, which holds the
        # block's activations and their cotangents, some of them in float32:
        # two and a half times a layer's named bytes (12.49 GB by the report
        # of rung 3 at 4 layers of 7B widths, of which state and gradients
        # 9.08 and a layer's names 1.41: 2.4 layers'; my chip runs, PR 56).
        # Where the parameters are sharded a layer's are gathered whole for its
        # forward and for its backward, the next layer's behind each, and its
        # gradient is whole before it is scattered: about six layers'
        # parameters (13.44 GB compiled at 16 layers over four chips, 5.7
        # layers' more than this reckons without them: compile-only, PR 47).
        # It leans high, by little: a report with room sends the chooser up
        # and one over the budget down, each at the price of a cache load;
        # a compile that runs out of memory is kept by no cache and is paid
        # at every start.
        layers = max(getattr(model_cfg, "n_layers", 1), 1)
        params_held = on_fullest_device(state.params)
        params_whole = sum(leaf.nbytes for leaf in jax.tree.leaves(state.params))
        held = (on_fullest_device(state) + params_held + on_fullest_device(batch)
                + 5 * sum(named.get(n, 0) for n in REMAT_LADDER[-1]) // (2 * layers)
                + (6 * params_whole // layers if params_whole > params_held else 0))
        # the step's own reckoned time: its flops a device over the peak. No
        # host part where the parameters are sharded: at 16 layers over four
        # chips q, k and v on the host compile to 0.61 GB more than rung 1
        # (15.54 for 14.93 GB, compile-only, where one chip's 4 layers take
        # 0.20), more than is free there, and a rung that cannot fit costs
        # every cold start its compile (PR 56)
        tokens = share["tokens"].shape[0] * (share["tokens"].shape[1] - 1)
        peak = _peak_flops()
        step_seconds = peak and flops_per_token_for_batch(
            model_cfg, batch, batch["tokens"].shape[1] - 1) * tokens / peak
        rungs = remat_candidates(
            REMAT_LADDER, named, layers, host_names if params_whole == params_held else (), step_seconds)
        saved = [held_at for _, _, held_at in rungs]

        def compile_rung(i):
            executable = step_at(*rungs[i][:2]).lower(state, batch).compile()
            return executable, _step_memory(executable)

        i, executable, why = choose_remat_rung(saved, limit, held, compile_rung)
        used = _step_memory(executable)
        free = int(limit * (1 - REMAT_MARGIN)) - (used - saved[i] if used is not None else held)
        names, host, _ = rungs[i]
        say(names, host, sum(named.get(n, 0) for n in names), sum(named.get(n, 0) for n in host),
            free, i, len(rungs) - 1, why)
        return executable

    chosen = []

    def step(state, batch):
        if not chosen:
            chosen.append(choose(state, batch))
        return chosen[0](state, batch)

    return step


def run_lm_training(model_module, model_cfg, loop: LoopConfig) -> dict:
    """Generic decoder-LM pretraining loop (llama/mixtral modules).

    model_module must expose init/loss_fn/sharding_rules/synthetic_batch and
    the config flops_per_token(). Returns the final metrics dict.

    Under a traced tony job (TONY_TRACE_* env from the executor) the whole
    run is one span with first-step (compile) and checkpoint child spans;
    outside a container the tracer is None and nothing is recorded.
    """
    obs_startup.begin("train")  # main_entered: the start-up account's first stamp of this process
    if os.environ.get(constants.ENV_METRICS_ENABLED) == "0":
        obs_metrics.set_enabled(False)  # the job opted out (tony.metrics.enabled)
    # structured logging (tony.log.*): this child's records join the job-wide
    # <staging>/logs aggregate; outside a container the helpers echo only
    obs_logging.init_from_env()
    tracer = obs_trace.init_from_env()
    if tracer is None:
        return _run_lm_training(model_module, model_cfg, loop, None)
    root, token = tracer.start_span("train.run")
    root.set(steps=loop.steps, batch_size=loop.batch_size)
    tracer.root_parent = root.span_id
    try:
        result = _run_lm_training(model_module, model_cfg, loop, tracer)
    except BaseException:
        tracer.end_span(root, token, status="error")
        obs_trace.shutdown()
        raise
    tracer.end_span(root, token)
    obs_trace.shutdown()
    return result


def _run_lm_training(model_module, model_cfg, loop: LoopConfig, tracer) -> dict:
    if loop.stage_axis > 1 and not hasattr(model_module, "pp_value_and_grad"):
        # fail in milliseconds, not after a multi-GB sharded init/restore
        raise ValueError(
            f"{model_module.__name__} has no pp_value_and_grad — "
            "pipeline parallelism (stage_axis > 1) needs a model with a "
            "1F1B train-step core (llama and mixtral families have one)"
        )
    if loop.stage_axis > 1 and loop.pp_chunks > 1:
        import inspect

        sig = inspect.signature(model_module.pp_value_and_grad)
        if "num_chunks" not in sig.parameters:
            raise ValueError(
                f"{model_module.__name__}.pp_value_and_grad has no interleaved "
                "schedule (num_chunks) — --pp_chunks > 1 is llama-family only"
            )
    init_distributed()  # no-op off-gang; joins jax.distributed under tony
    cache_dir = enable_compile_cache()
    obs_logging.info(f"[train] device {json.dumps(device_facts())} compile_cache={cache_dir}")
    spec = MeshSpec.auto(
        model=loop.model_axis, context=loop.context_axis, expert=loop.expert_axis,
        stage=loop.stage_axis,
    )
    # multi-slice pools (MultiSliceResourceManager) announce the DCN layout;
    # build() then restricts DCN crossings to data/pipeline axes
    num_slices = int(os.environ.get(constants.ENV_TPU_NUM_SLICES, "1") or "1")
    mesh = spec.build(num_slices=num_slices)
    n_chips = len(jax.devices())
    obs_startup.stamp("devices_ready")  # JAX imported, PJRT client up, the mesh built

    opt_cfg = OptimizerConfig(
        learning_rate=loop.learning_rate, warmup_steps=loop.warmup_steps,
        total_steps=loop.schedule_steps or loop.steps,
    )
    opt = opt_cfg.build()
    rules = model_module.sharding_rules(model_cfg)

    def init_state():
        return sharded_init(
            lambda: model_module.init(jax.random.PRNGKey(0), model_cfg), rules, mesh, opt
        )

    state, ckpt_mgr, start_step = restore_or_init(loop.checkpoint_dir or None, init_state)
    jax.block_until_ready(state)  # the stage ends when the weights are on the device, not when they were asked for
    obs_startup.stamp("weights_ready")
    if start_step:
        obs_logging.info(f"[train] resumed from checkpoint step {start_step}", step=start_step)
    # where the parameters really live: a layout that leaves a chip empty (or
    # piles everything on the first) shows here, not in the loss
    per_device: dict[int, int] = {}
    for leaf in jax.tree.leaves(state.params):
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] = per_device.get(shard.device.id, 0) + shard.data.nbytes
    obs_logging.info(f"[train] param bytes per device: {json.dumps(per_device, sort_keys=True)}")

    if loop.stage_axis > 1:
        # pipeline parallelism: the 1F1B schedule produces its own gradients
        # (hand-scheduled interleaved backward; see parallel/pipeline.py)
        step_fn = make_pp_train_step(
            functools.partial(
                model_module.pp_value_and_grad, cfg=model_cfg, mesh=mesh,
                num_microbatches=loop.pp_microbatches,
                **({"num_chunks": loop.pp_chunks} if loop.pp_chunks > 1 else {}),
            ),
            opt,
        )
    elif getattr(model_cfg, "remat_policy", None) == "auto" and model_cfg.remat:
        step_fn = _auto_remat_step(model_module, model_cfg, mesh, opt)
    else:
        step_fn = make_train_step(
            functools.partial(model_module.loss_fn, cfg=model_cfg, mesh=mesh), opt
        )
    # gathered-MLM batches (BERT) project only the masked positions through
    # the vocab head — derive the flops basis from an actual batch so the
    # reported MFU matches the work done (shared helper with bench.py)
    probe = model_module.synthetic_batch(
        jax.random.PRNGKey(0), 1, loop.seq_len, model_cfg
    )
    meter = Throughput(
        tokens_per_step=loop.batch_size * loop.seq_len,
        flops_per_token=flops_per_token_for_batch(model_cfg, probe, loop.seq_len),
        n_chips=n_chips,
        peak_flops=_peak_flops(),
    )

    key = jax.random.PRNGKey(start_step + 1)
    procs = jax.process_count()
    if loop.batch_size % procs:
        raise ValueError(
            f"global batch_size {loop.batch_size} must divide by the gang's "
            f"{procs} processes (elastic restarts re-split the SAME global "
            "batch across the new gang)"
        )
    local_rows = loop.batch_size // procs
    loader = None
    if loop.data_dir:
        # Real data: the native prefetching loader, data-parallel split by
        # process (the TF_CONFIG-analog contract: each gang member owns a
        # contiguous row-slice of every GLOBAL batch).
        from pathlib import Path

        from tony_tpu.data import TokenLoader
        from tony_tpu.data.dataset import ConsumptionCursor

        paths = sorted(Path(loop.data_dir).glob("*.tonytok"))
        # exact replay on resume: the draw is a pure function of
        # (data_seed, GLOBAL slot), so keeping the seed and global batch
        # FIXED and starting the loader at the resumed step replays the
        # uninterrupted stream — no sample repeated or skipped — even when
        # the gang restarted at a DIFFERENT size (global-order contract,
        # data/loader.py). The consumption cursor persisted next to each
        # checkpoint proves the resumed stream IS the checkpointed one: a
        # silently changed global batch or seed fails here instead of
        # double-consuming or dropping samples across the resize.
        if start_step and loop.checkpoint_dir:
            cursor = ConsumptionCursor.load(loop.checkpoint_dir, start_step)
            if cursor is not None:
                cursor.validate_resume(loop.batch_size, loop.data_seed, start_step)
                obs_logging.info(
                    f"[train] data cursor validated: resuming the global "
                    f"stream at batch {start_step} "
                    f"(written at world size {cursor.world_size}, now {procs})",
                    step=start_step,
                )
        loader = TokenLoader(
            paths, local_rows, loop.seq_len,
            shard_id=jax.process_index(), num_shards=procs,
            seed=loop.data_seed, start_index=start_step,
        )
        obs_logging.info(f"[train] data: {len(paths)} shards, {loader.total_tokens} tokens")

        def drop_cursor(next_batch: int) -> None:
            # rank 0 persists the consumption position with every checkpoint
            if jax.process_index() == 0:
                ConsumptionCursor(
                    global_batch_index=next_batch,
                    global_batch_size=loop.batch_size,
                    seed=loop.data_seed,
                    world_size=procs,
                ).save(loop.checkpoint_dir)
    else:
        def drop_cursor(next_batch: int) -> None:
            pass

    assemble = None
    if procs > 1:
        # each process contributes its contiguous row-slice; the global
        # batch array is sharded over the data-parallel mesh axes (the
        # spmd_train E2E pattern promoted into the loop)
        from jax.sharding import NamedSharding, PartitionSpec

        batch_sharding = NamedSharding(mesh, PartitionSpec(("data", "fsdp")))

        def assemble(local):
            import numpy as np

            return jax.make_array_from_process_local_data(
                batch_sharding, np.asarray(local)
            )

    def make_batch(step: int):
        """Pure-enough batch assembly for one step — the single definition
        both the synchronous and the overlapped pipeline paths run, so the
        fed batch sequence is bit-identical either way (the loader is only
        ever called from one thread, in step order)."""
        if loader is not None:
            local = loader.next()
            return {
                "tokens": assemble(local) if assemble else jax.numpy.asarray(local)
            }
        if assemble is not None:
            local = model_module.synthetic_batch(
                jax.random.fold_in(jax.random.fold_in(key, step), jax.process_index()),
                local_rows, loop.seq_len, model_cfg,
            )
            return {k: assemble(v) for k, v in local.items()}
        return model_module.synthetic_batch(
            jax.random.fold_in(key, step), loop.batch_size, loop.seq_len, model_cfg
        )

    metrics: dict = {}
    profiler = StepProfiler()  # no-op unless the executor exported TONY_PROFILE_DIR
    urgent = UrgentSaveSignal()  # cooperative-preemption checkpoint trigger
    pipeline = InputPipeline(
        make_batch, start_step, loop.steps,
        depth=None if loop.prefetch_depth < 0 else loop.prefetch_depth,
        tracer=tracer,
    )
    if pipeline.overlapped:
        obs_logging.info(
            f"[train] input pipeline: overlapped, depth {pipeline.depth}"
        )
    meter.start()
    # sampled step timing: one histogram observation (mean step wall time)
    # per logging window — the hot loop itself pays two int compares
    window_t0 = time.perf_counter()
    window_step0 = start_step
    try:
        for step in range(start_step, loop.steps):
            profiler.step(step)
            batch = pipeline.next(step)
            first = step == start_step
            if first:
                t_first = time.perf_counter()
            state, metrics = step_fn(state, batch)
            if first:
                # the first executed step is dominated by XLA compilation —
                # the critical-path item `tony trace` reports per worker
                jax.block_until_ready(metrics["loss"])
                first_s = time.perf_counter() - t_first
                obs_startup.stamp("first_step_done")
                _FIRST_STEP_SECONDS.set(first_s)
                obs_logging.info(f"[train] first step (compile included) {first_s:.2f}s")
                if tracer is not None:
                    with tracer.span("train.first_step", step=step) as sp:
                        sp.start_ms -= first_s * 1000.0
                window_t0, window_step0 = time.perf_counter(), step + 1
            meter.step()
            if (step + 1) % loop.log_every == 0 or step + 1 == loop.steps:
                jax.block_until_ready(metrics["loss"])
                report = meter.report()
                line = {
                    "step": int(metrics["step"]),
                    "loss": round(float(metrics["loss"]), 4),
                    "grad_norm": round(float(metrics["grad_norm"]), 4),
                    "tokens_per_sec": round(report["tokens_per_sec"], 1),
                    **({"mfu": round(report["mfu"], 4)} if "mfu" in report else {}),
                    "time": time.strftime("%H:%M:%S"),
                }
                obs_logging.info(json.dumps(line), **line)
                _drop_train_metrics(line)
                n_window = step + 1 - window_step0
                if n_window > 0:
                    _STEP_SECONDS.observe((time.perf_counter() - window_t0) / n_window)
                window_t0, window_step0 = time.perf_counter(), step + 1
                _drop_obs_metrics()  # after observe: the window's sample ships with it
                meter.start()
            saved_this_step = False
            if (
                ckpt_mgr is not None
                and loop.checkpoint_every
                and (step + 1) % loop.checkpoint_every == 0
            ):
                ckpt_mgr.save(step + 1, state)
                drop_cursor(step + 1)
                saved_this_step = True
            if ckpt_mgr is not None and (drain_req := urgent.poll()) is not None:
                # the pool is preempting this job (checkpoint-then-yield):
                # force-save NOW — synchronously, the gang dies the moment
                # every rank acknowledges — so the resumed gang loses only
                # the steps between this one and the kill. A periodic save
                # of this very step is not rewritten, just drained.
                obs_logging.warning(
                    f"[train] urgent pre-preemption checkpoint at step {step + 1}",
                    step=step + 1,
                )
                if not saved_this_step:
                    ckpt_mgr.save(step + 1, state, force=True)
                    drop_cursor(step + 1)
                ckpt_mgr.wait()
                urgent.acknowledge(drain_req, step + 1)
    finally:
        # a failed step/save must not leak the input-pipeline thread, the
        # loader's mmapped shards (gang restarts re-enter this function in
        # the same process) nor a dangling profiler capture
        pipeline.close()
        if loader is not None:
            loader.close()
        profiler.stop()  # flush if the run ended inside the capture window
    if ckpt_mgr is not None:
        # skip if this step is already on disk (resume that ran no new steps)
        if ckpt_mgr.latest_step() != loop.steps:
            ckpt_mgr.save(loop.steps, state, force=True)
            drop_cursor(loop.steps)
        ckpt_mgr.wait()
        ckpt_mgr.close()
    _drop_obs_metrics()  # final flush: last window + final checkpoint sample
    return {k: float(v) for k, v in metrics.items() if hasattr(v, "item") or isinstance(v, (int, float))}


def parse_loop_args(argv: list[str] | None = None) -> tuple[LoopConfig, dict]:
    """Shared CLI for example scripts; returns (LoopConfig, extra model args)."""
    import argparse

    import os

    from tony_tpu import constants

    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--schedule_steps", type=int, default=0,
                   help="LR-schedule horizon (0 = --steps); set when extending runs")
    p.add_argument("--batch_size", type=int, default=8)
    p.add_argument("--seq_len", type=int, default=512)
    p.add_argument("--log_every", type=int, default=10)
    # checkpoint settings default from the executor-injected env (the
    # tony.checkpoint.* keys of the frozen job conf); CLI flags override
    p.add_argument(
        "--checkpoint_dir", default=os.environ.get(constants.ENV_CHECKPOINT_DIR, "")
    )
    try:
        env_interval = int(os.environ.get(constants.ENV_CHECKPOINT_INTERVAL, "0") or 0)
    except ValueError:
        # a malformed tony.checkpoint.interval-steps must not crash every
        # worker at argparse-construction time; fall back to "final only"
        obs_logging.warning(
            f"[train] ignoring non-integer {constants.ENV_CHECKPOINT_INTERVAL}="
            f"{os.environ[constants.ENV_CHECKPOINT_INTERVAL]!r}"
        )
        env_interval = 0
    p.add_argument("--checkpoint_every", type=int, default=env_interval)
    p.add_argument("--learning_rate", type=float, default=3e-4)
    p.add_argument("--warmup_steps", type=int, default=100)
    p.add_argument("--model_axis", type=int, default=1)
    p.add_argument("--context_axis", type=int, default=1)
    p.add_argument("--expert_axis", type=int, default=1)
    p.add_argument("--stage_axis", type=int, default=1,
                   help="pipeline stages (1F1B schedule when > 1)")
    p.add_argument("--pp_microbatches", type=int, default=4)
    p.add_argument("--pp_chunks", type=int, default=1,
                   help=">1: interleaved 1F1B (virtual stage chunks per device; "
                        "llama family)")
    p.add_argument("--data_dir", default="")
    p.add_argument("--data_seed", type=int, default=0)
    p.add_argument("--prefetch_depth", type=int, default=-1,
                   help="input-pipeline lookahead; -1 = tony.train.prefetch-"
                        "depth via env (default 2), 0 = synchronous assembly")
    p.add_argument("--preset", default="tiny")
    args = p.parse_args(argv if argv is not None else sys.argv[1:])
    d = vars(args)
    preset = d.pop("preset")
    return LoopConfig(**d), {"preset": preset}
