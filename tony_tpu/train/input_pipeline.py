"""Overlapped input pipeline: assemble batch N+1 while the device runs step N.

The training loop's per-step input work — TokenLoader read, synthetic
generation, and the host-to-device transfer with the target batch sharding
(``jax.make_array_from_process_local_data`` / ``jnp.asarray``) — used to run
synchronously on the step path: the device sat idle while the host built the
next batch, and the host sat idle while the device computed. This module
double-buffers the two: a background thread assembles batches ahead (bounded
by ``depth``, default 2) and the step loop's :meth:`next` is a queue pop that
only blocks when input assembly is genuinely slower than compute.

Contracts the train loop relies on:

- **Batch-sequence parity**: ``make_batch(step)`` is invoked for exactly
  ``start_step, start_step+1, …`` in order, once each, on one thread —
  identical to the synchronous path, so a seeded run feeds bit-identical
  batches either way (asserted in tests/test_input_pipeline.py). With
  ``depth <= 0`` the pipeline IS the synchronous path: ``next`` calls
  ``make_batch`` inline, no thread exists.
- **Exception propagation**: a producer failure is re-raised from ``next``
  on the step loop's thread (with the original traceback as ``__cause__``),
  never swallowed — the loop's existing ``finally`` teardown runs.
- **Clean shutdown**: ``close`` is idempotent, unblocks a producer parked on
  a full queue, and joins the thread — safe to call from the ``finally``
  block mid-run (step failure, urgent-save drain) or after exhaustion.
- **Attributable waits**: every blocking ``next`` feeds the
  ``tony_train_input_wait_seconds`` histogram, and waits at or above
  ``span_min_ms`` emit a backdated ``train.input_wait`` span so the goodput
  ledger (obs/goodput.py) can charge the stall to the ``input_wait`` phase
  instead of diluting ``productive``.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from typing import Any, Callable

from tony_tpu import constants
from tony_tpu.obs import metrics as obs_metrics

_INPUT_WAIT_SECONDS = obs_metrics.histogram(
    "tony_train_input_wait_seconds",
    "time the step loop blocked waiting on the input pipeline, per step")

#: queue entries: ("batch", step, value) | ("error", step, exc) | ("end",)
_BATCH, _ERROR, _END = "batch", "error", "end"


def depth_from_env(env: dict[str, str] | None = None) -> int:
    """The executor-exported prefetch depth (``tony.train.prefetch-depth``
    → ``TONY_PREFETCH_DEPTH``); 2 outside a tony container. 0 disables the
    overlap (synchronous assembly, the pre-pipeline behavior)."""
    env = os.environ if env is None else env
    try:
        return int(env.get(constants.ENV_PREFETCH_DEPTH, "2") or "2")
    except ValueError:
        return 2


def span_min_ms_from_env(env: dict[str, str] | None = None) -> float:
    env = os.environ if env is None else env
    try:
        return float(env.get(constants.ENV_INPUT_WAIT_SPAN_MS, "25") or "25")
    except ValueError:
        return 25.0


class InputPipelineError(RuntimeError):
    """A batch producer failure, re-raised on the step loop's thread."""


class InputPipeline:
    """Bounded-lookahead batch prefetcher over a ``make_batch(step)`` callable.

    ``make_batch`` must be a pure-enough function of ``step`` (stateful
    sources like TokenLoader are fine — they are only ever called from the
    single producer thread, in step order). The producer runs ``depth``
    batches ahead at most; device-transfer work inside ``make_batch``
    (``jnp.asarray`` / ``make_array_from_process_local_data``) is safe on
    the background thread — JAX transfers are thread-safe and enqueue
    without blocking device compute.
    """

    def __init__(
        self,
        make_batch: Callable[[int], Any],
        start_step: int,
        end_step: int,
        depth: int | None = None,
        tracer=None,
        span_min_ms: float | None = None,
    ):
        self.make_batch = make_batch
        self.start_step = start_step
        self.end_step = end_step
        self.depth = depth_from_env() if depth is None else depth
        self.tracer = tracer
        self.span_min_ms = span_min_ms_from_env() if span_min_ms is None else span_min_ms
        self.wait_s_total = 0.0
        self._next_step = start_step          # sync path / parity bookkeeping
        self._closed = False
        self._thread: threading.Thread | None = None
        if self.depth > 0 and end_step > start_step:
            self._queue: queue.Queue = queue.Queue(maxsize=self.depth)
            self._stop = threading.Event()
            self._thread = threading.Thread(
                target=self._produce, name="tony-input-pipeline", daemon=True
            )
            self._thread.start()

    @property
    def overlapped(self) -> bool:
        return self._thread is not None

    # -- producer ------------------------------------------------------------
    def _produce(self) -> None:
        step = self.start_step
        try:
            while step < self.end_step and not self._stop.is_set():
                item = (_BATCH, step, self.make_batch(step))
                step += 1
                while not self._stop.is_set():
                    try:
                        self._queue.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue  # consumer is busy computing; re-check stop
            if not self._stop.is_set():
                self._queue.put((_END,))
        except BaseException as e:  # noqa: BLE001 — ship it to the consumer
            # same stop-rechecking retry as the batch path: with the queue
            # full of ready batches and a slow device step, a bounded put
            # would drop the error and leave next() parked forever once the
            # buffered batches drain — the error must outlive the backlog
            item = (_ERROR, step, e)
            while not self._stop.is_set():
                try:
                    self._queue.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue

    # -- consumer ------------------------------------------------------------
    def next(self, step: int) -> Any:
        """The batch for ``step``; called with consecutive steps starting at
        ``start_step``. Blocks only while the producer is behind; re-raises
        a producer failure; raises StopIteration past ``end_step``."""
        if self._closed:
            raise RuntimeError("InputPipeline.next() after close()")
        if step != self._next_step:
            raise ValueError(
                f"out-of-order batch request: step {step}, expected {self._next_step}"
            )
        if step >= self.end_step:
            raise StopIteration(step)
        self._next_step = step + 1
        if self._thread is None:
            return self.make_batch(step)
        t0 = time.perf_counter()
        item = self._queue.get()
        wait = time.perf_counter() - t0
        self.wait_s_total += wait
        _INPUT_WAIT_SECONDS.observe(wait)
        if self.tracer is not None and wait * 1000.0 >= self.span_min_ms:
            # backdated like train.first_step: the span covers the stall
            with self.tracer.span("train.input_wait", step=step) as sp:
                sp.start_ms -= wait * 1000.0
        if item[0] == _ERROR:
            raise InputPipelineError(
                f"input pipeline failed assembling batch {item[1]}"
            ) from item[2]
        if item[0] == _END:
            raise StopIteration(step)
        return item[2]

    # -- teardown ------------------------------------------------------------
    def close(self) -> None:
        """Idempotent; stops the producer, drains the queue so a producer
        parked on ``put`` wakes, and joins the thread (for at most 5 s: a
        producer still inside ``make_batch`` is a daemon and is left behind)."""
        if self._closed:
            return
        self._closed = True
        if self._thread is None:
            return
        self._stop.set()
        while True:  # drain: the producer's put(timeout) re-checks _stop
            try:
                self._queue.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=5.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
