"""The r11 step-path overhaul: overlapped input pipeline,
goodput input_wait attribution, bench-gate movement/provenance warnings, and
the size-1-axis collective guard.

Headline contracts:
- the overlapped pipeline feeds a BIT-IDENTICAL batch sequence to the
  synchronous path (loss-trajectory parity over a seeded run, both loader
  and synthetic sources);
- a producer failure propagates to the step loop's thread and teardown is
  clean mid-run;
- `tony bench --gate` warns on a gate round whose headline metric didn't
  move vs the prior round, and on perf records without profile provenance.
"""

import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.obs import goodput as obs_goodput
from tony_tpu.train.input_pipeline import InputPipeline, InputPipelineError


# ---------------------------------------------------------------------------
# pipeline unit contracts
# ---------------------------------------------------------------------------
class TestInputPipeline:
    def test_feeds_every_step_in_order_once(self):
        calls = []

        def make(step):
            calls.append(step)
            return step * 10

        with InputPipeline(make, 3, 9, depth=2) as p:
            assert p.overlapped
            got = [p.next(s) for s in range(3, 9)]
        assert got == [30, 40, 50, 60, 70, 80]
        assert calls == list(range(3, 9))

    def test_sync_mode_is_inline(self):
        p = InputPipeline(lambda s: s, 0, 4, depth=0)
        assert not p.overlapped
        assert [p.next(s) for s in range(4)] == [0, 1, 2, 3]
        p.close()

    def test_exhaustion_raises_stopiteration(self):
        with InputPipeline(lambda s: s, 0, 2, depth=2) as p:
            p.next(0), p.next(1)
            with pytest.raises(StopIteration):
                p.next(2)

    def test_out_of_order_request_rejected(self):
        with InputPipeline(lambda s: s, 0, 5, depth=1) as p:
            p.next(0)
            with pytest.raises(ValueError, match="out-of-order"):
                p.next(2)

    def test_producer_exception_propagates_with_cause(self):
        def bad(step):
            if step == 2:
                raise ValueError("shard went away")
            return step

        with InputPipeline(bad, 0, 6, depth=2) as p:
            assert p.next(0) == 0 and p.next(1) == 1
            with pytest.raises(InputPipelineError) as ei:
                p.next(2)
            assert isinstance(ei.value.__cause__, ValueError)

    def test_producer_error_survives_a_full_queue_backlog(self):
        """Review-caught hang: with the queue full of ready batches and a
        slow consumer, the error must wait out the backlog — a bounded put
        that drops it would leave next() parked forever once the buffered
        batches drain."""
        def bad(step):
            if step == 2:
                raise ValueError("boom after the backlog filled")
            return step

        p = InputPipeline(bad, 0, 10, depth=2)
        time.sleep(0.3)  # producer fills the 2-deep queue, then fails
        assert p.next(0) == 0 and p.next(1) == 1  # drain the backlog
        with pytest.raises(InputPipelineError):
            p.next(2)
        p.close()

    def test_close_is_idempotent_and_joins_even_when_producer_parked(self):
        # depth 1 with a never-consuming caller: the producer is parked on a
        # full queue; close() must still unblock + join it promptly
        p = InputPipeline(lambda s: bytes(1024), 0, 1000, depth=1)
        time.sleep(0.05)  # let the producer fill the queue and park
        t0 = time.perf_counter()
        p.close()
        p.close()
        assert time.perf_counter() - t0 < 2.0
        assert not p._thread.is_alive()

    def test_close_mid_run_after_partial_consumption(self):
        with InputPipeline(lambda s: s, 0, 100, depth=3) as p:
            for s in range(5):
                p.next(s)
        assert not p._thread.is_alive()

    def test_wait_metric_and_span_on_slow_producer(self):
        spans = []

        class _Span:
            def __init__(self):
                self.start_ms = 0.0
                self.attrs = {}

            def set(self, **kw):
                self.attrs.update(kw)
                return self

        class _Ctx:
            def __init__(self, rec):
                self.rec = rec

            def __enter__(self):
                return self.rec

            def __exit__(self, *exc):
                return False

        class _Tracer:
            def span(self, name, **attrs):
                sp = _Span()
                spans.append((name, sp))
                return _Ctx(sp)

        def slow(step):
            time.sleep(0.03)
            return step

        p = InputPipeline(slow, 0, 3, depth=1, tracer=_Tracer(), span_min_ms=5.0)
        for s in range(3):
            p.next(s)
        p.close()
        assert p.wait_s_total > 0
        assert spans and all(n == "train.input_wait" for n, _ in spans)

    def test_sub_floor_waits_emit_no_span(self):
        spans = []

        class _Tracer:
            def span(self, name, **attrs):  # pragma: no cover — must not run
                spans.append(name)
                raise AssertionError("span for a sub-floor wait")

        p = InputPipeline(lambda s: s, 0, 3, depth=2, tracer=_Tracer(),
                          span_min_ms=10_000.0)
        for s in range(3):
            p.next(s)
        p.close()
        assert spans == []


# ---------------------------------------------------------------------------
# loop-level parity: overlapped ≡ synchronous, bit-identical
# ---------------------------------------------------------------------------
class TestLoopParity:
    def _run(self, tmp_path, tag, depth, steps=4, **extra):
        from tony_tpu.models import llama
        from tony_tpu.train.loop import LoopConfig, run_lm_training

        return run_lm_training(
            llama, llama.LLAMA_TINY,
            LoopConfig(steps=steps, batch_size=2, seq_len=64, log_every=100,
                       warmup_steps=0, prefetch_depth=depth, **extra),
        )

    @pytest.mark.slow
    def test_synthetic_loss_trajectory_is_bit_identical(self, tmp_path):
        sync = self._run(tmp_path, "sync", depth=0)
        overlapped = self._run(tmp_path, "pre", depth=2)
        assert overlapped["step"] == sync["step"]
        assert overlapped["loss"] == sync["loss"], (sync, overlapped)

    @pytest.mark.slow
    def test_loader_loss_trajectory_is_bit_identical(self, tmp_path):
        from tony_tpu.data import write_token_shard

        rng = np.random.default_rng(7)
        data = tmp_path / "data"
        data.mkdir()
        write_token_shard(data / "s0.tonytok",
                          rng.integers(0, 256, 30_000, dtype=np.int32))
        sync = self._run(tmp_path, "sync", depth=0, data_dir=str(data))
        overlapped = self._run(tmp_path, "pre", depth=3, data_dir=str(data))
        assert overlapped["loss"] == sync["loss"], (sync, overlapped)

    def test_loader_failure_mid_run_tears_down_cleanly(self, tmp_path, monkeypatch):
        """A shard that dies mid-run surfaces as the pipeline error on the
        step loop's thread and the finally-block teardown leaves no live
        producer thread behind."""
        from tony_tpu.data import write_token_shard
        from tony_tpu.data.loader import TokenLoader

        rng = np.random.default_rng(8)
        data = tmp_path / "data"
        data.mkdir()
        write_token_shard(data / "s0.tonytok",
                          rng.integers(0, 256, 30_000, dtype=np.int32))
        real_next = TokenLoader.next
        state = {"n": 0}

        def dying_next(self):
            state["n"] += 1
            if state["n"] > 2:
                raise OSError("mmap torn under us")
            return real_next(self)

        monkeypatch.setattr(TokenLoader, "next", dying_next)
        before = {t.name for t in threading.enumerate()}
        with pytest.raises(InputPipelineError):
            self._run(tmp_path, "die", depth=2, steps=6, data_dir=str(data))
        for _ in range(50):
            leaked = {t.name for t in threading.enumerate()} - before
            if not any("input-pipeline" in n for n in leaked):
                break
            time.sleep(0.05)
        assert not any("input-pipeline" in n for n in leaked), leaked


# ---------------------------------------------------------------------------
# goodput: the input_wait phase
# ---------------------------------------------------------------------------
class TestGoodputInputWait:
    def test_input_wait_spans_claim_their_phase_exactly(self):
        from tony_tpu.cluster.events import Event, EventType

        def ev(t, ts, **payload):
            return Event(EventType(t), payload, ts)

        events = [
            ev("APPLICATION_INITED", 1000),
            ev("TASK_REGISTERED", 1100, task="worker:0"),
            ev("GANG_COMPLETE", 1200, tasks=1),
            ev("TASK_FINISHED", 9000, task="worker:0", exit_code=0),
            ev("APPLICATION_FINISHED", 9500, status="SUCCEEDED"),
        ]
        spans = [
            {"name": "train.input_wait", "start_ms": 3000, "end_ms": 3400},
            {"name": "train.input_wait", "start_ms": 5000, "end_ms": 5100},
        ]
        led = obs_goodput.build_ledger("a", events, spans)
        assert led.phases_ms["input_wait"] == 500
        assert sum(led.phases_ms.values()) == led.wall_ms  # exact partition
        # the waits came OUT of productive, not out of thin air
        assert led.phases_ms["productive"] == 9000 - 1200 - 500

    def test_input_wait_is_a_known_phase(self):
        assert "input_wait" in obs_goodput.PHASE_ORDER


# ---------------------------------------------------------------------------
# collectives: the size-1-axis transfer guard
# ---------------------------------------------------------------------------
class TestStopTransferIfSingle:
    def _shardmapped(self, n):
        from jax.sharding import Mesh, PartitionSpec as P

        from jax import shard_map
        from tony_tpu.parallel import collectives

        mesh = Mesh(np.array(jax.devices()[:n]).reshape(n), ("ring",))

        def body(x):
            return collectives.stop_transfer_if_single(
                collectives.rotate, "ring", x)

        return shard_map(
            body, mesh=mesh, in_specs=(P("ring"),), out_specs=P("ring"),
            axis_names={"ring"}, check_vma=False,
        )

    def test_size_one_axis_is_identity_with_no_collective(self):
        f = self._shardmapped(1)
        x = jnp.arange(8.0)
        np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(x))
        assert "ppermute" not in str(jax.make_jaxpr(f)(x))

    def test_multi_shard_axis_still_transfers(self):
        from jax.sharding import Mesh, PartitionSpec as P

        from jax import shard_map
        from tony_tpu.parallel import collectives

        f = self._shardmapped(4)
        x = jnp.arange(8.0)
        assert "ppermute" in str(jax.make_jaxpr(f)(x))
        # guarded == unguarded rotate
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("ring",))
        direct = shard_map(
            lambda x: collectives.rotate(x, "ring"),
            mesh=mesh, in_specs=(P("ring"),), out_specs=P("ring"),
            axis_names={"ring"}, check_vma=False,
        )
        np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(direct(x)))

    def test_ring_attention_single_shard_has_no_ppermute(self):
        """The call-site payoff: a context axis collapsed to one shard (the
        1-chip bench, an elastic shrink) runs ring attention with zero
        collective launches."""
        from jax.sharding import PartitionSpec as P

        from jax import shard_map
        from tony_tpu.parallel import MeshSpec
        from tony_tpu.parallel.context import ring_attention

        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (1, 2, 32, 16)) for kk in ks)
        mesh = MeshSpec(context=1).build(devices=jax.devices()[:1])
        spec = P(None, None, "context", None)
        ring = shard_map(
            functools.partial(ring_attention, axis_name="context", causal=True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            axis_names={"context"}, check_vma=False,
        )
        assert "ppermute" not in str(jax.make_jaxpr(ring)(q, k, v))
        from tony_tpu.ops.attention import attention_reference

        got = jax.jit(ring)(q, k, v)
        want = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# bench provenance: movement + profile warnings in the gate
# ---------------------------------------------------------------------------
class TestGateMovementWarnings:
    def _rec(self, n, value, **extra):
        # warmup_s varies per round so two flat rounds are distinct records
        # (the gate's self-comparison guard drops content-identical peers)
        return (f"BENCH_r{n:02d}.json", {
            "n": n, "rc": 0,
            "parsed": {"metric": "m_mfu", "value": value, "unit": "mfu",
                       "vs_baseline": round(value / 0.45, 4),
                       "warmup_s": 10.0 + n, **extra},
        })

    def test_unmoved_headline_warns(self):
        from tony_tpu.histserver import gate

        traj = [self._rec(1, 0.4906), self._rec(2, 0.4906)]
        res = gate.evaluate(traj[-1][1], traj)
        assert res.passed  # warn, not fail
        moves = [c for c in res.checks if c.metric == "movement"]
        assert moves and "gate-without-movement" in moves[0].note
        assert moves[0].reference_from == "BENCH_r01.json"

    def test_content_identical_copied_round_still_warns(self):
        """Review-caught: a BENCH_r06 checked in as a byte-identical copy
        of r05 is THE no-movement offense — the peers self-comparison
        guard drops it by content, so the check must detect duplicates
        explicitly."""
        from tony_tpu.histserver import gate

        r5 = self._rec(5, 0.4906)
        r6 = ("BENCH_r06.json", {"n": 6, "rc": 0,
                                 "parsed": dict(r5[1]["parsed"])})
        res = gate.evaluate(r6[1], [self._rec(4, 0.4883), r5, r6])
        moves = [c for c in res.checks if c.metric == "movement"]
        assert moves and "content-identical" in moves[0].note
        assert moves[0].reference_from == "BENCH_r05.json"

    def test_moved_headline_is_quiet(self):
        from tony_tpu.histserver import gate

        traj = [self._rec(1, 0.4906), self._rec(2, 0.5301)]
        res = gate.evaluate(traj[-1][1], traj)
        assert res.passed
        assert not [c for c in res.checks if c.metric == "movement"]

    def test_perf_record_without_profile_reference_warns(self):
        from tony_tpu.histserver import gate

        traj = [self._rec(1, 0.49)]
        cur = self._rec(2, 0.52, kernel_smoke="8/8")[1]
        res = gate.evaluate(cur, traj)
        assert res.passed
        notes = [c for c in res.checks if c.metric == "provenance"]
        assert notes and "profile" in notes[0].note

    def test_profile_reference_satisfies_provenance(self):
        from tony_tpu.histserver import gate

        traj = [self._rec(1, 0.49)]
        cur = self._rec(2, 0.52, kernel_smoke="8/8",
                        profile={"before": "profiles/a", "after": "profiles/b"})[1]
        res = gate.evaluate(cur, traj)
        assert not [c for c in res.checks if c.metric == "provenance"]
