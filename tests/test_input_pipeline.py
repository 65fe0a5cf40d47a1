"""The r11 step-path overhaul: overlapped input pipeline, kernel autotuner,
goodput input_wait attribution, bench-gate movement/provenance warnings, and
the size-1-axis collective guard.

Headline contracts:
- the overlapped pipeline feeds a BIT-IDENTICAL batch sequence to the
  synchronous path (loss-trajectory parity over a seeded run, both loader
  and synthetic sources);
- a producer failure propagates to the step loop's thread and teardown is
  clean mid-run;
- the autotuner cache round-trips to disk and the kernel entry points pick
  winners up (with stale entries degrading to the shipped defaults);
- `tony bench --gate` warns on a gate round whose headline metric didn't
  move vs the prior round, and on perf records without profile provenance.
"""

import functools
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.obs import goodput as obs_goodput
from tony_tpu.ops import tune
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
        from tony_tpu.data.native import TokenLoader

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
# autotuner: cache round-trip + kernel consult
# ---------------------------------------------------------------------------
class TestTuneCache:
    def test_miss_then_hit_and_persistence_roundtrip(self, tmp_path):
        path = str(tmp_path / "tune.json")
        c = tune.TuneCache(path)
        assert c.get("flash_fwd", (1, 2, 1, 256, 256, 64), "bfloat16", kind="v5e") is None
        c.put("flash_fwd", (1, 2, 1, 256, 256, 64), "bfloat16",
              {"block_q": 128, "block_k": 256}, ms=3.5, kind="v5e")
        c.save()
        # a FRESH object (new process analog) reads the same winner back
        c2 = tune.TuneCache(path)
        assert c2.get("flash_fwd", (1, 2, 1, 256, 256, 64), "bfloat16",
                      kind="v5e") == {"block_q": 128, "block_k": 256}
        # different device kind / shape / dtype are misses
        assert c2.get("flash_fwd", (1, 2, 1, 256, 256, 64), "bfloat16", kind="v4") is None
        assert c2.get("flash_fwd", (1, 2, 1, 512, 512, 64), "bfloat16", kind="v5e") is None
        assert c2.get("flash_fwd", (1, 2, 1, 256, 256, 64), "float32", kind="v5e") is None

    def test_save_merges_with_concurrent_writers(self, tmp_path):
        path = str(tmp_path / "tune.json")
        a, b = tune.TuneCache(path), tune.TuneCache(path)
        a.put("moe_gemm", (8, 64, 128), "bfloat16", {"tile": 64}, kind="v5e")
        a.save()
        b.put("int8_matmul", (128, 256, 256), "bfloat16",
              {"block_m": 128, "block_n": 128, "block_k": 256}, kind="v5e")
        b.save()
        c = tune.TuneCache(path)
        assert c.get("moe_gemm", (8, 64, 128), "bfloat16", kind="v5e")
        assert c.get("int8_matmul", (128, 256, 256), "bfloat16", kind="v5e")

    def test_corrupt_cache_is_cold_not_fatal(self, tmp_path):
        path = tmp_path / "tune.json"
        path.write_text("{torn")
        c = tune.TuneCache(str(path))
        assert c.get("flash_fwd", (1,), "bfloat16", kind="x") is None
        c.put("flash_fwd", (1,), "bfloat16", {"block_q": 8, "block_k": 128}, kind="x")
        c.save()
        assert tune.TuneCache(str(path)).get("flash_fwd", (1,), "bfloat16", kind="x")

    def test_lookup_honors_disable_env(self, tmp_path, monkeypatch):
        path = str(tmp_path / "tune.json")
        monkeypatch.setenv(tune.ENV_CACHE, path)
        c = tune.TuneCache(path)
        c.put("flash_fwd", (9,), "bfloat16", {"block_q": 8, "block_k": 128})
        c.save()
        assert tune.lookup("flash_fwd", (9,), "bfloat16") is not None
        monkeypatch.setenv(tune.ENV_DISABLE, "1")
        assert tune.lookup("flash_fwd", (9,), "bfloat16") is None

    def test_persist_winners_takes_lowest_ms_per_key(self, tmp_path):
        cache = tune.TuneCache(str(tmp_path / "t.json"))
        rows = [
            {"op": "flash_fwd", "shape": (1, 2, 1, 256, 256, 64),
             "dtype": "bfloat16", "params": {"block_q": 256, "block_k": 256}, "ms": 9.0},
            {"op": "flash_fwd", "shape": (1, 2, 1, 256, 256, 64),
             "dtype": "bfloat16", "params": {"block_q": 128, "block_k": 128}, "ms": 4.0},
            {"op": "flash_fwd", "shape": (1, 2, 1, 256, 256, 64),
             "dtype": "bfloat16", "params": {"block_q": 512, "block_k": 512},
             "ms": None, "error": "OOM"},
        ]
        tune.persist_winners(rows, cache)
        got = cache.get("flash_fwd", (1, 2, 1, 256, 256, 64), "bfloat16")
        assert got == {"block_q": 128, "block_k": 128}


class TestKernelConsult:
    def test_flash_entry_points_pick_the_tuned_blocks_up(self, tmp_path, monkeypatch):
        from tony_tpu.ops import attention as A

        path = str(tmp_path / "tune.json")
        monkeypatch.setenv(tune.ENV_CACHE, path)
        q = jnp.zeros((1, 2, 256, 64), jnp.bfloat16)
        shape = (1, 2, 1, 256, 256, 64)
        # cold cache → module defaults
        assert A._tuned_blocks("flash_fwd", q, 1, 256) == A._block_sizes(256, 256)
        c = tune.TuneCache(path)
        c.put("flash_fwd", shape, "bfloat16", {"block_q": 128, "block_k": 128})
        c.put("flash_bwd", shape, "bfloat16", {"block_q": 64, "block_k": 256})
        c.save()
        assert A._tuned_blocks("flash_fwd", q, 1, 256) == (128, 128)
        # fwd and bwd are tuned independently
        assert A._tuned_blocks("flash_bwd", q, 1, 256) == (64, 256)

    def test_explicit_env_override_beats_the_cache(self, tmp_path, monkeypatch):
        """Review-caught precedence: TONY_FLASH_BQ/BK (and TONY_MOE_TILE)
        are the operator's explicit debugging lever — a tune-cache hit must
        not silently win over them."""
        from tony_tpu.ops import attention as A
        from tony_tpu.ops import moe_gemm

        path = str(tmp_path / "tune.json")
        monkeypatch.setenv(tune.ENV_CACHE, path)
        c = tune.TuneCache(path)
        c.put("flash_fwd", (1, 2, 1, 256, 256, 64), "bfloat16",
              {"block_q": 128, "block_k": 128})
        c.put("moe_gemm", (8, 64, 128), "bfloat16", {"tile": 64})
        c.save()
        q = jnp.zeros((1, 2, 256, 64), jnp.bfloat16)
        assert A._tuned_blocks("flash_fwd", q, 1, 256) == (128, 128)
        monkeypatch.setenv("TONY_FLASH_BQ", "256")
        assert A._tuned_blocks("flash_fwd", q, 1, 256) == A._block_sizes(256, 256)
        assert moe_gemm.tuned_tile(8, 64, 128, "bfloat16") == 64
        monkeypatch.setenv("TONY_MOE_TILE", str(moe_gemm.TILE_M))
        assert moe_gemm.tuned_tile(8, 64, 128, "bfloat16") == moe_gemm.TILE_M

    def test_stale_entry_degrades_to_default_not_lowering_failure(
            self, tmp_path, monkeypatch):
        from tony_tpu.ops import attention as A

        path = str(tmp_path / "tune.json")
        monkeypatch.setenv(tune.ENV_CACHE, path)
        q = jnp.zeros((1, 2, 256, 64), jnp.bfloat16)
        shape = (1, 2, 1, 256, 256, 64)
        c = tune.TuneCache(path)
        # 192 does not divide 256; 100 is not lane-aligned — both invalid
        c.put("flash_fwd", shape, "bfloat16", {"block_q": 192, "block_k": 100})
        c.save()
        assert A._tuned_blocks("flash_fwd", q, 1, 256) == A._block_sizes(256, 256)

    def test_tuned_flash_matches_reference_numerics(self, tmp_path, monkeypatch):
        """A cache winner actually changes the kernel grid AND the math
        stays right (interpret mode on CPU)."""
        from tony_tpu.ops import attention as A

        path = str(tmp_path / "tune.json")
        monkeypatch.setenv(tune.ENV_CACHE, path)
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (1, 2, 256, 64), jnp.float32) * 0.5
        k = jax.random.normal(ks[1], (1, 1, 256, 64), jnp.float32) * 0.5
        v = jax.random.normal(ks[2], (1, 1, 256, 64), jnp.float32) * 0.5
        c = tune.TuneCache(path)
        c.put("flash_fwd", (1, 2, 1, 256, 256, 64), "float32",
              {"block_q": 128, "block_k": 128})
        c.save()
        assert A._tuned_blocks("flash_fwd", q, 1, 256) == (128, 128)
        got = A.flash_attention(q, k, v, causal=True)
        want = A.attention_reference(
            q, A.repeat_kv(k, 2), A.repeat_kv(v, 2), causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-3, rtol=2e-3)

    def test_int8_corrupt_cache_entry_degrades_not_crashes(self, tmp_path, monkeypatch):
        """Review-caught: a zero/misaligned tuned block must fall back to
        the shipped defaults, not ZeroDivisionError at trace time."""
        from tony_tpu.ops import quant

        path = str(tmp_path / "tune.json")
        monkeypatch.setenv(tune.ENV_CACHE, path)
        x = jnp.ones((128, 256), jnp.float32)
        qt = quant.quantize_int8(np.ones((256, 256), np.float32))
        c = tune.TuneCache(path)
        c.put("int8_matmul", (128, 256, 256), "float32",
              {"block_m": 0, "block_n": -128, "block_k": 100})
        c.save()
        out = quant.int8_matmul(x, qt)          # must not raise
        want = quant.int8_matmul_ref(x, qt)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=1e-2, rtol=1e-2)

    def test_moe_tuned_tile_validates_entries(self, tmp_path, monkeypatch):
        from tony_tpu.ops import moe_gemm

        path = str(tmp_path / "tune.json")
        monkeypatch.setenv(tune.ENV_CACHE, path)
        assert moe_gemm.tuned_tile(8, 64, 128, "bfloat16") == moe_gemm.TILE_M
        c = tune.TuneCache(path)
        c.put("moe_gemm", (8, 64, 128), "bfloat16", {"tile": 64})
        c.save()
        assert moe_gemm.tuned_tile(8, 64, 128, "bfloat16") == 64
        c.put("moe_gemm", (8, 64, 128), "bfloat16", {"tile": 60})  # not 8-aligned
        c.save()
        assert moe_gemm.tuned_tile(8, 64, 128, "bfloat16") == moe_gemm.TILE_M

    def test_sweep_flash_measures_and_persists_on_this_backend(self, tmp_path, monkeypatch):
        """The whole tony tune flow, CPU interpret mode: sweep a tiny
        geometry, persist, and see the kernel entry point consult it."""
        from tony_tpu.ops import attention as A

        path = str(tmp_path / "tune.json")
        monkeypatch.setenv(tune.ENV_CACHE, path)
        rows = tune.sweep_flash(1, 2, 1, 256, 64, dtype="float32", steps=1)
        measured = [r for r in rows if r.get("ms") is not None]
        assert {r["op"] for r in measured} == {"flash_fwd", "flash_bwd"}
        tune.persist_winners(rows)
        q = jnp.zeros((1, 2, 256, 64), jnp.float32)
        bq, bk = A._tuned_blocks("flash_fwd", q, 1, 256)
        best = min((r for r in measured if r["op"] == "flash_fwd"),
                   key=lambda r: r["ms"])
        assert (bq, bk) == (best["params"]["block_q"], best["params"]["block_k"])

    @pytest.mark.slow
    def test_tune_cli_dry_run_and_persist(self, tmp_path, capsys):
        from tony_tpu.cli.tune import main as tune_main

        cache = str(tmp_path / "tune.json")
        rc = tune_main(["--flash", "1,2,1,256,64", "--dtype", "float32",
                        "--steps", "1", "--dry-run"])
        assert rc == 0
        assert not os.path.exists(cache)
        rc = tune_main(["--flash", "1,2,1,256,64", "--dtype", "float32",
                        "--steps", "1", "--cache", cache])
        assert rc == 0
        data = json.loads(open(cache).read())
        assert any("flash_fwd" in k for k in data["entries"])

    def test_tune_cli_usage_errors(self, capsys):
        from tony_tpu.cli.tune import main as tune_main

        assert tune_main([]) == 2                       # nothing to sweep
        assert tune_main(["--flash", "1,2"]) == 2       # bad dims

    def test_tune_cli_registered_in_tony_main(self, capsys):
        from tony_tpu.cli.main import main as tony_main

        assert tony_main([]) == 0
        assert "tune" in capsys.readouterr().out


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
