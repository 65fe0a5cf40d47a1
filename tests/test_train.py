"""Trainer/checkpoint tests: Orbax roundtrip, resume path, MFU accounting."""

import functools

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from tony_tpu.models import mlp
from tony_tpu.train import OptimizerConfig, Throughput, TrainState, make_train_step
from tony_tpu.train.checkpoint import CheckpointManager, restore_or_init
from tony_tpu.train.metrics import detect_peak_flops, transformer_flops_per_token

KEY = jax.random.PRNGKey(0)
CFG = mlp.MLPConfig(input_dim=8, hidden_dim=16, num_classes=4)


def make_state():
    opt = OptimizerConfig(warmup_steps=0, total_steps=10).build()
    return TrainState.create(mlp.init(KEY, CFG), opt), opt


class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        state, _ = make_state()
        mgr = CheckpointManager(str(tmp_path / "ckpt"), use_async=False)
        assert mgr.save(3, state)
        assert mgr.latest_step() == 3

        fresh, _ = make_state()
        restored = mgr.restore(fresh)
        np.testing.assert_array_equal(
            np.asarray(restored.params["layer_0"]["w"]), np.asarray(state.params["layer_0"]["w"])
        )
        assert int(restored.step) == int(state.step)
        mgr.close()

    def test_restore_after_training_steps(self, tmp_path):
        state, opt = make_state()
        step = make_train_step(functools.partial(mlp.loss_fn, cfg=CFG), opt)
        batch = mlp.synthetic_batch(KEY, 8, CFG)
        for _ in range(3):
            state, _m = step(state, batch)
        mgr = CheckpointManager(str(tmp_path / "ckpt"), use_async=False)
        mgr.save(int(state.step), state)

        # gang-restart resume: fresh init, restore, continue
        def init_fn():
            s, _ = make_state()
            return s

        restored, mgr2, start = restore_or_init(str(tmp_path / "ckpt"), init_fn, use_async=False)
        assert start == 3
        restored, m = step(restored, batch)
        assert int(m["step"]) == 4
        mgr.close()
        mgr2.close()

    def test_restore_or_init_without_dir(self):
        state, mgr, start = restore_or_init(None, lambda: 42)
        assert (state, mgr, start) == (42, None, 0)

    def test_max_to_keep(self, tmp_path):
        state, _ = make_state()
        mgr = CheckpointManager(str(tmp_path / "ckpt"), max_to_keep=2, use_async=False)
        for s in (1, 2, 3):
            mgr.save(s, state)
        mgr.wait()
        assert mgr.latest_step() == 3
        steps = sorted(mgr._mgr.all_steps())
        assert steps == [2, 3]
        mgr.close()


class TestMetrics:
    def test_flops_formula_training_vs_inference(self):
        t = transformer_flops_per_token(1_000_000, 12, 768, 2048, training=True)
        i = transformer_flops_per_token(1_000_000, 12, 768, 2048, training=False)
        assert t > i
        assert t >= 6_000_000

    def test_detect_peak_flops_cpu(self):
        """MFU is a device metric: the CPU (any unknown device) has no peak,
        and a meter given none reports no MFU instead of a nominal one."""
        with pytest.raises(ValueError, match="device_kind"):
            detect_peak_flops()

        class V5e:
            device_kind = "TPU v5 lite"

        assert detect_peak_flops(V5e()) == 197e12
        m = Throughput(tokens_per_step=10, flops_per_token=10, n_chips=1, peak_flops=None)
        m.start()
        m.step()
        assert "mfu" not in m.report()

    def test_throughput_meter(self):
        m = Throughput(tokens_per_step=1000, flops_per_token=1000, n_chips=2, peak_flops=1e6)
        m.start()
        m.step()
        m.step()
        r = m.report()
        assert r["tokens_per_sec"] > 0
        assert 0 <= r["mfu"]
        assert r["tokens_per_sec_per_chip"] * 2 == r["tokens_per_sec"]


class TestLoopWithData:
    def test_run_lm_training_on_tonytok_shards(self, tmp_path):
        """End-to-end: shard files on disk → loader → train steps → loss finite."""
        import numpy as np

        from tony_tpu.data import write_token_shard
        from tony_tpu.models import llama
        from tony_tpu.train.loop import LoopConfig, run_lm_training

        rng = np.random.default_rng(0)
        data = tmp_path / "data"
        data.mkdir()
        for i in range(2):
            write_token_shard(
                data / f"s{i}.tonytok", rng.integers(0, 256, 20_000, dtype=np.int32)
            )
        cfg = llama.LLAMA_TINY
        out = run_lm_training(
            llama, cfg,
            LoopConfig(steps=3, batch_size=2, seq_len=64, log_every=1,
                       warmup_steps=0, data_dir=str(data)),
        )
        assert np.isfinite(out["loss"])
        assert out["step"] == 3


class TestDataReplayOnResume:
    @pytest.mark.slow
    def test_interrupted_run_equals_uninterrupted(self, tmp_path):
        """VERDICT r3 #6a end-to-end: a run checkpointed at step 4 and
        resumed to step 8 sees the SAME data stream as a run that never
        stopped — identical final loss (bitwise: same params path, same
        batches, same op order on CPU)."""
        import numpy as np

        from tony_tpu.data import write_token_shard
        from tony_tpu.models import llama
        from tony_tpu.train.loop import LoopConfig, run_lm_training

        rng = np.random.default_rng(0)
        data = tmp_path / "data"
        data.mkdir()
        write_token_shard(data / "s0.tonytok", rng.integers(0, 256, 40_000, dtype=np.int32))
        cfg = llama.LLAMA_TINY
        # schedule_steps pins the LR schedule to the full 8-step plan in
        # every run — the interrupted 4-step run must not decay twice as fast
        base = dict(batch_size=2, seq_len=64, log_every=100, warmup_steps=0,
                    data_dir=str(data), checkpoint_every=4, schedule_steps=8)
        ref = run_lm_training(
            llama, cfg,
            LoopConfig(steps=8, checkpoint_dir=str(tmp_path / "A"), **base),
        )
        # interrupted: 4 steps, "crash", resume the same config to 8
        run_lm_training(
            llama, cfg, LoopConfig(steps=4, checkpoint_dir=str(tmp_path / "B"), **base)
        )
        got = run_lm_training(
            llama, cfg, LoopConfig(steps=8, checkpoint_dir=str(tmp_path / "B"), **base)
        )
        assert got["step"] == 8
        assert got["loss"] == ref["loss"], (got, ref)


class TestCrossShapeResume:
    @pytest.mark.slow
    def test_restore_onto_smaller_mesh_keeps_training(self, tmp_path):
        """VERDICT r3 #6b: a checkpoint written by an 8-device FSDP run
        restores onto a 4-device mesh (Orbax reshards into the target
        shardings) and training continues with the same loss as the
        8-device continuation — the node-lost → re-pack-smaller story."""
        import functools

        from tony_tpu.models import llama
        from tony_tpu.parallel import MeshSpec
        from tony_tpu.train.trainer import make_train_step, sharded_init

        cfg = llama.LLAMA_TINY
        opt = OptimizerConfig(warmup_steps=0, total_steps=10).build()
        rules = llama.sharding_rules(cfg)
        init_fn = lambda: llama.init(KEY, cfg)  # noqa: E731
        batch = llama.synthetic_batch(KEY, 8, 32, cfg)

        mesh8 = MeshSpec(fsdp=8).build()
        state8 = sharded_init(init_fn, rules, mesh8, opt)
        step8 = make_train_step(
            functools.partial(llama.loss_fn, cfg=cfg, mesh=mesh8), opt
        )
        for _ in range(2):
            state8, _ = step8(state8, batch)
        mgr = CheckpointManager(str(tmp_path / "ckpt"), use_async=False)
        mgr.save(2, state8)
        mgr.wait()
        state8, m8 = step8(state8, batch)  # the 8-device continuation

        mesh4 = MeshSpec(fsdp=4).build(devices=jax.devices()[:4])
        state4 = sharded_init(init_fn, rules, mesh4, opt)
        restored = mgr.restore(state4)
        # restored arrays carry the 4-device shardings, not the saved ones
        p = jax.tree.leaves(restored.params)[0]
        assert len(p.sharding.device_set) == 4
        step4 = make_train_step(
            functools.partial(llama.loss_fn, cfg=cfg, mesh=mesh4), opt
        )
        _, m4 = step4(restored, batch)
        np.testing.assert_allclose(
            float(m4["loss"]), float(m8["loss"]), rtol=1e-5
        )
        mgr.close()


class TestOptimizerMemory:
    def test_mu_dtype_bf16_halves_first_moment(self):
        import jax.numpy as jnp

        from tony_tpu.models import mlp
        from tony_tpu.train import OptimizerConfig, TrainState, make_train_step

        params = mlp.init(jax.random.PRNGKey(0), mlp.MLPConfig())
        opt = OptimizerConfig(warmup_steps=0, total_steps=5, mu_dtype="bfloat16").build()
        state = TrainState.create(params, opt)
        mus = [l for l in jax.tree.leaves(state.opt_state)
               if hasattr(l, "dtype") and l.dtype == jnp.bfloat16]
        assert mus, "no bf16 first-moment leaves found"
        step = make_train_step(
            lambda p, b: mlp.loss_fn(p, b, mlp.MLPConfig()), opt
        )
        batch = mlp.synthetic_batch(jax.random.PRNGKey(1), 4, mlp.MLPConfig())
        state, metrics = step(state, batch)
        assert bool(jnp.isfinite(metrics["loss"]))


class TestLoopPipelineParallel:
    @pytest.mark.slow
    def test_run_lm_training_with_stage_axis(self):
        """tony-submit-path pipeline training: stage_axis=2 routes the loop
        through the 1F1B schedule (make_pp_train_step) on the virtual mesh."""
        import dataclasses as dc

        import numpy as np

        from tony_tpu.models import llama
        from tony_tpu.train.loop import LoopConfig, run_lm_training

        cfg = dc.replace(llama.LLAMA_TINY, max_seq=64)
        out = run_lm_training(
            llama, cfg,
            LoopConfig(steps=3, batch_size=8, seq_len=64, log_every=1,
                       warmup_steps=0, stage_axis=2, pp_microbatches=2),
        )
        assert np.isfinite(out["loss"])
        assert out["step"] == 3

    def test_stage_axis_rejects_models_without_pp(self):
        import pytest as _pytest

        from tony_tpu.models import bert
        from tony_tpu.train.loop import LoopConfig, run_lm_training

        with _pytest.raises(ValueError, match="pp_value_and_grad"):
            run_lm_training(
                bert, bert.BERT_TINY,
                LoopConfig(steps=1, batch_size=8, seq_len=64, stage_axis=2),
            )


class TestTrainMetricsDrop:
    def test_drop_and_executor_read(self, tmp_path, monkeypatch):
        """The loop's step report reaches the executor's metrics payload:
        loop._drop_train_metrics writes atomically to the advertised path;
        Executor._read_train_metrics picks it up; launch_child clears it
        (stale reports must not outlive an attempt)."""
        from tony_tpu import constants
        from tony_tpu.train import loop as loop_mod

        path = tmp_path / "m" / "worker_0.json"
        path.parent.mkdir()
        monkeypatch.setenv(constants.ENV_TRAIN_METRICS_FILE, str(path))
        line = {"step": 7, "loss": 1.25, "tokens_per_sec": 123.0, "mfu": 0.41}
        loop_mod._drop_train_metrics(line)
        import json as _json

        assert _json.loads(path.read_text()) == line

        # executor-side read + clear-on-launch, without standing up a gang
        from tony_tpu.cluster.executor import TaskExecutor as Executor

        ex = Executor.__new__(Executor)
        ex._train_metrics_path = str(path)
        assert Executor._read_train_metrics(ex) == line
        path.write_text("{not json")
        assert Executor._read_train_metrics(ex) is None  # malformed → ignored

        path.write_text(_json.dumps(line))

        class _Cfg:
            def get(self, *a, **k):
                return ""

        ex.config = _Cfg()
        ex.staging_dir = str(tmp_path)
        try:
            Executor.launch_child(ex, "true", {})
        except Exception:
            pass  # Popen details don't matter; the unlink happens first
        assert not path.exists()

    def test_drop_is_noop_outside_container(self, monkeypatch):
        from tony_tpu import constants
        from tony_tpu.train import loop as loop_mod

        monkeypatch.delenv(constants.ENV_TRAIN_METRICS_FILE, raising=False)
        loop_mod._drop_train_metrics({"step": 1})  # must not raise
