"""Config-system unit tests.

Mirrors the reference's TestTonyConfigurationFields (registry ↔ defaults
completeness) and TestUtils (memory/time parsing) — SURVEY.md §4.
"""

import json

import pytest

from tony_tpu import constants
from tony_tpu.config import TonyConfig, keys, parse_memory_string, parse_time_ms


class TestKeyRegistry:
    def test_every_known_key_has_a_default(self):
        # the TestTonyConfigurationFields analog: registry and defaults artifact
        # must never drift apart.
        missing = keys.all_known_keys() - set(keys.DEFAULTS)
        assert not missing, f"keys missing defaults: {sorted(missing)}"

    def test_every_default_is_a_known_key(self):
        unknown = set(keys.DEFAULTS) - keys.all_known_keys()
        assert not unknown, f"defaults for undeclared keys: {sorted(unknown)}"

    def test_defaults_are_strings(self):
        assert all(isinstance(v, str) for v in keys.DEFAULTS.values())


class TestParsing:
    @pytest.mark.parametrize(
        "s,expected",
        [("2g", 2 * 1024**3), ("512m", 512 * 1024**2), ("1024", 1024), ("3K", 3 * 1024), ("1gb", 1024**3)],
    )
    def test_memory(self, s, expected):
        assert parse_memory_string(s) == expected

    def test_memory_bad(self):
        with pytest.raises(ValueError):
            parse_memory_string("two gigs")

    @pytest.mark.parametrize(
        "s,expected", [("500", 500), ("500ms", 500), ("5s", 5000), ("2m", 120000), ("1h", 3600000)]
    )
    def test_time(self, s, expected):
        assert parse_time_ms(s) == expected


class TestLayering:
    def test_defaults_present(self):
        cfg = TonyConfig()
        assert cfg.get(keys.APPLICATION_FRAMEWORK) == "jax"
        assert cfg.get_int(keys.TASK_MAX_MISSED_HEARTBEATS) == 25

    def test_scheduler_indexed_key_defaults_true(self):
        """The r14 kill switch (docs/performance.md "Scheduler pass"):
        indexed scheduling is the default — parity-proven identical
        semantics — and false restores the reference policy verbatim."""
        cfg = TonyConfig()
        assert cfg.get_bool(keys.POOL_SCHEDULER_INDEXED) is True
        flipped = TonyConfig({keys.POOL_SCHEDULER_INDEXED: "false"})
        assert flipped.get_bool(keys.POOL_SCHEDULER_INDEXED) is False

    def test_train_keys_registered_with_defaults(self):
        """The r11 step-path knobs (docs/performance.md): registered,
        defaulted, and typed the way the executor reads them."""
        cfg = TonyConfig()
        assert cfg.get_int(keys.TRAIN_PREFETCH_DEPTH) == 2
        assert cfg.get_time_ms(keys.TRAIN_INPUT_WAIT_SPAN_MS) == 25
        for k in (keys.TRAIN_PREFETCH_DEPTH, keys.TRAIN_INPUT_WAIT_SPAN_MS):
            assert k in keys.DEFAULTS

    def test_layer_order_later_wins(self, tmp_path):
        site = tmp_path / "site.json"
        site.write_text(json.dumps({keys.APPLICATION_QUEUE: "prod", keys.AM_RETRY_COUNT: "2"}))
        job = tmp_path / "job.json"
        job.write_text(json.dumps({keys.AM_RETRY_COUNT: "3"}))
        cfg = TonyConfig.from_layers(str(site), str(job), [f"{keys.AM_RETRY_COUNT}=5"])
        assert cfg.get(keys.APPLICATION_QUEUE) == "prod"   # from site
        assert cfg.get_int(keys.AM_RETRY_COUNT) == 5       # --conf wins

    def test_nested_json_flattens(self, tmp_path):
        f = tmp_path / "job.json"
        f.write_text(json.dumps({"tony": {"worker": {"instances": 4, "memory": "2g"}}}))
        cfg = TonyConfig().load_file(str(f))
        assert cfg.instances("worker") == 4
        assert cfg.get_memory_bytes(keys.jobtype_key("worker", keys.MEMORY_SUFFIX)) == 2 * 1024**3

    def test_hadoop_xml_parity(self, tmp_path):
        # the reference's job files are Hadoop-style XML (e.g. tony-examples/
        # mnist-tensorflow/tony.xml); we accept the same shape.
        f = tmp_path / "tony.xml"
        f.write_text(
            """<?xml version="1.0"?>
            <configuration>
              <property><name>tony.worker.instances</name><value>2</value></property>
              <property><name>tony.application.name</name><value>mnist</value></property>
            </configuration>"""
        )
        cfg = TonyConfig().load_file(str(f))
        assert cfg.instances("worker") == 2
        assert cfg.get(keys.APPLICATION_NAME) == "mnist"

    def test_toml(self, tmp_path):
        f = tmp_path / "job.toml"
        f.write_text('[tony.worker]\ninstances = 2\n[tony.application]\nname = "t"\n')
        cfg = TonyConfig().load_file(str(f))
        assert cfg.instances("worker") == 2


class TestJobTypes:
    def _cfg(self):
        return TonyConfig(
            {
                "tony.ps.instances": "2",
                "tony.worker.instances": "4",
                "tony.tensorboard.instances": "1",
                "tony.evaluator.instances": "0",
            }
        )

    def test_job_types_discovered(self):
        assert self._cfg().job_types() == ("ps", "tensorboard", "worker")

    def test_zero_instance_types_excluded(self):
        assert "evaluator" not in self._cfg().job_types()

    def test_tracked_untracked_split(self):
        cfg = self._cfg()
        assert cfg.untracked_types() >= {"ps", "tensorboard"}
        assert cfg.tracked_types() == ("worker",)

    def test_dependency_keys(self):
        cfg = self._cfg().set(keys.dependency_key("worker", "ps"), "5s")
        assert cfg.dependencies() == {"worker": {"ps": 5000}}


class TestFreeze:
    def test_freeze_blocks_mutation(self):
        cfg = TonyConfig().freeze()
        with pytest.raises(RuntimeError):
            cfg.set("tony.application.name", "x")

    def test_roundtrip_artifact(self, tmp_path):
        cfg = TonyConfig({"tony.worker.instances": "4"})
        cfg.freeze()
        path = cfg.write_final(tmp_path)
        assert path.endswith(constants.TONY_FINAL_CONF)
        loaded = TonyConfig.load_final(path)
        assert loaded.frozen
        assert loaded.instances("worker") == 4
        # frozen artifact is the WHOLE truth: defaults were baked in at freeze
        assert loaded.get(keys.TASK_HEARTBEAT_INTERVAL_MS) == "1000"
