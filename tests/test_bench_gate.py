"""``tony bench --gate`` as a repo check (tier-1, docs/history.md).

Every checked-in ``*BENCH_*.json`` must satisfy the gate schema, and each
trajectory must pass its own gate — a PR that lands a regressed bench record
(or a malformed one) fails here, which is the whole point of turning the perf
history into an enforced contract (ROADMAP item 5). The train family
(``BENCH_*.json``) has no checked-in round — the chip's numbers live in
``PERF_LEDGER.jsonl`` — so its gate runs over a trajectory built in a
temporary directory, in the shape ``python bench.py`` prints.
"""

import json
import os

import pytest

from tony_tpu.histserver import gate

pytestmark = [pytest.mark.history]

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def write_train_trajectory(directory) -> None:
    """Three ``BENCH_r0N.json`` rounds, each wrapping one bench.py line."""
    for n, (mfu, step_ms) in enumerate(((0.46, 1516.5), (0.48, 1460.0), (0.49, 1435.2)), 1):
        parsed = {
            "metric": "llama_train_mfu_1chip_1chip", "value": mfu, "unit": "mfu",
            "vs_baseline": round(mfu / 0.45, 4), "runs_mfu": [mfu] * 3,
            "preset": "1chip", "model": "llama", "batch": 12, "seq": 2048,
            "n_chips": 1, "device_kind": "TPU v5 lite", "warmup_s": 10.0 + n,
            "tokens_per_sec": round(12 * 2048 / (step_ms / 1000), 1),
            "step_time_ms": step_ms, "kernel_smoke": "8/8",
        }
        with open(os.path.join(str(directory), f"BENCH_r{n:02d}.json"), "w") as f:
            json.dump({"n": n, "cmd": "python bench.py", "rc": 0, "parsed": parsed}, f)


@pytest.fixture(scope="module")
def train_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("train_trajectory")
    write_train_trajectory(d)
    return str(d)


def _trajectory(train_dir):
    traj = gate.load_trajectory(train_dir)
    assert traj, "no BENCH_*.json trajectory"
    return traj


class TestCheckedInTrajectory:
    def test_every_record_satisfies_the_gate_schema(self, train_dir):
        for fname, rec in _trajectory(train_dir):
            errors = gate.validate_record(rec, wrapper=True)
            assert not errors, f"{fname}: {errors}"

    def test_rounds_are_ordered_and_unique(self, train_dir):
        rounds = [rec["n"] for _, rec in _trajectory(train_dir)]
        assert rounds == sorted(rounds)
        assert len(set(rounds)) == len(rounds)

    def test_gate_passes_on_current_trajectory(self, train_dir):
        """The newest record vs the rest of the trajectory: a perf history
        must satisfy its own contract."""
        traj = _trajectory(train_dir)
        result = gate.evaluate(traj[-1][1], traj)
        assert result.passed, "\n" + result.render()

    def test_gate_cli_passes_on_current_trajectory(self, train_dir, capsys):
        from tony_tpu.cli.history import main_bench

        assert main_bench(["--gate", "--trajectory-dir", train_dir]) == 0
        assert "PASS" in capsys.readouterr().out

    def test_gate_cli_fails_on_synthetic_regression(self, train_dir, tmp_path, capsys):
        from tony_tpu.cli.history import main_bench

        traj = _trajectory(train_dir)
        regressed = json.loads(json.dumps(traj[-1][1]))  # deep copy
        regressed["parsed"]["value"] *= 0.8
        regressed["parsed"]["vs_baseline"] *= 0.8
        path = tmp_path / "regressed.json"
        path.write_text(json.dumps(regressed))
        assert main_bench(["--gate", "--trajectory-dir", train_dir,
                           "--record", str(path)]) == 1
        assert "REGRESSION" in capsys.readouterr().out

    def test_gate_cli_rejects_malformed_record(self, train_dir, tmp_path, capsys):
        from tony_tpu.cli.history import main_bench

        path = tmp_path / "bad.json"
        path.write_text(json.dumps({"parsed": {"metric": "m"}}))
        assert main_bench(["--gate", "--trajectory-dir", train_dir,
                           "--record", str(path)]) == 2
        assert "gate schema" in capsys.readouterr().err

    def test_raw_bench_line_is_gateable(self, train_dir, capsys):
        """`python bench.py | tony bench --gate --record -`: a raw bench
        output line (no wrapper) gates directly."""
        from tony_tpu.cli.history import main_bench

        traj = _trajectory(train_dir)
        raw = dict(gate.parsed_of(traj[-1][1]))
        import io
        import sys as _sys

        stdin, _sys.stdin = _sys.stdin, io.StringIO(json.dumps(raw))
        try:
            assert main_bench(["--gate", "--trajectory-dir", train_dir,
                               "--record", "-"]) == 0
        finally:
            _sys.stdin = stdin


def _serve_trajectory():
    traj = gate.load_trajectory(REPO_ROOT, "SERVE_BENCH_*.json")
    assert traj, "no checked-in SERVE_BENCH_*.json trajectory"
    return traj


class TestServeBenchFamily:
    """The SERVE_BENCH family (`tony loadtest` records, docs/serving.md):
    same wrapper schema, its own headline metric and trajectory, plus the
    serve-specific gated directions (ttft_p99_ms regresses UPWARD)."""

    def test_family_patterns_do_not_collide(self):
        train = {name for name, _ in gate.load_trajectory(REPO_ROOT)}
        assert not train  # the train family's rounds live in PERF_LEDGER.jsonl
        serve = {name for name, _ in _serve_trajectory()}
        assert not train & serve
        assert all(n.startswith("SERVE_BENCH_") for n in serve)

    def test_every_record_satisfies_the_gate_schema(self):
        for fname, rec in _serve_trajectory():
            errors = gate.validate_record(rec, wrapper=True)
            assert not errors, f"{fname}: {errors}"
            p = gate.parsed_of(rec)
            assert p["metric"] == "serve_tokens_per_sec"
            # the serve headline extras every record must carry
            for key in ("tokens_per_sec", "ttft_p99_ms", "requests_failed"):
                assert key in p, f"{fname}: missing {key}"
            assert p["requests_failed"] == 0, \
                f"{fname}: a record with client-visible failures is not gateable"

    def test_gate_directions_cover_the_serve_headline(self):
        assert gate.GATE_METRICS.get("ttft_p99_ms") == -1
        assert gate.GATE_METRICS.get("tokens_per_sec") == +1
        # disagg rounds gate the prefill→decode handoff p50 downward too
        assert gate.GATE_METRICS.get("handoff_p50_ms") == -1

    def test_gate_fails_on_regressed_handoff_latency(self):
        """A disagg round whose KV-handoff tail blows up must fail the gate
        even when throughput held — and an improving handoff passes."""
        base = json.loads(json.dumps(_serve_trajectory()[-1][1]))
        base["parsed"]["handoff_p50_ms"] = 100.0
        cand = json.loads(json.dumps(base))
        cand["n"] = base["n"] + 1
        cand["parsed"]["handoff_p50_ms"] = 400.0
        result = gate.evaluate(cand, [("SERVE_BENCH_base.json", base)])
        assert not result.passed
        assert [c.metric for c in result.checks if not c.passed] == \
            ["handoff_p50_ms"]
        cand["parsed"]["handoff_p50_ms"] = 50.0
        assert gate.evaluate(cand, [("SERVE_BENCH_base.json", base)]).passed

    def test_disagg_rounds_carry_the_handoff_field(self):
        """Any serve round that moved KV pages through the handoff must also
        record the handoff latency it is gated on."""
        seen = 0
        for fname, rec in _serve_trajectory():
            p = gate.parsed_of(rec)
            if p.get("kv_handoff_pages"):
                seen += 1
                assert p.get("handoff_p50_ms", 0) > 0, fname
        assert seen > 0, "no disagg round in the SERVE_BENCH trajectory"

    def test_gate_cli_passes_on_serve_trajectory(self, capsys):
        from tony_tpu.cli.history import main_bench

        assert main_bench(["--gate", "--trajectory-dir", REPO_ROOT,
                           "--pattern", "SERVE_BENCH_*.json"]) == 0
        assert "PASS" in capsys.readouterr().out

    def test_gate_cli_fails_on_regressed_serve_record(self, tmp_path, capsys):
        """Throughput dropping OR the TTFT tail growing past tolerance must
        fail the gate — direction matters per metric."""
        from tony_tpu.cli.history import main_bench

        traj = _serve_trajectory()
        for mutate in (
            lambda p: p.update(value=p["value"] * 0.5,
                               tokens_per_sec=p["tokens_per_sec"] * 0.5,
                               vs_baseline=p["vs_baseline"] * 0.5),
            lambda p: p.update(ttft_p99_ms=p["ttft_p99_ms"] * 2.0),
        ):
            regressed = json.loads(json.dumps(traj[-1][1]))
            regressed["n"] = traj[-1][1]["n"] + 1
            mutate(regressed["parsed"])
            path = tmp_path / "regressed.json"
            path.write_text(json.dumps(regressed))
            assert main_bench(["--gate", "--trajectory-dir", REPO_ROOT,
                               "--pattern", "SERVE_BENCH_*.json",
                               "--record", str(path)]) == 1
            assert "REGRESSION" in capsys.readouterr().out

    def test_serve_records_do_not_gate_against_the_train_family(self, train_dir):
        """Trajectories compare within one `metric` name only: the serve
        record diffs against nothing in the BENCH_* family."""
        serve_rec = _serve_trajectory()[-1][1]
        result = gate.evaluate(serve_rec, _trajectory(train_dir))
        assert result.passed
        assert any("fresh trajectory" in c.note for c in result.checks)


def _cbench_trajectory():
    traj = gate.load_trajectory(REPO_ROOT, "CBENCH_*.json")
    assert traj, "no checked-in CBENCH_*.json trajectory"
    return traj


class TestCbenchFamily:
    """The CBENCH family (`tony cbench` records, docs/performance.md
    "Control-plane scalability"): same wrapper schema, its own headline
    metric ("weighted decisions/sec" — the geometric mean of the five
    control-plane throughputs), and per-benchmark gated directions (the
    journal-replay wall and latency tails regress UPWARD)."""

    def test_family_patterns_do_not_collide(self):
        train = {name for name, _ in gate.load_trajectory(REPO_ROOT)}
        serve = {name for name, _ in gate.load_trajectory(REPO_ROOT, "SERVE_BENCH_*.json")}
        cb = {name for name, _ in _cbench_trajectory()}
        assert not cb & (train | serve)
        assert all(n.startswith("CBENCH_") for n in cb)

    def test_every_record_satisfies_the_gate_schema(self):
        for fname, rec in _cbench_trajectory():
            errors = gate.validate_record(rec, wrapper=True)
            assert not errors, f"{fname}: {errors}"
            p = gate.parsed_of(rec)
            assert p["metric"] == "control_plane_ops_per_sec"
            # every record carries all five benchmarks + its provenance
            for key in ("sched_decisions_per_sec", "heartbeats_per_sec",
                        "journal_replay_ms", "journal_records_per_sec",
                        "sweep_jobs_per_sec", "resweep_ms",
                        "portal_scrape_ms", "portal_ams_per_sec"):
                assert key in p, f"{fname}: missing {key}"
            assert isinstance(p.get("sizes"), dict), f"{fname}: no sizes block"

    def test_gate_directions_cover_the_cbench_metrics(self):
        assert gate.GATE_METRICS.get("journal_replay_ms") == -1
        assert gate.GATE_METRICS.get("heartbeat_churn_p99_ms") == -1
        assert gate.GATE_METRICS.get("heartbeats_per_sec") == +1
        assert gate.GATE_METRICS.get("portal_ams_per_sec") == +1
        assert gate.GATE_METRICS.get("sweep_jobs_per_sec") == +1

    def test_trajectory_shows_the_fixes_moving_the_numbers(self):
        """Acceptance: r02 (post-fix) strictly better than r01 (baseline) on
        the headline metric AND on journal-replay wall-time — the round
        pair is the measured proof the refactors paid off."""
        by_round = {rec["n"]: gate.parsed_of(rec) for _, rec in _cbench_trajectory()}
        r01, r02 = by_round[1], by_round[2]
        assert r02["value"] > r01["value"]
        assert r02["journal_replay_ms"] < r01["journal_replay_ms"]
        assert r02["vs_baseline"] > 1.0

    def test_recorder_round_holds_the_scheduler_lane(self):
        """Acceptance (r15): the flight recorder rides the scheduler lane
        from r04 on (`sched_recorder: "on"`), and observability must not
        undo PR 14's win — r04's `sched_incremental_p50_ms` stays within the
        gate tolerance of r03's, compared directly when the rounds share a
        machine fingerprint (the gate itself only ever compares
        same-fingerprint peers)."""
        by_round = {rec["n"]: gate.parsed_of(rec) for _, rec in _cbench_trajectory()}
        r03, r04 = by_round[3], by_round[4]
        assert r04.get("sched_recorder") == "on"
        assert "sched_recorder" not in r03  # the pre-recorder round
        if gate.machine_of(r04) == gate.machine_of(r03):
            tol = gate.DEFAULT_METRIC_TOLERANCE_PCT["sched_incremental_p50_ms"]
            ceiling = r03["sched_incremental_p50_ms"] * (1 + tol / 100.0)
            assert r04["sched_incremental_p50_ms"] <= ceiling, (
                f"recorder-on round regressed the incremental pass: "
                f"{r04['sched_incremental_p50_ms']}ms > {ceiling}ms")
            # the cold full-pass lane holds too
            tol = gate.DEFAULT_METRIC_TOLERANCE_PCT["sched_decisions_per_sec"]
            floor = r03["sched_decisions_per_sec"] * (1 - tol / 100.0)
            assert r04["sched_decisions_per_sec"] >= floor

    def test_gate_cli_passes_on_cbench_trajectory(self, capsys):
        from tony_tpu.cli.history import main_bench

        assert main_bench(["--gate", "--trajectory-dir", REPO_ROOT,
                           "--pattern", "CBENCH_*.json"]) == 0
        assert "PASS" in capsys.readouterr().out

    def test_gate_cli_fails_on_regressed_cbench_record(self, tmp_path, capsys):
        """The headline dropping OR the journal-replay wall growing past
        tolerance must fail the gate — direction matters per metric."""
        from tony_tpu.cli.history import main_bench

        traj = _cbench_trajectory()
        for mutate in (
            lambda p: p.update(value=p["value"] * 0.5,
                               vs_baseline=p["vs_baseline"] * 0.5),
            lambda p: p.update(journal_replay_ms=p["journal_replay_ms"] * 3.0),
            lambda p: p.update(heartbeats_per_sec=p["heartbeats_per_sec"] * 0.5),
        ):
            regressed = json.loads(json.dumps(traj[-1][1]))
            regressed["n"] = traj[-1][1]["n"] + 1
            mutate(regressed["parsed"])
            path = tmp_path / "regressed.json"
            path.write_text(json.dumps(regressed))
            assert main_bench(["--gate", "--trajectory-dir", REPO_ROOT,
                               "--pattern", "CBENCH_*.json",
                               "--record", str(path)]) == 1
            assert "REGRESSION" in capsys.readouterr().out

    def test_provenance_warning_when_sizes_missing(self):
        """A cbench record without its tony.cbench.* sizes cannot be
        compared against the trajectory — the gate must say so (the same
        discipline as the profile-provenance warning for MFU rounds)."""
        traj = _cbench_trajectory()
        naked = json.loads(json.dumps(traj[-1][1]))
        naked["parsed"].pop("sizes", None)
        naked["n"] = traj[-1][1]["n"] + 1
        result = gate.evaluate(naked, traj)
        assert any(c.metric == "provenance" and "sizes" in c.note
                   for c in result.checks)

    def test_movement_warning_on_copied_cbench_round(self):
        """The anti-gate-without-movement check covers this family too: a
        content-identical copy of the latest round warns loudly."""
        traj = _cbench_trajectory()
        copied = json.loads(json.dumps(traj[-1][1]))
        result = gate.evaluate(copied, traj)
        assert any("gate-without-movement" in c.note for c in result.checks)

    def test_machine_fingerprint_scopes_comparisons(self):
        """Machine provenance (r14): control-plane lanes are CPU-bound, so
        a record gates only against same-fingerprint peers — a same-box
        drop is a real regression, a cross-box delta is a visible note,
        never a reference in either direction."""
        def rec(n, value, hps, cpus):
            return {"n": n, "rc": 0, "parsed": {
                "metric": "control_plane_ops_per_sec", "value": value,
                "unit": "ops/s", "vs_baseline": 1.0,
                "heartbeats_per_sec": hps, "sizes": {"apps": 1},
                "machine": {"cpus": cpus, "arch": "x86_64"}}}
        fast_box = [("CBENCH_r91.json", rec(1, 100.0, 1500.0, 8))]
        # same machine, halved heartbeat throughput: a real regression
        same = rec(2, 101.0, 750.0, 8)
        assert not gate.evaluate(same, fast_box).passed
        # different machine: not a regression reference — pass, with the
        # skipped rounds surfaced loudly
        moved = rec(2, 50.0, 750.0, 2)
        result = gate.evaluate(moved, fast_box)
        assert result.passed
        assert any("different hardware" in c.note for c in result.checks)
        # records WITHOUT fingerprints keep comparing with each other (the
        # pre-provenance trajectory stays self-consistent)
        bare = rec(1, 100.0, 1500.0, 8)
        bare["parsed"].pop("machine")
        bare2 = rec(2, 101.0, 700.0, 8)
        bare2["parsed"].pop("machine")
        assert not gate.evaluate(bare2, [("CBENCH_r92.json", bare)]).passed

    def test_cbench_records_do_not_gate_against_other_families(self, train_dir):
        cb_rec = _cbench_trajectory()[-1][1]
        result = gate.evaluate(cb_rec, _trajectory(train_dir))
        assert result.passed
        assert any("fresh trajectory" in c.note for c in result.checks)
