"""Data plane: TONYTOK shards and the TokenLoader's stream.

Deterministic fixtures; the stream is pinned by digest (it must not drift
unseen) and the env contract (shard_id/num_shards split) asserted directly.
"""

import hashlib
import threading
import time

import numpy as np
import pytest

from tony_tpu.cluster.metrics import HostMetricsSampler
from tony_tpu.data import TokenShardWriter, read_shard, write_token_shard
from tony_tpu.data.loader import TokenLoader

# (batch, seq), the stream's other arguments, and the first three batches over `_golden_shards` as sha256 of
# the int32 bytes. Taken at commit 75e5479 from BOTH loaders it had, the C++
# one and its Python twin, which agreed: a change of the draw (the splitmix
# hash, the slot arithmetic, epochs) or of the shard format shows here.
_GOLDEN = [
    ((4, 64), dict(seed=7, shard_id=0, num_shards=1, start_index=0), [
        "ae2f46c123c43b39ae4100bfb87445b5f17e5d1821f90c149012024826796755",
        "5f21551030e341e3ea3bf8011e240dfbbede2f3a56dfc881a8a19afb177b7f90",
        "2e0902d4926217df343928cc8cf0c713122b83292d2b5ca4e9702813e1d8526e",
    ]),
    ((2, 96), dict(seed=11, shard_id=1, num_shards=3, start_index=5), [
        "502a04da2082229668b5f0874c6e323e93e02f9a7bfba21511eb5c67334cd4a1",
        "47ee5fcd6624603a62c45b9a6567a61304cd10739f1ba9d68dcac5ede198f2d8",
        "7ecae8140b00ac4bd37e2b47e4a1d8a2462f402d9af2081f38af16bcf01acaf2",
    ]),
]


def _golden_shards(tmp_path):
    # one shard stored as uint16 and one as int32 (tokens past 65535)
    a = (np.arange(5000, dtype=np.int64) * 7919 % 60000).astype(np.int32)
    b = (np.arange(3000, dtype=np.int64) * 104729 % 128256).astype(np.int32)
    return [write_token_shard(tmp_path / "a.tonytok", a),
            write_token_shard(tmp_path / "b.tonytok", b)]


@pytest.fixture()
def shards(tmp_path):
    rng = np.random.default_rng(0)
    paths = []
    for i in range(3):
        toks = rng.integers(0, 32000, size=4096 + i * 512, dtype=np.int32)
        paths.append(write_token_shard(tmp_path / f"s{i}.tonytok", toks))
    return paths


class TestShardFormat:
    def test_roundtrip_u16(self, tmp_path):
        toks = np.arange(1000, dtype=np.int32) % 60000
        p = write_token_shard(tmp_path / "a.tonytok", toks)
        np.testing.assert_array_equal(read_shard(p), toks)

    def test_roundtrip_i32(self, tmp_path):
        toks = np.array([0, 70000, 128255], dtype=np.int32)
        p = write_token_shard(tmp_path / "b.tonytok", toks)
        got = read_shard(p)
        assert got.dtype == np.int32
        np.testing.assert_array_equal(got, toks)

    def test_rejects_garbage(self, tmp_path):
        p = tmp_path / "bad.tonytok"
        p.write_bytes(b"NOTATOKENFILE" * 10)
        with pytest.raises(ValueError):
            read_shard(p)

    def test_writer_rolls_shards(self, tmp_path):
        w = TokenShardWriter(tmp_path / "out", shard_tokens=1000)
        for _ in range(5):
            w.append(np.arange(400, dtype=np.int32))
        paths = w.close()
        assert len(paths) == 2
        total = sum(read_shard(p).size for p in paths)
        assert total == 2000


class TestTokenLoader:
    def test_batch_shape_and_range(self, shards):
        with TokenLoader(shards, batch=4, seq=128, seed=7) as ld:
            b = ld.next()
        assert b.shape == (4, 129) and b.dtype == np.int32
        assert b.min() >= 0 and b.max() < 32000

    def test_deterministic_across_instances(self, shards):
        with TokenLoader(shards, batch=2, seq=64, seed=3) as a:
            got_a = [a.next() for _ in range(4)]
        with TokenLoader(shards, batch=2, seq=64, seed=3) as b:
            got_b = [b.next() for _ in range(4)]
        for x, y in zip(got_a, got_b):
            np.testing.assert_array_equal(x, y)

    def test_seed_changes_stream(self, shards):
        with TokenLoader(shards, batch=2, seq=64, seed=1) as a, \
             TokenLoader(shards, batch=2, seq=64, seed=2) as b:
            assert not np.array_equal(a.next(), b.next())

    def test_dp_shards_resplit_one_global_stream(self, shards):
        """The global-order contract: K shards' local batches, concatenated
        in shard order, reconstruct the K=1 stream with batch G exactly —
        workers own disjoint row-slices of ONE global batch sequence."""
        G, STEPS = 8, 3
        with TokenLoader(shards, batch=G, seq=64, seed=5) as ref:
            want = [ref.next() for _ in range(STEPS)]
        for K in (2, 4):
            parts = []
            for sid in range(K):
                with TokenLoader(shards, batch=G // K, seq=64,
                                 shard_id=sid, num_shards=K, seed=5) as ld:
                    parts.append([ld.next() for _ in range(STEPS)])
            for t in range(STEPS):
                got = np.concatenate([parts[sid][t] for sid in range(K)])
                np.testing.assert_array_equal(got, want[t], err_msg=f"K={K} t={t}")

    def test_reshard_resume_no_repeat_no_skip(self, shards):
        """The elastic-replay contract (VERDICT r4 #1): a run that consumed
        3 global batches at K=2 and resumes at K=4 (same global batch G,
        start_index=3) continues the EXACT global stream — bitwise equal to
        the uninterrupted K=1 reference, nothing repeated, nothing skipped."""
        G, SPLIT, TOTAL = 8, 3, 6
        with TokenLoader(shards, batch=G, seq=64, seed=9) as ref:
            want = [ref.next() for _ in range(TOTAL)]
        # phase 1: K=2 consumes global batches [0, SPLIT)
        for sid in range(2):
            with TokenLoader(shards, batch=G // 2, seq=64,
                             shard_id=sid, num_shards=2, seed=9) as ld:
                for t in range(SPLIT):
                    np.testing.assert_array_equal(
                        ld.next(), want[t][sid * (G // 2):(sid + 1) * (G // 2)]
                    )
        # phase 2 ("node lost, gang shrunk... or grown"): K=4 resumes at
        # start_index=SPLIT and continues the same global stream
        for K in (4, 1):
            for sid in range(K):
                with TokenLoader(shards, batch=G // K, seq=64, shard_id=sid,
                                 num_shards=K, seed=9, start_index=SPLIT) as ld:
                    for t in range(SPLIT, TOTAL):
                        np.testing.assert_array_equal(
                            ld.next(),
                            want[t][sid * (G // K):(sid + 1) * (G // K)],
                            err_msg=f"K={K} sid={sid} t={t}",
                        )

    @pytest.mark.parametrize("case", range(len(_GOLDEN)))
    def test_the_stream_is_the_pinned_one(self, tmp_path, case):
        shape, stream, want = _GOLDEN[case]
        with TokenLoader(_golden_shards(tmp_path), *shape, **stream) as ld:
            got = [hashlib.sha256(ld.next().tobytes()).hexdigest() for _ in want]
        assert got == want

    def test_start_index_replays_stream_exactly(self, shards):
        """Resume contract (VERDICT r3 #6a): the draw is pure in
        (seed, batch index), so a loader restarted at index k reproduces
        the uninterrupted stream from batch k — no repeats, no skips."""
        with TokenLoader(shards, batch=2, seq=64, seed=3) as full:
            stream = [full.next() for _ in range(8)]
        with TokenLoader(shards, batch=2, seq=64, seed=3, start_index=4) as resumed:
            for i in range(4, 8):
                np.testing.assert_array_equal(resumed.next(), stream[i])

    def test_start_index_replay_python_fallback(self, shards):
        """Named for the Python twin of the C++ loader, which is the loader now: the
        same contract cut at another index."""
        with TokenLoader(shards, batch=2, seq=64, seed=3) as full:
            stream = [full.next() for _ in range(6)]
        with TokenLoader(shards, batch=2, seq=64, seed=3, start_index=3) as resumed:
            for i in range(3, 6):
                np.testing.assert_array_equal(resumed.next(), stream[i])

    def test_negative_start_index_raises(self, shards):
        with pytest.raises(ValueError, match="start_index"):
            TokenLoader(shards, batch=1, seq=8, start_index=-1)

    def test_empty_paths_raise(self):
        with pytest.raises(ValueError):
            TokenLoader([], batch=1, seq=8)

    def test_bad_shard_id_raises(self, shards):
        with pytest.raises(ValueError):
            TokenLoader(shards, batch=1, seq=8, shard_id=2, num_shards=2)

    @pytest.mark.parametrize("reads", [0, 1, 50])
    def test_close_cannot_block_and_starts_no_thread(self, shards, reads):
        before = threading.active_count()
        ld = TokenLoader(shards, batch=2, seq=64, seed=9)
        for _ in range(reads):
            ld.next()
        assert threading.active_count() == before
        t0 = time.perf_counter()
        ld.close()
        ld.close()  # a second close is harmless
        assert time.perf_counter() - t0 < 1.0

    def test_too_little_data_raises(self, tmp_path):
        p = write_token_shard(tmp_path / "tiny.tonytok", np.arange(4, dtype=np.int32))
        with pytest.raises((ValueError, RuntimeError)):
            TokenLoader([p], batch=1, seq=64)


class TestHostMetrics:
    def test_sample_fields(self):
        s = HostMetricsSampler()
        s.sample()  # first call primes the cpu delta
        m = s.sample()
        assert set(m) == {"cpu_util_pct", "mem_used_pct", "mem_total_mb", "rss_mb", "ncpus"}
        assert 0 <= m["cpu_util_pct"] <= 100
        assert 0 <= m["mem_used_pct"] <= 100
        assert m["ncpus"] >= 1

    def test_rss_is_this_process_resident_set(self):
        assert HostMetricsSampler().sample()["rss_mb"] > 0  # /proc/self/statm


class TestPrepareCorpus:
    def test_bytes_roundtrip_and_training_flow(self, tmp_path):
        from tony_tpu.data.prepare import prepare_corpus

        text = "hello tpu world! " * 400
        src = tmp_path / "doc.txt"
        src.write_text(text)
        manifest = prepare_corpus([src], tmp_path / "shards", append_eod=True)
        assert manifest["n_docs"] == 1
        assert manifest["vocab_size"] == 256
        assert manifest["total_tokens"] == len(text.encode()) + 1

        # the shards stream straight into the loader → training batches
        with TokenLoader(manifest["shards"], batch=2, seq=32) as loader:
            b = loader.next()
            assert b.shape == (2, 33)
            assert int(b.max()) < 256
            # window contents are literal utf-8 bytes of the corpus
            decoded = bytes(int(t) for t in b[0] if t != 0).decode("utf-8")
            assert "tpu" in decoded or "hello" in decoded or "world" in decoded

    def test_cli_entry(self, tmp_path, capsys):
        import json

        from tony_tpu.data.prepare import main

        src = tmp_path / "a.txt"
        src.write_text("abc " * 5000)
        rc = main([str(src), "--out", str(tmp_path / "out")])
        assert rc == 0
        manifest = json.loads(capsys.readouterr().out)
        assert manifest["total_tokens"] == 20001
