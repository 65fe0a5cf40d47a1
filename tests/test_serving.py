"""Continuous-batching engine: greedy parity with generate(), slot reuse."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import generate, llama
from tony_tpu.models.serving import ContinuousBatcher

CFG = dataclasses.replace(llama.LLAMA_TINY, max_seq=64)
KEY = jax.random.PRNGKey(0)


def _params():
    return llama.init(KEY, CFG)


def _prompt(n, seed):
    return jax.random.randint(jax.random.PRNGKey(seed), (1, n), 0, CFG.vocab_size)


class TestContinuousBatching:
    def test_greedy_parity_with_generate(self):
        # three requests, different prompt lengths, all slots available:
        # every request must reproduce batch-of-one greedy generate()
        params = _params()
        eng = ContinuousBatcher(params, CFG, num_slots=4, max_len=64)
        prompts = {_i: _prompt(n, seed=_i) for _i, n in enumerate((3, 7, 5))}
        rids = {i: eng.submit(list(np.asarray(p[0])), max_new_tokens=6)
                for i, p in prompts.items()}
        results = eng.run()
        for i, p in prompts.items():
            want = generate.generate(params, p, CFG, max_new_tokens=6)
            np.testing.assert_array_equal(
                np.asarray(results[rids[i]]), np.asarray(want[0]),
                err_msg=f"request {i} diverged from generate()",
            )

    @pytest.mark.slow
    def test_more_requests_than_slots(self):
        # 2 slots, 4 requests: retirement must free slots for later admissions
        params = _params()
        eng = ContinuousBatcher(params, CFG, num_slots=2, max_len=64)
        prompts = {i: _prompt(4 + i, seed=10 + i) for i in range(4)}
        budgets = {0: 3, 1: 7, 2: 2, 3: 5}
        rids = {i: eng.submit(list(np.asarray(p[0])), max_new_tokens=budgets[i])
                for i, p in prompts.items()}
        results = eng.run()
        assert set(results) == set(rids.values())
        for i, p in prompts.items():
            assert len(results[rids[i]]) == budgets[i]
            want = generate.generate(params, p, CFG, max_new_tokens=budgets[i])
            np.testing.assert_array_equal(
                np.asarray(results[rids[i]]), np.asarray(want[0]),
                err_msg=f"request {i} diverged under slot contention",
            )

    def test_retired_slot_lengths_flush_batched(self):
        # retirement only RECORDS the slot; the device-side length zeroing
        # happens in one batched update per step (per-retirement .set()
        # dispatches measured −25% engine tok/s, BASELINE r3-cont) — and a
        # slot re-admitted before the flush must keep its fresh length
        params = _params()
        eng = ContinuousBatcher(params, CFG, num_slots=2, max_len=64)
        r0 = eng.submit(list(np.asarray(_prompt(5, seed=30)[0])), max_new_tokens=2)
        eng.run()
        # r0 retired; its slot is recorded but possibly not yet flushed.
        # budget > decode_chunk so r1 is still RUNNING after one step (a
        # request finishing inside the step re-populates _retired_slots)
        r1 = eng.submit(list(np.asarray(_prompt(7, seed=31)[0])), max_new_tokens=20)
        eng.step()  # nothing running: flushes retirements, then admits r1 (maybe into slot0)
        assert not eng._retired_slots  # flushed
        lengths = np.asarray(eng.cache.lengths)
        for s in range(2):
            if s in eng.running:
                assert lengths[s] > 0, "re-admitted slot lost its length"
        r1_slot = next(req.slot for req in eng.running.values())
        eng.run()
        # full drain: one more step flushes the remaining retirement; idle
        # slots stay pinned at length 0 (no +1 regrowth)
        eng.step()
        assert not eng._retired_slots
        lengths = np.asarray(eng.cache.lengths)
        assert lengths[r1_slot] == 0
        assert all(lengths[s] == 0 for s in range(2) if s not in eng.running)
        assert len(eng.done[r1]) == 20

    def test_staggered_submission(self):
        # submit mid-flight: a new request joins while others are decoding
        params = _params()
        eng = ContinuousBatcher(params, CFG, num_slots=2, max_len=64)
        p0 = _prompt(5, seed=20)
        r0 = eng.submit(list(np.asarray(p0[0])), max_new_tokens=8)
        for _ in range(3):
            eng.step()
        p1 = _prompt(3, seed=21)
        r1 = eng.submit(list(np.asarray(p1[0])), max_new_tokens=4)
        while eng.step():
            pass
        for rid, p, n in ((r0, p0, 8), (r1, p1, 4)):
            want = generate.generate(params, p, CFG, max_new_tokens=n)
            np.testing.assert_array_equal(
                np.asarray(eng.done[rid]), np.asarray(want[0]))

    def test_eos_retires_early(self):
        params = _params()
        p = _prompt(4, seed=30)
        ref = generate.generate(params, p, CFG, max_new_tokens=8)
        eos = int(np.asarray(ref[0])[2])  # third generated token as fake EOS
        eng = ContinuousBatcher(params, CFG, num_slots=2, max_len=64, eos_id=eos)
        rid = eng.submit(list(np.asarray(p[0])), max_new_tokens=8)
        results = eng.run()
        out = results[rid]
        assert out[-1] == eos and len(out) <= 3

    def test_budget_validation(self):
        eng = ContinuousBatcher(_params(), CFG, num_slots=1, max_len=16)
        with pytest.raises(ValueError, match="max_len"):
            eng.submit(list(range(10)), max_new_tokens=10)

    def test_non_power_of_two_max_len(self):
        # bucket(20)=32 > max_len=24: the pad must cap at max_len, and the
        # result must still match generate()
        params = _params()
        eng = ContinuousBatcher(params, CFG, num_slots=1, max_len=24)
        p = _prompt(20, seed=50)
        rid = eng.submit(list(np.asarray(p[0])), max_new_tokens=4)
        results = eng.run()
        want = generate.generate(params, p, CFG, max_new_tokens=4)
        np.testing.assert_array_equal(np.asarray(results[rid]), np.asarray(want[0]))

    def test_int8_params_serve(self):
        from tony_tpu.ops import quant

        params = _params()
        qparams, _, _ = quant.quantize_tree(params, min_size=1 << 10)
        eng = ContinuousBatcher(qparams, CFG, num_slots=2, max_len=64)
        p = _prompt(4, seed=40)
        rid = eng.submit(list(np.asarray(p[0])), max_new_tokens=4)
        out = eng.run()[rid]
        assert len(out) == 4
        assert all(0 <= t < CFG.vocab_size for t in out)


class TestIdleAdmission:
    def test_start_up_and_drain_admit_with_nothing_running(self):
        """No chunk to hide behind: a pass that finds nothing in flight admits at
        once, takes the first token at once (a 1-token request is done there,
        with no decode chunk at all), dispatches the first chunk behind that and
        returns without reading it; the next pass does. After the engine has
        drained, the same again."""
        from tony_tpu.models import serving

        def counts():
            return [serving._CHUNKS.value(), serving._ADMISSIONS.value(under="idle"),
                    serving._ADMISSIONS.value(under="chunk")]

        params = _params()
        eng = ContinuousBatcher(params, CFG, num_slots=2, max_len=64, decode_chunk=4)
        for prompt_seed in (60, 61):  # start-up, then after a drain
            p = _prompt(5, seed=prompt_seed)
            before = counts()
            rid = eng.submit(list(np.asarray(p[0])), max_new_tokens=6)
            one = eng.submit([9, 8, 7], max_new_tokens=1)
            assert eng.step()
            assert [a - b for a, b in zip(counts(), before)] == [1, 2, 0]
            assert [len(r.out) for r in eng.running.values()] == [1] and len(eng.done[one]) == 1
            assert next(iter(eng.running.values())).slot_s > 0
            eng.run()
            assert [a - b for a, b in zip(counts(), before)] == [2, 2, 0]
            want = generate.generate(params, p, CFG, max_new_tokens=6)
            np.testing.assert_array_equal(np.asarray(eng.done[rid]), np.asarray(want[0]))
            assert not eng.running and not np.asarray(eng.cache.lengths).any()


class TestLengthBucketing:
    def test_parity_across_bucket_boundary(self):
        # prompt length just under a bucket edge + enough new tokens that the
        # chunked decode crosses power-of-two cache views (16 → 32 → 64):
        # every variant must agree with batch-of-one generate().
        # f32 like the MoE greedy-parity test above: the contract here is
        # engine PLUMBING (bucket growth, view write-back) ≡ generate() —
        # under bf16 the tiny model produces exactly-tied top logits
        # (quantized to the same bf16 value) and XLA's scan fusion breaks
        # the tie differently than the un-scanned reference, flipping one
        # boundary sample between the two argmaxes
        cfg = dataclasses.replace(CFG, dtype="float32")
        params = llama.init(KEY, cfg)
        eng = ContinuousBatcher(params, cfg, num_slots=2, max_len=64, decode_chunk=4)
        p = _prompt(13, seed=9)   # 13 + chunk → needed 17 → bucket 32 → later 64
        rid = eng.submit(list(np.asarray(p[0])), max_new_tokens=40)
        results = eng.run()
        want = generate.generate(params, p, cfg, max_new_tokens=40)
        np.testing.assert_array_equal(np.asarray(results[rid]), np.asarray(want[0]))

    def test_staged_prefill_admitted_after_retirement(self):
        # more requests than slots with tiny budgets: the speculative staged
        # prefill (dispatched during the chunk) must land in freed slots and
        # still match generate()
        params = _params()
        eng = ContinuousBatcher(params, CFG, num_slots=2, max_len=64, decode_chunk=2)
        prompts = {i: _prompt(4 + i, seed=20 + i) for i in range(5)}
        rids = {i: eng.submit(list(np.asarray(p[0])), max_new_tokens=3)
                for i, p in prompts.items()}
        results = eng.run()
        assert len(results) == 5
        for i, p in prompts.items():
            want = generate.generate(params, p, CFG, max_new_tokens=3)
            np.testing.assert_array_equal(np.asarray(results[rids[i]]), np.asarray(want[0]))


class TestRaggedDecode:
    """Pallas per-slot-length decode attention (interpret mode on CPU) and
    its engine integration."""

    def test_kernel_matches_masked_reference(self):
        from tony_tpu.ops.decode_attention import ragged_decode_attention
        from tony_tpu.models.serving import _masked_slot_attention

        S, H, Hkv, maxT, Dh = 3, 4, 2, 256, 128
        ks = jax.random.split(jax.random.PRNGKey(3), 5)
        q = jax.random.normal(ks[0], (S, H, Dh), jnp.float32)
        ck = jax.random.normal(ks[1], (S, Hkv, maxT, Dh), jnp.float32)
        cv = jax.random.normal(ks[2], (S, Hkv, maxT, Dh), jnp.float32)
        cur_k = jax.random.normal(ks[3], (S, Hkv, Dh), jnp.float32)
        cur_v = jax.random.normal(ks[4], (S, Hkv, Dh), jnp.float32)
        # lengths are CACHE-only counts; 0 = empty cache (self-attention only)
        lengths = jnp.array([0, 129, 250], jnp.int32)
        # chunk=128 keeps the MULTI-chunk DMA pipeline under test (length 250
        # → 2 slabs; the default 256 would make every slot single-slab here)
        for window in (0, 128):
            got = ragged_decode_attention(
                q, ck, cv, lengths, cur_k=cur_k, cur_v=cur_v, window=window,
                chunk=128,
            )
            want = _masked_slot_attention(
                q, ck, cv, lengths, H // Hkv, window=window, cur_k=cur_k, cur_v=cur_v
            )
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5,
                err_msg=f"window={window}",
            )

    def test_ragged_engine_greedy_parity(self):
        # full engine with attn='ragged' (interpret-mode kernel) must match
        # generate() exactly, like the bucketed engine does
        params = _params()
        cfg = dataclasses.replace(CFG, max_seq=128)
        eng = ContinuousBatcher(params, cfg, num_slots=2, max_len=128, attn="ragged")
        p = _prompt(5, seed=9)
        rid = eng.submit(list(np.asarray(p[0])), max_new_tokens=4)
        results = eng.run()
        want = generate.generate(params, p, cfg, max_new_tokens=4)
        np.testing.assert_array_equal(np.asarray(results[rid]), np.asarray(want[0]))


class TestMixtralServing:
    def test_mixtral_generate_matches_forward_argmax(self):
        # teacher-forced parity: greedy decode of the MoE model reproduces
        # the training forward's argmax chain (same property the llama
        # generate tests assert)
        from tony_tpu.models import mixtral

        mcfg = dataclasses.replace(mixtral.MIXTRAL_TINY, max_seq=32)
        params = mixtral.init(KEY, mcfg)
        # prompt length 20 > 16: prefill takes the ROUTED dispatch branch of
        # _ffn_with_cache while decode takes the all-expert branch — parity
        # with the training forward proves both agree
        prompt = jax.random.randint(jax.random.PRNGKey(4), (1, 20), 0, mcfg.vocab_size)
        out = generate.generate(params, prompt, mcfg, max_new_tokens=4)
        # teacher-forced: feed prompt + generated prefix, compare argmax
        toks = jnp.concatenate([prompt, out], axis=1)
        logits, _ = mixtral.forward(params, toks[:, :-1], mcfg)
        want = jnp.argmax(logits[0, prompt.shape[1] - 1:], axis=-1)
        np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(want))

    def test_mixtral_continuous_batcher(self):
        from tony_tpu.models import mixtral

        # f32: the contract here is engine PLUMBING ≡ generate() (slots,
        # admission, chunking, retirement). In bf16 a batched [S,1,D]
        # projection differs from the batch-1 one by 1 ulp (deterministic
        # XLA tiling), and the MoE router amplifies that into a token flip
        # on knife-edge prompts — rounding luck, not a plumbing property.
        mcfg = dataclasses.replace(mixtral.MIXTRAL_TINY, max_seq=64, dtype="float32")
        params = mixtral.init(KEY, mcfg)
        eng = ContinuousBatcher(params, mcfg, num_slots=2, max_len=64)
        prompts = {i: jax.random.randint(jax.random.PRNGKey(10 + i), (1, 4), 0, mcfg.vocab_size)
                   for i in range(3)}
        rids = {i: eng.submit(list(np.asarray(p[0])), max_new_tokens=5)
                for i, p in prompts.items()}
        results = eng.run()
        for i, p in prompts.items():
            want = generate.generate(params, p, mcfg, max_new_tokens=5)
            np.testing.assert_array_equal(
                np.asarray(results[rids[i]]), np.asarray(want[0]),
                err_msg=f"mixtral request {i} diverged from generate()",
            )


class TestSwaDecode:
    def test_windowed_generate_matches_forward(self):
        # a sliding-window model decoded BEYOND its window must still match
        # the training forward's argmax chain (r2 gap: decode read the full
        # cache; now both prefill and decode apply the band)
        swa_cfg = dataclasses.replace(CFG, sliding_window=8, max_seq=64)
        params = llama.init(KEY, swa_cfg)
        prompt = _prompt(6, seed=11)
        out = generate.generate(params, prompt, swa_cfg, max_new_tokens=8)
        toks = jnp.concatenate([prompt, out], axis=1)
        logits = llama.forward(params, toks[:, :-1], swa_cfg)
        want = jnp.argmax(logits[0, prompt.shape[1] - 1:], axis=-1)
        np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(want))


class TestChunkedPrefill:
    def test_chunked_prefill_greedy_parity(self):
        """prefill_chunk splits a long prompt into exact middle chunks + a
        padded final chunk, one per engine step — outputs must still equal
        batch-of-one generate() exactly."""
        params = _params()
        cfg = dataclasses.replace(CFG, max_seq=64)
        eng = ContinuousBatcher(
            params, cfg, num_slots=2, max_len=64, prefill_chunk=8,
        )
        prompts = {i: _prompt(n, seed=20 + i) for i, n in enumerate((23, 5, 17))}
        rids = {i: eng.submit(list(np.asarray(p[0])), max_new_tokens=5)
                for i, p in prompts.items()}
        results = eng.run()
        for i, p in prompts.items():
            want = generate.generate(params, p, cfg, max_new_tokens=5)
            np.testing.assert_array_equal(
                np.asarray(results[rids[i]]), np.asarray(want[0]),
                err_msg=f"chunked-prefill request {i} diverged",
            )

    def test_decode_interleaves_with_chunked_prefill(self):
        """While a long prompt prefills chunk by chunk, already-running
        requests keep producing tokens (the stall-bound property)."""
        params = _params()
        cfg = dataclasses.replace(CFG, max_seq=64)
        eng = ContinuousBatcher(
            params, cfg, num_slots=1, max_len=64, prefill_chunk=4, decode_chunk=2,
        )
        r0 = eng.submit(list(np.asarray(_prompt(3, seed=30)[0])), max_new_tokens=8)
        eng.step()  # nothing running: r0 prefilled and admitted, its first token taken
        r1 = eng.submit(list(np.asarray(_prompt(20, seed=31)[0])), max_new_tokens=3)
        produced_before = len(eng.running[0].out) if 0 in eng.running else 0
        eng.step()  # r0's chunk is dispatched; behind it r1 advances ONE prefill chunk
        produced_after = len(eng.running[0].out) if 0 in eng.running else 99
        assert produced_after > produced_before  # decode kept flowing
        results = eng.run()
        want0 = generate.generate(params, _prompt(3, seed=30), cfg, max_new_tokens=8)
        want1 = generate.generate(params, _prompt(20, seed=31), cfg, max_new_tokens=3)
        np.testing.assert_array_equal(np.asarray(results[r0]), np.asarray(want0[0]))
        np.testing.assert_array_equal(np.asarray(results[r1]), np.asarray(want1[0]))

    def test_final_chunk_pad_capped_at_max_len(self):
        """Review repro geometry: prompt 59, chunk 8, max_len 64 — the
        final chunk's pad must cap at max_len - pos or the padded write
        clamps and shifts real prompt K/V (silent corruption)."""
        params = _params()
        cfg = dataclasses.replace(CFG, max_seq=64)
        eng = ContinuousBatcher(params, cfg, num_slots=1, max_len=64, prefill_chunk=8)
        p = _prompt(59, seed=59)
        rid = eng.submit(list(np.asarray(p[0])), max_new_tokens=5)
        results = eng.run()
        want = generate.generate(params, p, cfg, max_new_tokens=5)
        np.testing.assert_array_equal(np.asarray(results[rid]), np.asarray(want[0]))


class TestTPServing:
    """Model-axis tensor-parallel decode (VERDICT r4 #3): the training
    column/row rules shard the decode projections, the cache shards over
    heads, the host loop is untouched — greedy output must match the
    single-device engine exactly."""

    def test_tp2_greedy_matches_single_device(self):
        from tony_tpu.parallel import MeshSpec

        params = _params()
        prompts = [[1, 2, 3, 4], [7, 8]]
        ref = ContinuousBatcher(params, CFG, num_slots=2, max_len=64, decode_chunk=4)
        rids = [ref.submit(p, max_new_tokens=6) for p in prompts]
        want = ref.run()

        mesh = MeshSpec(model=2).build(devices=jax.devices()[:2])
        eng = ContinuousBatcher(
            params, CFG, num_slots=2, max_len=64, decode_chunk=4, mesh=mesh,
        )
        rids2 = [eng.submit(p, max_new_tokens=6) for p in prompts]
        got = eng.run()
        # the cache (and so the decode step's operands) really shard over
        # the model axis — this is TP, not a replicated copy
        assert len(eng.cache.k.sharding.device_set) == 2
        for ra, rb in zip(rids, rids2):
            assert got[rb] == want[ra], (got[rb], want[ra])

    def test_tp_rejects_paged_and_bad_heads(self):
        from tony_tpu.parallel import MeshSpec

        params = _params()
        mesh = MeshSpec(model=2).build(devices=jax.devices()[:2])
        with pytest.raises(ValueError, match="dense"):
            ContinuousBatcher(params, CFG, num_slots=1, max_len=64,
                              kv="paged", page_len=32, mesh=mesh)
        cfg3 = dataclasses.replace(CFG, n_heads=3, n_kv_heads=3)
        with pytest.raises(ValueError, match="divide"):
            ContinuousBatcher(llama.init(KEY, cfg3), cfg3, num_slots=1,
                              max_len=64, mesh=mesh)

    def test_tp2_per_request_sampling_and_streaming(self):
        """The dynamic per-slot sampler and drain_stream ride the TP engine
        unchanged (host bookkeeping never sees the mesh)."""
        from tony_tpu.parallel import MeshSpec

        params = _params()
        mesh = MeshSpec(model=2).build(devices=jax.devices()[:2])
        eng = ContinuousBatcher(
            params, CFG, num_slots=2, max_len=64, decode_chunk=4, mesh=mesh,
        )
        g = eng.submit([1, 2, 3], max_new_tokens=6)  # greedy (engine default)
        s = eng.submit([4, 5], max_new_tokens=6, temperature=0.8, top_k=8)
        out = eng.run()
        ref = ContinuousBatcher(params, CFG, num_slots=2, max_len=64, decode_chunk=4)
        g_ref = ref.submit([1, 2, 3], max_new_tokens=6)
        ref_out = ref.run()
        assert out[g] == ref_out[g_ref]  # greedy slot exact despite sampled neighbor
        assert len(out[s]) == 6
        assert all(0 <= t < CFG.vocab_size for t in out[s])


class TestCancel:
    """Request cancellation (VERDICT r4 #4): a cancelled request frees its
    slot within one decode chunk wherever it was in the pipeline."""

    def test_cancel_running_frees_slot_within_one_chunk(self):
        params = _params()
        eng = ContinuousBatcher(params, CFG, num_slots=1, max_len=64, decode_chunk=4)
        r = eng.submit([1, 2, 3], max_new_tokens=50)
        eng.step()  # admitted with nothing running
        eng.step()  # first chunk
        assert 0 in eng.running and len(eng.running[0].out) == 5
        assert eng.cancel(r) is True
        eng.step()  # the cancelled slot is given up at this chunk's dispatch
        assert not eng.running
        assert r not in eng.done  # cancelled output is discarded, not surfaced
        # the slot is genuinely free: a new request admits and completes
        r2 = eng.submit([4, 5], max_new_tokens=3)
        out = eng.run()
        assert len(out[r2]) == 3

    def test_cancel_pending_and_staged(self):
        params = _params()
        eng = ContinuousBatcher(params, CFG, num_slots=1, max_len=64, decode_chunk=2)
        r1 = eng.submit([1, 2], max_new_tokens=4)
        r2 = eng.submit([3, 4], max_new_tokens=4)  # queued behind the 1-slot engine
        assert eng.cancel(r2) is True  # still pending
        out = eng.run()
        assert r1 in out and r2 not in out
        assert eng.cancel(999) is False  # unknown rid

    def test_cancel_staged_paged_releases_prefix_pins(self):
        params = _params()
        cfg = dataclasses.replace(CFG, max_seq=64)
        eng = ContinuousBatcher(params, cfg, num_slots=1, max_len=64,
                                decode_chunk=2, kv="paged", page_len=32)
        prompt = list(range(1, 40))  # > one full page → prefix registered
        rA = eng.submit(prompt, max_new_tokens=2)
        eng.run()
        avail0 = eng.allocator.available()
        rB = eng.submit(prompt, max_new_tokens=2)
        eng._stage_prefills(1)  # stage → prefix pages pinned, the rest prefilled
        assert eng._staged and eng._staged[0].matched, "test setup: no prefix hit"
        assert eng.cancel(rB) is True
        assert eng.allocator.available() == avail0  # pins released
        assert rA in eng.done


class TestHostLoopCompileStability:
    """The r5 root-cause: host-loop cache/token updates whose eager shapes
    varied per retirement/admission pattern re-compiled a tiny executable
    per distinct pattern (builders' run, older than this code, r5). The fixed-shape helpers must compile ONCE no matter
    how retirement patterns vary."""

    @pytest.mark.parametrize("kv", ["dense", "paged"])
    def test_helpers_compile_once_across_varying_patterns(self, kv):
        from tony_tpu.models import serving as S

        params = _params()
        eng = ContinuousBatcher(
            params, CFG, num_slots=4, max_len=64, kv=kv, page_len=16,
        )
        set0 = S._set_slot_token._cache_size()
        mask0 = (S._mask_zero_paged if kv == "paged" else S._mask_zero)._cache_size()
        # three waves with DIFFERENT lengths and counts → different
        # retirement patterns (1, then 3, then 2 slots retiring together)
        for wave in ([4], [3, 5, 6], [7, 4]):
            for j, n in enumerate(wave):
                eng.submit(list(np.asarray(_prompt(n, seed=n + j)[0])),
                           max_new_tokens=2 + j)
            while eng.step():
                pass
        helper = S._mask_zero_paged if kv == "paged" else S._mask_zero
        # <= 1: the jit caches are module-level, so an earlier test (or the
        # other kv parametrization) may have compiled the same shapes
        # already; the bug this guards against adds one entry PER pattern
        assert S._set_slot_token._cache_size() - set0 <= 1, (
            "per-admission token write re-traced: the slot index leaked in "
            "as a constant again"
        )
        assert helper._cache_size() - mask0 <= 1, (
            "retirement flush re-traced across patterns: the update shape "
            "is no longer fixed at [S]"
        )
