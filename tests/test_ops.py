"""Op-level tests: the reference path, the VJP wiring, and the kernels under
the Pallas interpreter. That the kernels compile for the chip is
tests/test_chip_compile.py; their numerics on the chip are bench.py --smoke."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.ops import attention as A
from tony_tpu.ops import layers as L


class TestLayers:
    def test_rms_norm_f32_accumulation(self):
        x = jnp.full((2, 8), 3.0, jnp.bfloat16)
        w = jnp.ones((8,), jnp.bfloat16)
        out = L.rms_norm(x, w)
        np.testing.assert_allclose(np.asarray(out, np.float32), 1.0, atol=1e-2)

    def test_rope_rotation_preserves_norm(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 16, 32))
        cos, sin = L.rope_frequencies(32, 16)
        y = L.apply_rope(x, cos, sin)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(x), axis=-1),
            np.linalg.norm(np.asarray(y), axis=-1),
            rtol=1e-5,
        )

    def test_rope_position_zero_is_identity(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 4, 8))
        cos, sin = L.rope_frequencies(8, 4)
        y = L.apply_rope(x, cos, sin, positions=jnp.zeros((4,), jnp.int32))
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6)

    def test_cross_entropy_ignores_masked(self):
        logits = jax.random.normal(jax.random.PRNGKey(2), (2, 4, 8))
        targets = jnp.array([[1, 2, -100, -100], [3, -100, -100, -100]])
        loss, n = L.cross_entropy_loss(logits, targets)
        assert int(n) == 3
        assert np.isfinite(float(loss))

    def test_cross_entropy_perfect_prediction(self):
        targets = jnp.array([[0, 1]])
        logits = jax.nn.one_hot(targets, 4) * 100.0
        loss, _ = L.cross_entropy_loss(logits, targets)
        assert float(loss) < 1e-3

    # chunk: the whole sequence, a divisor of it, a non-divisor (padded with
    # ignored targets); what is ignored; the cotangent the loss is handed
    @pytest.mark.parametrize("cotangent", [1.0, 3.0])
    @pytest.mark.parametrize("ignored", ["some", "a_row", "all"])
    @pytest.mark.parametrize("chunk", [16, 4, 5])
    def test_chunked_cross_entropy_matches_plain(self, chunk, ignored, cotangent):
        """Value, dx and dW of the chunked loss against cross_entropy_loss on
        whole logits, in float32: the chunked loss takes its gradient in its
        forward pass (a custom_vjp), the plain one by autodiff."""
        x, w, targets = _head_case(ignored)

        def plain(x, w):
            loss, n = L.cross_entropy_loss(jnp.einsum("btd,dv->btv", x, w), targets)
            return cotangent * loss, n

        def chunked(x, w):
            loss, n = L.chunked_cross_entropy_loss(x, w, targets, chunk=chunk)
            return cotangent * loss, n

        (want, want_n), (wx, ww) = jax.value_and_grad(plain, argnums=(0, 1), has_aux=True)(x, w)
        (got, got_n), (gx, gw) = jax.value_and_grad(chunked, argnums=(0, 1), has_aux=True)(x, w)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
        assert int(got_n) == int(want_n)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(wx), rtol=2e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(ww), rtol=2e-4, atol=1e-6)
        # the primal alone (no gradient asked) is the same number
        np.testing.assert_allclose(float(chunked(x, w)[0]), float(want), rtol=1e-5)

    @pytest.mark.parametrize("chunk", [16, 5])
    def test_chunked_cross_entropy_bfloat16_inside_the_benchmarks_band(self, chunk):
        """bfloat16 activations and head against the float32 plain loss on the
        same values: inside benchmark/check.py's bands at train_8k (relative
        RMS 0.02 on the value's side, 0.015 on the gradients')."""
        x, w, targets = _head_case("some", D=64, V=256)
        xb, wb = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)

        def plain(x, w):
            return L.cross_entropy_loss(jnp.einsum("btd,dv->btv", x, w), targets)[0]

        def chunked(x, w):
            return L.chunked_cross_entropy_loss(x, w, targets, chunk=chunk)[0]

        want, (wx, ww) = jax.value_and_grad(plain, argnums=(0, 1))(
            xb.astype(jnp.float32), wb.astype(jnp.float32))
        got, (gx, gw) = jax.value_and_grad(chunked, argnums=(0, 1))(xb, wb)
        assert gx.dtype == jnp.bfloat16 and gw.dtype == jnp.bfloat16
        assert abs(float(got) - float(want)) < 0.02 * abs(float(want))
        assert _rel_rms(gx, wx) < 0.015
        assert _rel_rms(gw, ww) < 0.015

    def test_chunked_cross_entropy_takes_three_vocabulary_products(self):
        """Differentiated, a chunk holds three products with the vocabulary
        among their dimensions (logits, dx, dW; four when the backward formed
        the logits again), and the primal alone holds one."""
        x, w, targets = _head_case("some", V=48)  # no other dimension is 48

        def chunked(x, w):
            return L.chunked_cross_entropy_loss(x, w, targets, chunk=4)[0]

        def vocabulary_products(jaxpr) -> int:
            n = 0
            for eqn in jaxpr.eqns:
                shapes = [v.aval.shape for v in (*eqn.invars, *eqn.outvars)]
                n += eqn.primitive.name == "dot_general" and any(48 in shape for shape in shapes)
                n += sum(vocabulary_products(sub) for sub in jax.core.jaxprs_in_params(eqn.params))
            return n

        assert vocabulary_products(jax.make_jaxpr(jax.grad(chunked, argnums=(0, 1)))(x, w).jaxpr) == 3
        assert vocabulary_products(jax.make_jaxpr(chunked)(x, w).jaxpr) == 1


def _head_case(ignored: str, B=2, T=16, D=8, V=32):
    """Final hidden states, a head and targets of which `ignored` are -100."""
    x = jax.random.normal(jax.random.PRNGKey(3), (B, T, D), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(4), (D, V), jnp.float32) * 0.1
    targets = jax.random.randint(jax.random.PRNGKey(5), (B, T), 0, V)
    if ignored == "some":
        targets = targets.at[0, :3].set(-100).at[1, 7].set(-100)
    elif ignored == "a_row":
        targets = targets.at[1].set(-100)
    else:
        targets = jnp.full_like(targets, -100)
    return x, w, targets


def _rel_rms(a, ref) -> float:
    a, ref = np.asarray(a, np.float64), np.asarray(ref, np.float64)
    return float(np.sqrt(np.mean((a - ref) ** 2) / np.mean(ref ** 2)))


class TestAttentionReference:
    def test_causal_masking(self):
        # changing a future token must not affect an earlier position's output
        q, k, v = (jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(0), i), (1, 2, 8, 4))
                   for i in range(3))
        out1 = A.attention_reference(q, k, v, causal=True)
        k2 = k.at[:, :, -1].set(99.0)
        v2 = v.at[:, :, -1].set(99.0)
        out2 = A.attention_reference(q, k2, v2, causal=True)
        np.testing.assert_allclose(np.asarray(out1[:, :, :-1]), np.asarray(out2[:, :, :-1]), atol=1e-5)
        assert not np.allclose(np.asarray(out1[:, :, -1]), np.asarray(out2[:, :, -1]))

    def test_repeat_kv(self):
        k = jax.random.normal(jax.random.PRNGKey(0), (2, 2, 4, 8))
        r = A.repeat_kv(k, 3)
        assert r.shape == (2, 6, 4, 8)
        np.testing.assert_array_equal(np.asarray(r[:, 0]), np.asarray(r[:, 1]))

    def test_mha_dispatch_cpu_uses_reference(self):
        q, k, v = (jnp.ones((1, 1, 8, 4)),) * 3
        out = A.mha(q, k, v, causal=True, impl="auto")
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(A.attention_reference(q, k, v, causal=True)), atol=1e-6
        )

    def test_flash_vjp_wiring_grads_flow(self):
        # on CPU mha falls back to reference, but the custom-vjp path must
        # still be differentiable when called explicitly via interpret mode
        q, k, v = (jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(1), i), (1, 2, 16, 4))
                   for i in range(3))

        def loss(q, k, v):
            return A.attention_reference(q, k, v, causal=True).sum()

        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        assert all(np.isfinite(np.asarray(g)).all() for g in grads)


def _absolute_reference(q, k, v, causal=True, window=0):
    """`attention_reference` with the kernels' ABSOLUTE positions (query i sees
    keys <= i), which differ from its bottom-aligned ones where Tk > Tq."""
    Tq, Tk, D = q.shape[2], k.shape[2], q.shape[3]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * D ** -0.5
    qp, kp = jnp.arange(Tq)[:, None], jnp.arange(Tk)[None, :]
    if causal:
        s = jnp.where(qp >= kp, s, A.NEG_INF)
    if window > 0:
        s = jnp.where(qp - kp < window, s, A.NEG_INF)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


class TestFlashAttentionInterpret:
    """Kernel numerics on CPU via the Pallas interpreter (conftest sets
    TONY_PALLAS_INTERPRET=1): forward + the FlashAttention-2 backward."""

    def _qkv(self, B=1, H=2, T=512, D=64):
        ks = [jax.random.fold_in(jax.random.PRNGKey(7), i) for i in range(3)]
        return tuple(jax.random.normal(k, (B, H, T, D), jnp.float32) * 0.5 for k in ks)

    def test_forward_matches_reference(self):
        q, k, v = self._qkv()
        out = A._flash_fwd_impl(q, k, v, True, 256, 256)[0]
        want = A.attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)

    def test_lse_matches_reference(self):
        q, k, v = self._qkv(T=256)
        _, lse = A._flash_fwd_impl(q, k, v, True, 256, 256)
        D = q.shape[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * D ** -0.5
        mask = jnp.tril(jnp.ones(s.shape[-2:], bool))
        want = jax.nn.logsumexp(jnp.where(mask, s, A.NEG_INF), axis=-1)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(want), atol=2e-4, rtol=2e-4)

    def test_backward_matches_reference(self):
        q, k, v = self._qkv()
        w = jnp.arange(q.shape[-1], dtype=jnp.float32)

        def loss_flash(q, k, v):
            return (A._flash_trainable(q, k, v, True) * w).sum()

        def loss_ref(q, k, v):
            return (A.attention_reference(q, k, v, causal=True) * w).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("dq dk dv".split(), gf, gr):
            scale = float(jnp.max(jnp.abs(b))) + 1e-9
            err = float(jnp.max(jnp.abs(a - b))) / scale
            assert err < 2e-4, f"{name} rel err {err}"

    @pytest.mark.parametrize("t,want", [
        (512, (256, 512)),      # the module's blocks, bq != bk
        (768, (256, 256)),      # divides 256 but not 512: bk halves
        (2048, (256, 512)),
        (640, (128, 128)),
        (8192, (256, 512)),     # the training cells' length
        (257, (1, 1)),          # halves to nothing
        (132, (1, 1)),          # divides itself but is no multiple of 8
    ])
    def test_block_sizes_shrink_to_divide(self, t, want):
        """The largest blocks under the module's that divide the length; a
        block under 8 rows sends every entry point to the XLA reference."""
        bq, bk = A._block_sizes(t, t)
        assert (bq, bk) == want
        assert t % bq == 0 and t % bk == 0

    def test_awkward_length_falls_back_to_reference(self):
        # T=257: _block_sizes degenerates; flash_attention must return the
        # reference result (and not crash or mis-tile)
        ks = [jax.random.fold_in(jax.random.PRNGKey(17), i) for i in range(3)]
        q, k, v = (jax.random.normal(kk, (1, 2, 257, 64), jnp.float32) * 0.5 for kk in ks)
        out = A.flash_attention(q, k, v, causal=True)
        want = A.attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)

    def test_bq_ne_bk_matches_reference(self):
        # asymmetric blocks (the production default) through fwd AND bwd
        q, k, v = self._qkv(T=512)
        w = jnp.arange(q.shape[-1], dtype=jnp.float32)

        def loss_flash(q, k, v):
            return (A._flash_trainable(q, k, v, True) * w).sum()

        def loss_ref(q, k, v):
            return (A.attention_reference(q, k, v, causal=True) * w).sum()

        assert A._block_sizes(512, 512) == (256, 512)  # exercising bq != bk
        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("dq dk dv".split(), gf, gr):
            scale = float(jnp.max(jnp.abs(b))) + 1e-9
            err = float(jnp.max(jnp.abs(a - b))) / scale
            assert err < 2e-4, f"{name} rel err {err}"

    def test_gqa_forward_matches_reference(self):
        B, H, Hkv, T, D = 1, 4, 2, 512, 64
        ks = [jax.random.fold_in(jax.random.PRNGKey(11), i) for i in range(3)]
        q = jax.random.normal(ks[0], (B, H, T, D), jnp.float32) * 0.5
        k = jax.random.normal(ks[1], (B, Hkv, T, D), jnp.float32) * 0.5
        v = jax.random.normal(ks[2], (B, Hkv, T, D), jnp.float32) * 0.5
        out = A._flash_fwd_impl(q, k, v, True, 256, 256)[0]
        want = A.attention_reference(q, A.repeat_kv(k, 2), A.repeat_kv(v, 2), causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)

    def test_gqa_backward_matches_reference(self):
        B, H, Hkv, T, D = 1, 4, 2, 512, 64
        ks = [jax.random.fold_in(jax.random.PRNGKey(13), i) for i in range(3)]
        q = jax.random.normal(ks[0], (B, H, T, D), jnp.float32) * 0.5
        k = jax.random.normal(ks[1], (B, Hkv, T, D), jnp.float32) * 0.5
        v = jax.random.normal(ks[2], (B, Hkv, T, D), jnp.float32) * 0.5
        w = jnp.arange(D, dtype=jnp.float32)

        def loss_flash(q, k, v):
            return (A._flash_trainable(q, k, v, True) * w).sum()

        def loss_ref(q, k, v):
            # reference path: broadcast kv, let autodiff reduce back over group
            return (
                A.attention_reference(q, A.repeat_kv(k, 2), A.repeat_kv(v, 2), causal=True) * w
            ).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("dq dk dv".split(), gf, gr):
            assert a.shape == b.shape, f"{name}: {a.shape} vs {b.shape}"
            scale = float(jnp.max(jnp.abs(b))) + 1e-9
            err = float(jnp.max(jnp.abs(a - b))) / scale
            assert err < 2e-4, f"{name} rel err {err}"

    def test_backward_causal_tk_gt_tq(self):
        # Tk > Tq + causal: k blocks wholly past the causal horizon must come
        # back as exact ZERO dk/dv (no q block visits them: the zeros are the
        # resident blocks' first write)
        B, H, Tq, Tk, D = 1, 2, 256, 1024, 64
        ks = [jax.random.fold_in(jax.random.PRNGKey(17), i) for i in range(3)]
        q = jax.random.normal(ks[0], (B, H, Tq, D), jnp.float32) * 0.5
        k = jax.random.normal(ks[1], (B, H, Tk, D), jnp.float32) * 0.5
        v = jax.random.normal(ks[2], (B, H, Tk, D), jnp.float32) * 0.5

        def loss_flash(q, k, v):
            return A._flash_trainable(q, k, v, True).sum()

        def loss_ref(q, k, v):
            return _absolute_reference(q, k, v).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("dq dk dv".split(), gf, gr):
            scale = float(jnp.max(jnp.abs(b))) + 1e-9
            err = float(jnp.max(jnp.abs(a - b))) / scale
            assert err < 2e-4, f"{name} rel err {err}"
        # keys at positions >= Tq are unreachable: gradients exactly zero
        np.testing.assert_array_equal(np.asarray(gf[1][:, :, Tq:, :]), 0.0)
        np.testing.assert_array_equal(np.asarray(gf[2][:, :, Tq:, :]), 0.0)

    def test_backward_noncausal(self):
        q, k, v = self._qkv(T=256)

        def loss_flash(q, k, v):
            return A._flash_trainable(q, k, v, False).sum()

        def loss_ref(q, k, v):
            return A.attention_reference(q, k, v, causal=False).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            scale = float(jnp.max(jnp.abs(b))) + 1e-9
            assert float(jnp.max(jnp.abs(a - b))) / scale < 2e-4


def _brute_force_classes(Tq, Tk, bq, bk, causal, window):
    """[Tq//bq, Tk//bk] of 0 hidden / 1 edge / 2 interior from the [Tq, Tk]
    mask itself (absolute positions, as the kernels count them)."""
    qp, kp = np.arange(Tq)[:, None], np.arange(Tk)[None, :]
    mask = np.ones((Tq, Tk), bool)
    if causal:
        mask &= qp >= kp
    if window > 0:
        mask &= qp - kp < window
    blocks = mask.reshape(Tq // bq, bq, Tk // bk, bk)
    return blocks.any(axis=(1, 3)).astype(int) + blocks.all(axis=(1, 3))


class TestFlashBlockClasses:
    @pytest.mark.parametrize("Tq,Tk", [(1024, 1024), (512, 1024)])
    @pytest.mark.parametrize("bq,bk", [(128, 256), (256, 128)])
    @pytest.mark.parametrize("window", ["none", "one_block", "three_blocks", "past_Tk"])
    @pytest.mark.parametrize("causal", [True, False])
    def test_counts_and_loop_bounds_match_the_mask(self, causal, window, bq, bk, Tq, Tk):
        """Every pair of an interior block visible, none of a hidden block,
        every visible pair in a visited block: `flash_block_classes` and the
        bounds the kernels loop over (`_k_runs`, from a q block's side: the
        forward and the backward walk the same runs) against the mask."""
        window = {"none": 0, "one_block": bk, "three_blocks": 3 * bk, "past_Tk": Tk + 5}[window]
        want = _brute_force_classes(Tq, Tk, bq, bk, causal, window)
        nq, nk = Tq // bq, Tk // bk
        by_q = np.zeros_like(want)
        for qb in range(nq):
            s, e1, e2, e = A._k_runs(qb, bq, bk, nk, causal, window)
            by_q[qb, s:e] = 1
            by_q[qb, e1:e2] = 2
        np.testing.assert_array_equal(by_q, want)
        assert A.flash_block_classes(Tq, Tk, bq, bk, causal, window) == {
            "interior": int((want == 2).sum()), "edge": int((want == 1).sum()),
            "hidden": int((want == 0).sum())}
        # where the band is wide, no block is crossed by both of its edges
        if causal and window and A._wide_band(bq, bk, window):
            qp, kp = np.arange(Tq)[:, None], np.arange(Tk)[None, :]
            above = (qp < kp).reshape(nq, bq, nk, bk).any(axis=(1, 3))
            below = (qp - kp >= window).reshape(nq, bq, nk, bk).any(axis=(1, 3))
            assert not (above & below & (want > 0)).any()

    def test_the_training_cells_shape(self):
        # 8192 positions, band 4096, the module's blocks: what PERF.md section 5 quotes
        assert A.flash_block_classes(8192, 8192, 256, 512, True, 4096) == {
            "interior": 168, "edge": 48, "hidden": 296}


class TestSegmentIds:
    """Packed-sequence (segment-id) masking: reference semantics + the flash
    kernels (the forward, the one backward) in interpret mode."""

    def _packed(self, B=1, H=2, T=512, D=64, n_seg=3, seed=23):
        ks = [jax.random.fold_in(jax.random.PRNGKey(seed), i) for i in range(4)]
        q, k, v = (jax.random.normal(kk, (B, H, T, D), jnp.float32) * 0.5 for kk in ks[:3])
        bounds = jnp.sort(jax.random.randint(ks[3], (n_seg - 1,), 1, T))
        seg = jnp.searchsorted(bounds, jnp.arange(T), side="right")
        seg = jnp.broadcast_to(seg[None, :], (B, T)).astype(jnp.int32)
        return q, k, v, seg

    def test_flash_fwd_matches_reference(self):
        q, k, v, seg = self._packed()
        out = A._flash_fwd_impl(q, k, v, True, 256, 256, seg)[0]
        want = A.attention_reference(q, k, v, causal=True, segment_ids=seg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)

    def test_flash_fwd_equals_per_segment_slices(self):
        # ground truth from first principles: run each segment separately
        q, k, v, seg = self._packed(B=1)
        out = A._flash_fwd_impl(q, k, v, True, 256, 256, seg)[0]
        seg_np = np.asarray(seg[0])
        for s in np.unique(seg_np):
            idx = np.where(seg_np == s)[0]
            lo, hi = idx.min(), idx.max() + 1
            piece = A.attention_reference(
                q[:, :, lo:hi], k[:, :, lo:hi], v[:, :, lo:hi], causal=True
            )
            np.testing.assert_allclose(
                np.asarray(out[:, :, lo:hi]), np.asarray(piece), atol=2e-5, rtol=2e-5
            )

    def test_flash_bwd_matches_reference(self):
        q, k, v, seg = self._packed(H=4)
        kv = k[:, ::2], v[:, ::2]  # GQA: 2 kv heads for 4 q heads
        w = jnp.arange(q.shape[-1], dtype=jnp.float32)

        def loss_flash(q, k, v):
            return (A._flash_trainable_seg(q, k, v, seg, True) * w).sum()

        def loss_ref(q, k, v):
            return (
                A.attention_reference(
                    q, A.repeat_kv(k, 2), A.repeat_kv(v, 2), causal=True, segment_ids=seg
                ) * w
            ).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, *kv)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, *kv)
        for name, a, b in zip("dq dk dv".split(), gf, gr):
            scale = float(jnp.max(jnp.abs(b))) + 1e-9
            err = float(jnp.max(jnp.abs(a - b))) / scale
            assert err < 2e-4, f"{name} rel err {err}"


class TestSlidingWindow:
    """Mistral/Mixtral-style sliding-window attention: reference semantics +
    both flash kernels (the forward, the one backward)."""

    def _qkv(self, B=1, H=4, Hkv=2, T=768, D=64, seed=31):
        ks = [jax.random.fold_in(jax.random.PRNGKey(seed), i) for i in range(3)]
        q = jax.random.normal(ks[0], (B, H, T, D), jnp.float32) * 0.5
        k = jax.random.normal(ks[1], (B, Hkv, T, D), jnp.float32) * 0.5
        v = jax.random.normal(ks[2], (B, Hkv, T, D), jnp.float32) * 0.5
        return q, k, v

    def test_reference_window_band(self):
        # row i attends exactly (i-window, i]
        q, k, v = self._qkv(H=1, Hkv=1, T=16, D=8)
        out = A.attention_reference(q, k, v, causal=True, window=4)
        # compare against a hand-built mask softmax
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (8 ** -0.5)
        i = jnp.arange(16)[:, None]
        j = jnp.arange(16)[None, :]
        mask = (i >= j) & (i - j < 4)
        p = jax.nn.softmax(jnp.where(mask, s, A.NEG_INF), axis=-1)
        want = jnp.einsum("bhqk,bhkd->bhqd", p, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)

    def test_flash_fwd_matches_reference(self):
        q, k, v = self._qkv()
        for window in (300, 256, 512):
            out = A._flash_fwd_impl(q, k, v, True, 256, 256, None, window)[0]
            want = A.attention_reference(
                q, A.repeat_kv(k, 2), A.repeat_kv(v, 2), causal=True, window=window
            )
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5,
                err_msg=f"window={window}",
            )

    def test_flash_bwd_matches_reference(self):
        q, k, v = self._qkv()
        w = jnp.arange(q.shape[-1], dtype=jnp.float32)
        for window in (300, 512):
            def loss_flash(q, k, v):
                return (A._flash_trainable(q, k, v, True, window) * w).sum()

            def loss_ref(q, k, v):
                return (
                    A.attention_reference(
                        q, A.repeat_kv(k, 2), A.repeat_kv(v, 2), causal=True, window=window
                    ) * w
                ).sum()

            gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
            gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
            for name, a, b in zip("dq dk dv".split(), gf, gr):
                scale = float(jnp.max(jnp.abs(b))) + 1e-9
                err = float(jnp.max(jnp.abs(a - b))) / scale
                assert err < 2e-4, f"window={window} {name} rel err {err}"

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("blocks", [(128, 256), (256, 128)])
    def test_every_block_class_through_both_kernels(self, blocks, dtype):
        """T 1024, window 512, GQA 4:2: every q block's k loop has hidden
        blocks, blocks an edge crosses and interior runs, two blocks long
        where the k block is the smaller. float32 at the file's tolerances;
        bfloat16 (operands straight into the MXU, p and ds cast to it)
        against the float32 reference at a bfloat16 rounding."""
        bq, bk = blocks
        T, window = 1024, 512
        classes = A.flash_block_classes(T, T, bq, bk, True, window)
        assert min(classes.values()) > 0, classes
        q, k, v = self._qkv(T=T)
        do = jax.random.normal(jax.random.PRNGKey(5), q.shape, jnp.float32)

        def ref(q, k, v):
            return A.attention_reference(
                q, A.repeat_kv(k, 2), A.repeat_kv(v, 2), causal=True, window=window)

        want, vjp = jax.vjp(ref, q, k, v)
        want = (want, *vjp(do))
        dt = jnp.dtype(dtype)
        q, k, v, do = (x.astype(dt) for x in (q, k, v, do))
        o, lse = A._flash_fwd_lanes(q, k, v, True, bq, bk, None, window)
        got = (o, *A._flash_bwd_impl(q, k, v, o, lse, do, True, bq, bk, None, window))
        tol = 2e-4 if dtype == "float32" else 2e-2
        for name, a, b in zip("o dq dk dv".split(), got, want):
            assert a.dtype == dt, name
            scale = float(jnp.max(jnp.abs(b)))
            err = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))) / scale
            assert err < tol, f"{name} rel err {err}"

    def test_window_with_segments(self):
        q, k, v = self._qkv(T=512)
        seg = jnp.where(jnp.arange(512) < 300, 1, 2)[None, :].astype(jnp.int32)
        out = A._flash_fwd_impl(q, k, v, True, 256, 256, seg, 128)[0]
        want = A.attention_reference(
            q, A.repeat_kv(k, 2), A.repeat_kv(v, 2),
            causal=True, segment_ids=seg, window=128,
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)

    def test_model_level_swa(self):
        import dataclasses as dc

        from tony_tpu.models import llama

        base = dc.replace(llama.LLAMA_TINY, max_seq=256, remat=False)
        params = llama.init(jax.random.PRNGKey(0), base)
        batch = llama.synthetic_batch(jax.random.PRNGKey(1), 2, 256, base)
        for impl in ("reference", "flash"):
            l_full, _ = llama.loss_fn(params, batch, dc.replace(base, attn_impl=impl))
            l_swa, _ = llama.loss_fn(
                params, batch, dc.replace(base, attn_impl=impl, sliding_window=64)
            )
            assert float(l_full) != float(l_swa), impl  # the window must bite
        l_ref, _ = llama.loss_fn(
            params, batch, dc.replace(base, attn_impl="reference", sliding_window=64)
        )
        l_fl, _ = llama.loss_fn(
            params, batch, dc.replace(base, attn_impl="flash", sliding_window=64)
        )
        np.testing.assert_allclose(float(l_ref), float(l_fl), rtol=2e-3)


# What nothing outside a kernel's module can change: its block sizes. One fresh interpreter with every
# lever that used to be read at import set to another legal value, and a tuner cache file that holds an
# entry for each consult there was; each case reads its own line of the child's one answer.
_LEVERS = {  # the variable: its module under tony_tpu.ops, the constant it set, another legal value
    "TONY_FLASH_BQ": ("attention", "_BLOCK_Q", 128), "TONY_FLASH_BK": ("attention", "_BLOCK_K", 256),
    "TONY_MOE_TILE": ("moe_gemm", "TILE_M", 64), "TONY_MOE_TILE_BWD": ("moe_gemm", "TILE_M_BWD", 64),
    "TONY_MOE_FCHUNK": ("moe_gemm", "F_CHUNK", 256), "TONY_DECODE_CHUNK": ("decode_attention", "CHUNK", 128),
}
_CACHE_ENTRIES = {
    "flash_fwd|cpu|1x1x1x256x256x64|float32": {"block_q": 128, "block_k": 128},
    "moe_gemm|cpu|8x128x128|bfloat16": {"tile": 64},
    "int8_matmul|cpu|256x512x256|float32": {"block_m": 128, "block_n": 128, "block_k": 256},
}


def _constants():
    import importlib

    return {lever: getattr(importlib.import_module(f"tony_tpu.ops.{module}"), name) for lever, (module, name, _) in _LEVERS.items()}


_CHILD = f"""
import importlib, json
import jax, jax.numpy as jnp
from jax.experimental import pallas as pl
from tony_tpu.ops import attention as A, quant as Q
from tony_tpu.parallel import expert as EX

out = {{lever: getattr(importlib.import_module("tony_tpu.ops." + module), name) for lever, (module, name, _) in {_LEVERS!r}.items()}}

def recording(fn, note):
    def wrapped(*a, **kw):
        note(*a, **kw)
        return fn(*a, **kw)
    return wrapped

# each choice read where it is handed on: the forward's blocks, the routing's tile, the matmul's grid
A._flash_fwd_impl = recording(A._flash_fwd_impl, lambda q, k, v, causal, bq, bk, *rest: out.update(flash=[bq, bk]))
q = jax.ShapeDtypeStruct((1, 1, 256, 64), jnp.float32)
jax.eval_shape(lambda q, k, v: A.flash_attention(q, k, v, causal=True), q, q, q)

EX.route_ragged = recording(EX.route_ragged, lambda *a, tile=None, **kw: out.update(moe=tile))
bf = jnp.bfloat16
bank = jax.ShapeDtypeStruct((8, 128, 128), bf)
jax.eval_shape(lambda x, r, wg, wu, wd: EX.moe_ffn(x, r, wg, wu, wd, EX.MoEConfig(num_experts=8, top_k=2)),
               jax.ShapeDtypeStruct((1, 16, 128), bf), jax.ShapeDtypeStruct((128, 8), bf), bank, bank, bank)

pl.pallas_call = recording(pl.pallas_call, lambda *a, **kw: out.update(int8=list(kw["grid"])))
jax.eval_shape(Q.int8_matmul, jax.ShapeDtypeStruct((256, 512), jnp.float32),
               Q.QTensor(jax.ShapeDtypeStruct((512, 256), jnp.int8), jax.ShapeDtypeStruct((256,), jnp.float32)))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def levered_child(tmp_path_factory):
    import json
    import os
    import subprocess
    import sys

    cache = tmp_path_factory.mktemp("tune") / "tune.json"
    cache.write_text(json.dumps({"version": 1, "entries": {k: {"params": v} for k, v in _CACHE_ENTRIES.items()}}))
    env = {**os.environ, **{lever: str(other) for lever, (_, _, other) in _LEVERS.items()},
           "TONY_TUNE_CACHE": str(cache), "JAX_PLATFORMS": "cpu", "TONY_PALLAS_INTERPRET": "1"}
    env.pop("TONY_TUNE_DISABLE", None)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run([sys.executable, "-c", _CHILD], env=env, cwd=root, capture_output=True, text=True, timeout=150)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.splitlines()[-1])


class TestNothingOutsideTheModuleChangesItsBlocks:
    @pytest.mark.parametrize("lever", sorted(_LEVERS))
    def test_a_set_lever_is_not_read(self, levered_child, lever):
        assert levered_child[lever] == _constants()[lever] != _LEVERS[lever][2]

    @pytest.mark.parametrize("choice", ["flash", "moe", "int8"])
    def test_a_cache_entry_changes_no_choice(self, levered_child, choice):
        from tony_tpu.ops import moe_gemm as MG
        from tony_tpu.ops import quant as Q

        source = {"flash": list(A._block_sizes(256, 256)), "moe": MG.TILE_M,
                  "int8": [256 // Q._BLOCK_M, 256 // Q._BLOCK_N, 512 // Q._BLOCK_K]}
        assert levered_child[choice] == source[choice]

    @pytest.mark.parametrize("verb", ["tune", "nosuch"])
    def test_no_verb_retunes_them(self, capsys, verb):
        from tony_tpu.cli.main import main as tony_main

        assert tony_main([verb]) == 2
        assert f"unknown command {verb!r}" in capsys.readouterr().err

    @pytest.mark.parametrize("key", ["tony.tune.enabled", "tony.tune.cache-file"])
    def test_no_key_names_them(self, key):
        """Held like any name the program never declared, and read by nothing."""
        from tony_tpu.config import TonyConfig, keys

        assert key not in keys.all_known_keys() and key not in TonyConfig()
        assert TonyConfig({key: "false"}).get(key) == "false"
