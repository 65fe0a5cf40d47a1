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


def _fused_bwd_cases():
    """float32 over the whole cross; bfloat16 where the benchmark trains
    (causal, GQA 4:1) over every band and shape."""
    for shape in ("square", "tk_gt_tq", "segments"):
        for causal in (True, False):
            for window in ("none", "inside_a_block", "several_blocks"):
                for n_rep in (1, 4):
                    yield pytest.param(causal, window, n_rep, shape, "float32",
                                       id=f"{shape}-{'causal' if causal else 'full'}-{window}-rep{n_rep}-float32")
    for shape in ("square", "tk_gt_tq", "segments"):
        for window in ("none", "inside_a_block", "several_blocks"):
            yield pytest.param(True, window, 4, shape, "bfloat16", id=f"{shape}-causal-{window}-rep4-bfloat16")


class TestFusedFlashBackward:
    """The one backward call (`flash_bwd`: a tile's s, p, dp and ds formed once
    and fed to dv, dk and dq) against `jax.grad` of the plain reference."""

    BQ, BK, TQ, D = 128, 128, 512, 64

    @pytest.mark.parametrize("causal,window,n_rep,shape,dtype", _fused_bwd_cases())
    def test_gradients_match_the_reference(self, causal, window, n_rep, shape, dtype):
        """4 x 4 (or 4 x 6) blocks of 128: with a band of 300 one run holds
        interior, diagonal and window-edge pairs and hidden ones on both sides;
        a band of 64 lies inside a block, where one tile owes both edges."""
        bq, bk, Tq, D = self.BQ, self.BK, self.TQ, self.D
        Tk = Tq + 2 * bk if shape == "tk_gt_tq" else Tq
        window = {"none": 0, "inside_a_block": 64, "several_blocks": 300}[window]
        if causal and window == 300 and shape != "tk_gt_tq":
            classes = A.flash_block_classes(Tq, Tk, bq, bk, causal, window)
            assert min(classes.values()) > 0, classes
        H, Hkv = 4, 4 // n_rep
        ks = [jax.random.fold_in(jax.random.PRNGKey(52), i) for i in range(4)]
        q = jax.random.normal(ks[0], (1, H, Tq, D), jnp.float32) * 0.5
        k = jax.random.normal(ks[1], (1, Hkv, Tk, D), jnp.float32) * 0.5
        v = jax.random.normal(ks[2], (1, Hkv, Tk, D), jnp.float32) * 0.5
        do = jax.random.normal(ks[3], q.shape, jnp.float32)
        seg = None
        if shape == "segments":  # three segments, their edges inside blocks
            seg = jnp.searchsorted(jnp.array([90, 301]), jnp.arange(Tq), side="right")[None, :].astype(jnp.int32)

        def ref(q, k, v):
            k, v = A.repeat_kv(k, n_rep), A.repeat_kv(v, n_rep)
            if Tk > Tq:
                return _absolute_reference(q, k, v, causal, window)
            return A.attention_reference(q, k, v, causal=causal, segment_ids=seg, window=window)

        want = jax.vjp(ref, q, k, v)[1](do)
        dt = jnp.dtype(dtype)
        q, k, v, do = (x.astype(dt) for x in (q, k, v, do))
        o, lse = A._flash_fwd_lanes(q, k, v, causal, bq, bk, seg, window)
        got = A._flash_bwd_impl(q, k, v, o, lse, do, causal, bq, bk, seg, window)
        for name, a, b in zip("dq dk dv".split(), got, want):
            assert a.dtype == dt and a.shape == b.shape, name
            a = np.asarray(a.astype(jnp.float32))
            if dtype == "float32":
                err, tol = np.max(np.abs(a - b)) / np.max(np.abs(b)), 2e-4
            else:  # the benchmark's band for a gradient (`grad_rel_rms`, benchmark/check.py)
                err, tol = np.sqrt(np.mean((a - b) ** 2) / np.mean(np.asarray(b) ** 2)), 0.015
            assert err < tol, f"{name} rel err {err}"
        if causal and Tk > Tq:  # keys no query reaches: exact zeros
            assert not np.asarray(got[1][:, :, Tq:]).any() and not np.asarray(got[2][:, :, Tq:]).any()


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


class TestGroupedSwiGLU:
    """ops/moe_gemm's forward under the interpreter against jax.lax.ragged_dot on
    the same sorted rows: the geometry it always had (an expert's whole slab one
    block), and a wide one cut small, where the width is walked in blocks (what
    6144 x 2048 needs to fit VMEM), the banks are every layer's with a layer
    index, and the row tiles past the groups are skipped."""

    @staticmethod
    def _case(E, D, F, tile, sizes, layers=1, seed=0):
        from tony_tpu.ops import moe_gemm as MG

        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        padded = [max(-(-n // tile), 1) * tile for n in sizes]
        rows = (sum(sizes) // tile + E + 3) * tile                   # slack past the groups, as a static bound leaves
        xs = (jax.random.normal(ks[0], (rows, D)) * 0.5).astype(jnp.bfloat16)
        wg = (jax.random.normal(ks[1], (layers, E, D, F)) / D ** 0.5).astype(jnp.bfloat16)
        wu = (jax.random.normal(ks[2], (layers, E, D, F)) / D ** 0.5).astype(jnp.bfloat16)
        wd = (jax.random.normal(ks[3], (layers, E, F, D)) / F ** 0.5).astype(jnp.bfloat16)
        gs = jnp.asarray(padded, jnp.int32)
        tg = MG.tile_group_map(gs, rows // tile, tile)
        return MG, xs, wg, wu, wd, gs, tg, sum(padded)

    @staticmethod
    def _ragged(xs, wg, wu, wd, gs):
        g = jax.nn.silu(jax.lax.ragged_dot(xs, wg, gs))
        return jax.lax.ragged_dot((g * jax.lax.ragged_dot(xs, wu, gs)).astype(xs.dtype), wd, gs)

    def test_the_geometry_it_always_had(self):
        MG, xs, wg, wu, wd, gs, tg, live_rows = self._case(4, 128, 256, 16, [20, 0, 16, 7])
        assert MG.width_block(128, 256, 2) == 256 and MG.width_block(1024, 2048, 2) == 2048      # one block: the slab resident
        got = MG.moe_swiglu_grouped(xs, wg[0], wu[0], wd[0], tg, 16)
        want = self._ragged(xs, wg[0], wu[0], wd[0], gs)
        np.testing.assert_allclose(np.asarray(got[:live_rows], jnp.float32), np.asarray(want[:live_rows], jnp.float32),
                                   atol=3e-2, rtol=3e-2)

    @pytest.mark.parametrize("layer", [0, 2])
    def test_a_wide_geometry_cut_small(self, monkeypatch, layer):
        MG, xs, wg, wu, wd, gs, tg, live_rows = self._case(4, 256, 512, 16, [33, 5, 0, 16], layers=3, seed=layer)
        assert MG.width_block(6144, 2048, 2) == 512                                              # the served width: four blocks
        monkeypatch.setattr(MG, "_WEIGHT_VMEM", 3 * 256 * 128 * 2 * 2)                           # room for a block of 128
        assert MG.width_block(256, 512, 2) == 128
        got = MG.moe_swiglu_rows(xs, wg, wu, wd, tg, 16, jnp.int32(live_rows // 16), jnp.int32(layer), name="moe_swiglu_decode")
        want = self._ragged(xs, wg[layer], wu[layer], wd[layer], gs)
        np.testing.assert_allclose(np.asarray(got[:live_rows], jnp.float32), np.asarray(want[:live_rows], jnp.float32),
                                   atol=3e-2, rtol=3e-2)
        # another layer's bank gives another answer: the index is read
        other = MG.moe_swiglu_rows(xs, wg, wu, wd, tg, 16, jnp.int32(live_rows // 16), jnp.int32(1))
        assert np.abs(np.asarray(other[:live_rows], jnp.float32) - np.asarray(want[:live_rows], jnp.float32)).max() > 0.1


class TestAGroupWithNoRows:
    """A held layer is forward only, and a held expert that no row chose has no
    row tile there (parallel/expert.route_ragged): the grouped product fetches
    the slabs of the held-and-chosen experts alone. With a backward, every
    group keeps a tile: the expert's weight-gradient blocks are initialised at
    its first. Choices are set by hand: router logits are 4 x the first E
    columns of a row, which hold 2 at the first choice and 1 at the second."""

    E, D, F, T, K, TILE = 8, 128, 128, 24, 2, 16
    HELD = (1, 5)                                                        # experts 1 .. 5 of 8
    CASES = {
        # experts 1, 3 and 5 (the first, a middle and the last held) get no row; 2 gets two tiles, 4 one
        "some-held-experts-unchosen": ([2] * 20 + [4] * 4, [7] * 20 + [0] * 4),
        "no-held-expert-chosen": ([0] * 24, [7] * 24),
        # the serve_reason shape: every held expert has rows in every step
        "every-held-expert-chosen": ([1 + t % 5 for t in range(24)], [1 + (t + 2) % 5 for t in range(24)]),
    }

    @classmethod
    def _rows(cls, first, second, seed=0):
        x = np.array(jax.random.normal(jax.random.PRNGKey(seed), (cls.T, cls.D)) * 0.5)
        x[:, :cls.E] = 0
        x[np.arange(cls.T), first] = 2
        x[np.arange(cls.T), second] = 1
        return jnp.asarray(x, jnp.bfloat16), jnp.zeros((cls.D, cls.E), jnp.float32).at[:cls.E].set(4 * jnp.eye(cls.E))

    @classmethod
    def _banks(cls, experts, seed=1):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        up = lambda k: (jax.random.normal(k, (experts, cls.D, cls.F)) / cls.D ** 0.5).astype(jnp.bfloat16)
        return up(ks[0]), up(ks[1]), (jax.random.normal(ks[2], (experts, cls.F, cls.D)) / cls.F ** 0.5).astype(jnp.bfloat16)

    @staticmethod
    def _blocks_in_range(call):
        """Every block index of the recorded pallas_call, at every grid step, names a block its operand has."""
        spec, operands, scalars = call
        for m in range(spec.grid[0]):
            for c in range(spec.grid[1]):
                for bs, operand in zip((*spec.in_specs, spec.out_specs), (*operands, operands[0])):   # ys has xs' shape
                    index = [int(i) for i in bs.index_map(jnp.int32(m), jnp.int32(c), *scalars)]
                    blocks = [-(-dim // (b or 1)) for dim, b in zip(operand.shape, bs.block_shape)]
                    assert all(0 <= i < n for i, n in zip(index, blocks)), (m, c, index, blocks)

    def _held(self, monkeypatch, case, form="in_kernel"):
        """(y, rows) of the kernel's path and of ragged_dot's on the same values, and what the kernel was handed,
        in the form ``held_form`` chooses at 24 tokens (the kernel gathers and sums) or staged through HBM."""
        from jax.experimental import pallas as pl

        from tony_tpu.ops import moe_gemm as MG
        from tony_tpu.parallel import expert as EX
        from tony_tpu.parallel.expert import MoEConfig, held_expert_ffn

        monkeypatch.setattr(MG, "TILE_M", self.TILE)
        if form == "staged":
            monkeypatch.setattr(EX, "HELD_IN_KERNEL_TOKENS", 0)
        assert EX.held_form(self.T, self.D, 2) == form
        seen = {}
        rows_call, tokens_call, pallas_call = MG.moe_swiglu_rows, MG.moe_swiglu_tokens, pl.pallas_call

        def recorded_rows(xs, wg, wu, wd, tile_group, tile, live, *rest):
            seen.update(tile_group=np.asarray(tile_group), live=int(live), tile=tile, rows=xs.shape[0], form="staged")
            return rows_call(xs, wg, wu, wd, tile_group, tile, live, *rest)

        def recorded_tokens(x, sort_tok, gate_sorted, wg, wu, wd, tile_group, tile, live, *rest):
            seen.update(tile_group=np.asarray(tile_group), live=int(live), tile=tile, rows=sort_tok.shape[0], form="in_kernel")
            return tokens_call(x, sort_tok, gate_sorted, wg, wu, wd, tile_group, tile, live, *rest)

        def recorded_pallas(kernel, *, grid_spec, **kw):
            inner = pallas_call(kernel, grid_spec=grid_spec, **kw)

            def run(tile_group, meta, *operands):
                seen["call"] = (grid_spec, operands, (tile_group, meta))
                return inner(tile_group, meta, *operands)
            return run

        monkeypatch.setattr(MG, "moe_swiglu_rows", recorded_rows)
        monkeypatch.setattr(MG, "moe_swiglu_tokens", recorded_tokens)
        monkeypatch.setattr(pl, "pallas_call", recorded_pallas)
        cfg = MoEConfig(num_experts=self.E, top_k=self.K, held=self.HELD)
        x, router = self._rows(*self.CASES[case])
        banks = tuple(b[None] for b in self._banks(self.HELD[1]))
        got = held_expert_ffn(x, router, None, *banks, jnp.int32(0), cfg)
        plain = held_expert_ffn(x.astype(jnp.float32), router, None, *(b.astype(jnp.float32) for b in banks), jnp.int32(0), cfg)
        assert seen["call"][1][0].dtype == jnp.bfloat16                  # the kernel ran once: float32 rows take ragged_dot
        assert seen["form"] == form
        return got, plain, seen

    @pytest.mark.parametrize("case,form", [*((c, f) for c in CASES for f in ("in_kernel", "staged")),
                                           ("training-keeps-a-tile", "staged")])
    def test_a_group_with_no_rows(self, monkeypatch, case, form):
        if case == "training-keeps-a-tile":
            return self._training(monkeypatch)
        (y, rows), (y_plain, rows_plain), seen = self._held(monkeypatch, case, form)
        first, count = self.HELD
        chosen = np.array([c for pair in zip(*self.CASES[case]) for c in pair])
        want_rows = np.bincount(chosen[(chosen >= first) & (chosen < first + count)] - first, minlength=count)
        assert np.array_equal(np.asarray(rows), want_rows) and np.array_equal(np.asarray(rows_plain), want_rows)
        np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(y_plain), atol=3e-2, rtol=3e-2)
        # the static bound stays; the live tiles are the groups' own, an expert's ceil(rows / tile), none for none
        tiles = -(-want_rows // self.TILE)
        assert seen["rows"] == (-(-self.T * self.K // self.TILE) + count) * self.TILE and seen["tile"] == self.TILE
        assert seen["live"] == tiles.sum()
        assert seen["tile_group"][:seen["live"]].tolist() == np.repeat(np.arange(count), tiles).tolist()
        self._blocks_in_range(seen["call"])
        if case == "some-held-experts-unchosen":
            assert seen["live"] == 3 and (want_rows > 0).sum() == 2 and np.abs(np.asarray(y_plain)).max() > 0.1
        elif case == "no-held-expert-chosen":
            assert seen["live"] == 0 and np.isfinite(np.asarray(y, np.float32)).all() and not np.asarray(y, np.float32).any()
        else:
            # what they were when every group had a tile at least: nothing of this traffic changes
            old = np.maximum(tiles, 1)
            assert np.array_equal(tiles, old) and seen["live"] == old.sum() and (want_rows > 0).all()

    def _training(self, monkeypatch):
        """moe_ffn with a backward (held is None), expert 2 of 4 chosen by no token: its group is one tile
        of padding, and the fused backward's gradients are ragged_dot's, that expert's zero."""
        from tony_tpu.ops import moe_gemm as MG
        from tony_tpu.parallel.expert import MoEConfig, moe_ffn, route_ragged

        monkeypatch.setattr(MG, "TILE_M", self.TILE)
        E = 4
        x, router = self._rows([0] * 20 + [3] * 4, [1] * 12 + [3] * 8 + [0] * 4)
        router = router[:, :E].astype(jnp.bfloat16)
        banks = self._banks(E)
        cot = jax.random.normal(jax.random.PRNGKey(3), (1, self.T, self.D), jnp.float32)
        sizes = np.asarray(route_ragged(x[None], router, MoEConfig(num_experts=E, top_k=self.K), tile=self.TILE)[4])
        assert sizes.tolist() == [32, 16, 16, 16]                        # 24, 12, 0 and 12 rows: no group under a tile

        def loss(dispatch, x, wg, wu, wd):
            y, _ = moe_ffn(x[None], router, wg, wu, wd, MoEConfig(num_experts=E, top_k=self.K, dispatch=dispatch))
            return (y.astype(jnp.float32) * cot).sum(), y

        (_, y), got = jax.value_and_grad(lambda *a: loss("ragged", *a), argnums=(0, 1, 2, 3), has_aux=True)(x, *banks)
        (_, y_plain), want = jax.value_and_grad(lambda *a: loss("ragged_xla", *a), argnums=(0, 1, 2, 3), has_aux=True)(x, *banks)
        np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(y_plain, np.float32), atol=3e-2, rtol=3e-2)
        for g, w in zip(got, want):
            g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
            assert np.isfinite(g).all()
            np.testing.assert_allclose(g, w, atol=4e-2 * np.abs(w).max(), rtol=4e-2)
        for g in got[1:]:
            assert not np.asarray(g[2], np.float32).any() and np.abs(np.asarray(g[0], np.float32)).max() > 0


class TestTheKernelGathersAndSums:
    """``held_expert_ffn`` in the kernel's form (``moe_gemm.moe_swiglu_tokens``: the grouped product takes
    ``x [T, D]`` and returns ``y [T, D]``) against the staged form (``x[sort_tok]`` at the static bound, the
    product over rows, the choices gathered back and summed in XLA) on the same values, under the
    interpreter: the four routed cells' decode shapes cut small (their slots, their top-k, a part of the
    experts held, an expert's width in one block and in several), and the edges: no choice on any held
    expert, a held expert no row chose, an idle slot under ``count_mask``, T in no whole sublane group."""

    D, F, TILE = 128, 256, 16
    SHAPES = {  # T, experts, top_k, held (first, count), width blocks, scoring
        "serve_notes-like": (24, 32, 8, (4, 8), 1, "sigmoid"),
        "serve_docqa-like": (48, 16, 4, (8, 4), 2, "softmax"),
        "serve_assist-like": (64, 12, 10, (0, 6), 1, "softmax"),
        "serve_reason-like": (256, 16, 8, (2, 4), 2, "sigmoid"),
        "t-in-no-whole-sublane-group": (20, 8, 3, (0, 4), 1, "softmax"),
    }
    EDGES = ("no-held-expert-chosen", "a-held-expert-unchosen", "an-idle-slot-is-not-counted")

    def _both(self, monkeypatch, x, router, bias, banks, cfg, blocks=1, count_mask=None):
        from tony_tpu.ops import moe_gemm as MG
        from tony_tpu.parallel import expert as EX

        monkeypatch.setattr(MG, "TILE_M", self.TILE)
        if blocks > 1:
            monkeypatch.setattr(MG, "_WEIGHT_VMEM", 3 * self.D * (self.F // blocks) * 2 * 2)
        assert MG.width_block(self.D, self.F, 2) == self.F // blocks
        ran = []
        tokens_call, rows_call = MG.moe_swiglu_tokens, MG.moe_swiglu_rows
        monkeypatch.setattr(MG, "moe_swiglu_tokens", lambda *a, **kw: (ran.append("in_kernel"), tokens_call(*a, **kw))[1])
        monkeypatch.setattr(MG, "moe_swiglu_rows", lambda *a, **kw: (ran.append("staged"), rows_call(*a, **kw))[1])
        call = lambda: EX.held_expert_ffn(x, router, bias, *banks, jnp.int32(1), cfg, count_mask=count_mask, name="moe_swiglu_decode")
        assert EX.held_form(x.shape[0], self.D, 2) == "in_kernel"
        got = call()
        monkeypatch.setattr(EX, "HELD_IN_KERNEL_TOKENS", 0)
        want = call()
        assert ran == ["in_kernel", "staged"]
        return got, want

    def _banks(self, count, seed=1):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        up = lambda k: (jax.random.normal(k, (2, count, self.D, self.F)) / self.D ** 0.5).astype(jnp.bfloat16)
        return up(ks[0]), up(ks[1]), (jax.random.normal(ks[2], (2, count, self.F, self.D)) / self.F ** 0.5).astype(jnp.bfloat16)

    @staticmethod
    def _close(y, y_staged):
        """Within bfloat16 rounding: both forms round a tile's output and the gates alike and sum a
        token's choices wide, in another order, then round once (an ulp of bfloat16 is 2 ** -8 of the value)."""
        y, y_staged = np.asarray(y, np.float32), np.asarray(y_staged, np.float32)
        assert y.shape == y_staged.shape and np.isfinite(y).all()
        np.testing.assert_allclose(y, y_staged, rtol=2 ** -7, atol=2 ** -7 * max(np.abs(y_staged).max(), 1e-3))

    @pytest.mark.parametrize("case", [*SHAPES, *EDGES])
    def test_the_two_forms_agree(self, monkeypatch, case):
        from tony_tpu.parallel.expert import MoEConfig

        if case in self.SHAPES:
            T, E, K, held, blocks, scoring = self.SHAPES[case]
            ks = jax.random.split(jax.random.PRNGKey(len(case)), 3)
            x = (jax.random.normal(ks[0], (T, self.D)) * 0.5).astype(jnp.bfloat16)
            router = jax.random.normal(ks[1], (self.D, E), jnp.float32) / self.D ** 0.5
            bias = 0.1 * jax.random.normal(ks[2], (E,)) if scoring == "sigmoid" else None
            cfg = MoEConfig(num_experts=E, top_k=K, held=held, scoring=scoring, routed_scale=2.5 if scoring == "sigmoid" else 1.0)
            (y, rows), (y_staged, rows_staged) = self._both(monkeypatch, x, router, bias, self._banks(held[1]), cfg, blocks)
            assert np.array_equal(np.asarray(rows), np.asarray(rows_staged)) and int(rows.sum()) > 0
            assert np.abs(np.asarray(y_staged, np.float32)).max() > 0.05
            return self._close(y, y_staged)
        # the edges, choices set by hand as TestAGroupWithNoRows sets them: top-2 of 8, experts 1 .. 5 held
        first, second = {
            "no-held-expert-chosen": ([0] * 24, [7] * 24),
            "a-held-expert-unchosen": ([2] * 20 + [4] * 4, [7] * 20 + [5] * 4),
            "an-idle-slot-is-not-counted": ([1 + t % 5 for t in range(24)], [1 + (t + 2) % 5 for t in range(24)]),
        }[case]
        x, router = TestAGroupWithNoRows._rows(first, second)
        cfg = MoEConfig(num_experts=8, top_k=2, held=(1, 5))
        live = jnp.arange(24) % 3 != 1 if case == "an-idle-slot-is-not-counted" else None
        (y, rows), (y_staged, rows_staged) = self._both(monkeypatch, x, router, None, self._banks(5), cfg, count_mask=live)
        assert np.array_equal(np.asarray(rows), np.asarray(rows_staged))
        self._close(y, y_staged)
        chosen = np.array([first, second]).T                               # [T, 2]
        counted = chosen if live is None else chosen[np.asarray(live)]
        assert np.asarray(rows).tolist() == np.bincount(counted[(counted >= 1) & (counted < 6)] - 1, minlength=5).tolist()
        if case == "no-held-expert-chosen":
            assert not np.asarray(y, np.float32).any() and not np.asarray(rows).any()      # live == 0: y is zero
        elif case == "a-held-expert-unchosen":
            assert np.asarray(rows).tolist() == [0, 20, 0, 4, 4] and np.abs(np.asarray(y, np.float32)).max() > 0.1
        else:
            # an idle slot's row is computed like any (y is the unmasked call's) and counted as none
            assert int(rows.sum()) == 2 * int(live.sum()) and np.abs(np.asarray(y, np.float32)[1]).max() > 0.05

    def test_the_form_follows_the_shapes(self):
        """In the kernel at the four cells' decode batches at their widths and at a 512-row bucket, staged at
        a 1024- and a 2048-row chunk, and once staged never in the kernel again as T grows."""
        from tony_tpu.parallel.expert import held_form

        for T, D in ((24, 5120), (48, 4096), (64, 4096), (256, 6144), (512, 6144)):
            assert held_form(T, D, 2) == "in_kernel", (T, D)
        for D in (4096, 5120, 6144):
            assert held_form(2048, D, 2) == "staged" and held_form(1024, D, 2) == "staged"
            forms = [held_form(T, D, 2) for T in range(8, 4097, 8)]
            switch = forms.index("staged")
            assert switch > 0 and set(forms[:switch]) == {"in_kernel"} and set(forms[switch:]) == {"staged"}
        assert held_form(64, 16384, 2) == "in_kernel" and held_form(512, 16384, 2) == "staged"      # what VMEM holds


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
