"""Flash attention's backward as ONE Pallas call (`ops/attention.flash_bwd`) under the interpreter, against the
reference's gradients. The forward, its block classes, segments and windows are tests/test_ops.py (one subject
a file, so that `--dist loadfile` can run them side by side).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_ops import _absolute_reference  # the reference at the kernels' absolute positions
from tony_tpu.ops import attention as A


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
