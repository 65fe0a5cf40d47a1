"""Paged KV cache + prefix caching (VERDICT r3 #4).

Block-paged page pool with per-slot page tables, refcounted shared-prefix
reuse, reservation-based admission. Properties under test:
- the paged Pallas kernel matches the dense ragged kernel bit-for-bit in
  math (interpret mode on CPU), including sliding windows and page-table
  indirection through a shuffled pool;
- the paged ENGINE matches the dense engine's greedy outputs exactly;
- N same-prefix requests cost ~1 prefill (prefix_hit_tokens accounting)
  and still match the dense engine;
- a pool smaller than slots × max_pages (the HBM win) still serves
  everything, waiting at admission instead of failing;
- allocator invariants: page 0 never allocated, LRU reuse-pool eviction,
  refcount sharing.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models.llama import LLAMA_TINY, init
from tony_tpu.models.paged_cache import PageAllocator, prefix_keys
from tony_tpu.models.serving import ContinuousBatcher


def _params():
    return init(jax.random.PRNGKey(0), LLAMA_TINY)


# ---------------------------------------------------------------------------
# Kernel parity: paged vs dense ragged, shuffled pages, with/without SWA
# ---------------------------------------------------------------------------
class TestPagedKernel:
    def test_matches_dense_ragged_kernel(self):
        from tony_tpu.ops.decode_attention import (
            paged_decode_attention,
            ragged_decode_attention,
        )

        S, H, Hkv, maxT, Dh, PLEN = 3, 4, 2, 256, 128, 64
        max_pages = maxT // PLEN
        P = S * max_pages + 2
        ks = jax.random.split(jax.random.PRNGKey(3), 5)
        q = jax.random.normal(ks[0], (S, H, Dh), jnp.float32)
        ck = jax.random.normal(ks[1], (S, Hkv, maxT, Dh), jnp.float32)
        cv = jax.random.normal(ks[2], (S, Hkv, maxT, Dh), jnp.float32)
        cur_k = jax.random.normal(ks[3], (S, Hkv, Dh), jnp.float32)
        cur_v = jax.random.normal(ks[4], (S, Hkv, Dh), jnp.float32)
        lengths = jnp.array([0, 129, 250], jnp.int32)
        # scatter the dense caches into a SHUFFLED page pool: parity then
        # proves the page-table indirection, not just the math
        rng = np.random.default_rng(0)
        pt = rng.permutation(P)[: S * max_pages].reshape(S, max_pages).astype(np.int32)
        kp = np.zeros((P, Hkv, PLEN, Dh), np.float32)
        vp = np.zeros((P, Hkv, PLEN, Dh), np.float32)
        for s in range(S):
            for j in range(max_pages):
                kp[pt[s, j]] = np.asarray(ck)[s, :, j * PLEN:(j + 1) * PLEN]
                vp[pt[s, j]] = np.asarray(cv)[s, :, j * PLEN:(j + 1) * PLEN]
        for window in (0, 100):
            want = ragged_decode_attention(
                q, ck, cv, lengths, cur_k=cur_k, cur_v=cur_v,
                window=window, chunk=PLEN,
            )
            got = paged_decode_attention(
                q, jnp.asarray(kp)[None], jnp.asarray(vp)[None], lengths, jnp.asarray(pt),
                jnp.int32(0), cur_k=cur_k, cur_v=cur_v, window=window,
            )
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5,
                err_msg=f"window={window}",
            )

    def test_rejects_unaligned_page_len(self):
        """Direct kernel callers get the same sublane-alignment guard the
        engine enforces: page_len must be a multiple of 8 (ADVICE r4)."""
        from tony_tpu.ops.decode_attention import paged_decode_attention

        S, H, Hkv, Dh = 1, 2, 1, 128
        for plen in (4, 12):
            kp = jnp.zeros((1, 2, Hkv, plen, Dh), jnp.float32)
            with pytest.raises(ValueError, match="multiple of 8"):
                paged_decode_attention(
                    jnp.zeros((S, H, Dh), jnp.float32), kp, kp,
                    jnp.zeros((S,), jnp.int32),
                    jnp.zeros((S, 1), jnp.int32), jnp.int32(0),
                    cur_k=jnp.zeros((S, Hkv, Dh), jnp.float32),
                    cur_v=jnp.zeros((S, Hkv, Dh), jnp.float32),
                )

    @pytest.mark.parametrize("window", [0, 100])
    @pytest.mark.parametrize("staged", [False, True])
    def test_layer_index_reads_that_layers_pages(self, staged, window):
        """The operand is the whole pool and a layer index: layer ``l`` of an
        L=3 pool gives, bit for bit, what a one-layer pool holding only that
        layer's pages gives at index 0 — so the index picks the layer and
        nothing else about the read changed."""
        from tony_tpu.ops.decode_attention import paged_decode_attention

        L, S, H, Hkv, Dh, PLEN, max_pages, W = 3, 3, 4, 2, 128, 64, 4, 8
        P = S * max_pages + 2
        ks = jax.random.split(jax.random.PRNGKey(7), 7)
        q = jax.random.normal(ks[0], (S, H, Dh), jnp.float32)
        kp = jax.random.normal(ks[1], (L, P, Hkv, PLEN, Dh), jnp.float32)
        vp = jax.random.normal(ks[2], (L, P, Hkv, PLEN, Dh), jnp.float32)
        cur_k = jax.random.normal(ks[3], (S, Hkv, Dh), jnp.float32)
        cur_v = jax.random.normal(ks[4], (S, Hkv, Dh), jnp.float32)
        lengths = jnp.array([0, 129, 250], jnp.int32)
        pt = jnp.asarray(np.random.default_rng(1).permutation(P)[: S * max_pages]
                         .reshape(S, max_pages).astype(np.int32))
        extra = {}
        if staged:
            extra = dict(
                staged_k=jax.random.normal(ks[5], (S, W, Hkv, Dh), jnp.float32),
                staged_v=jax.random.normal(ks[6], (S, W, Hkv, Dh), jnp.float32),
                staged_count=jnp.full((S,), 5, jnp.int32),
            )
        outs = []
        for l in range(L):
            whole = paged_decode_attention(
                q, kp, vp, lengths, pt, jnp.int32(l), cur_k=cur_k, cur_v=cur_v,
                window=window, **extra)
            alone = paged_decode_attention(
                q, kp[l][None], vp[l][None], lengths, pt, jnp.int32(0),
                cur_k=cur_k, cur_v=cur_v, window=window, **extra)
            np.testing.assert_array_equal(np.asarray(whole), np.asarray(alone), err_msg=f"layer {l}")
            outs.append(np.asarray(whole))
        assert not np.array_equal(outs[0], outs[1])  # the layers do differ

    def test_rejects_one_layers_slice(self):
        """One signature: a [P, Hkv, page_len, Dh] slice is refused by name,
        not read as a pool of P layers."""
        from tony_tpu.ops.decode_attention import paged_decode_attention

        kp = jnp.zeros((2, 1, 8, 128), jnp.float32)
        with pytest.raises(ValueError, match="whole pool"):
            paged_decode_attention(
                jnp.zeros((1, 2, 128), jnp.float32), kp, kp, jnp.zeros((1,), jnp.int32),
                jnp.zeros((1, 1), jnp.int32), jnp.int32(0),
                cur_k=jnp.zeros((1, 1, 128), jnp.float32),
                cur_v=jnp.zeros((1, 1, 128), jnp.float32),
            )


# ---------------------------------------------------------------------------
# The page walk: a fetch handed from slot to slot, the chunk's staged rows and
# the current token in one fold; against plain attention over the explicit list
# of positions a step may see
# ---------------------------------------------------------------------------
WALK_PAGE, WALK_PAGES, WALK_WINDOW, WALK_STAGED, WALK_LAYERS = 16, 8, 40, 8, 3
WALK_MAX_LEN = WALK_PAGE * WALK_PAGES

#: the slots IN ORDER (a slot's first fetch is its predecessor's to start): (positions its tenant
#: has written to its pages, the step's length, staged rows). Slabs read: a page each from the
#: window's first to the pool part's last
WALK_SLOTS = {
    "the-first-slot-idle": (0, 0, 0),
    "one-slab-after-an-idle-slot-count-0": (10, 10, 0),               # warms up itself, hands on
    "two-slabs-behind-one-count-3": (27, 30, 3),                      # handed into buffer 1
    "idle-between-two-live-slots": (0, 0, 0),                         # the chain breaks
    "three-slabs-resuming-after-the-idle-slot-count-7": (40, 47, 7),  # and resumes: a warm-up
    "every-position-staged-count-5": (0, 5, 5),                       # neither receives nor hands on
    "window-skips-three-pages-four-slabs": (100, 100, 0),             # c0 = 3, warms up again
    "window-skips-three-pages-three-slabs-count-7": (83, 90, 7),      # odd after even
    # the chunk began at 125 and is 5 steps in: the caller clips 130 to max_len - 1, so the
    # newest cached rows (122..124) lie past the pool's part of the slot and are not read
    "length-clipped-at-max-len-minus-1": (125, WALK_MAX_LEN - 1, 5),  # odd after odd
    "every-position-staged-count-1": (0, 1, 1),
    "exactly-one-page-count-0": (16, 16, 0),
    "every-position-staged-count-7": (0, 7, 7),
    "the-last-slot-live-two-slabs-count-1": (32, 33, 1),
}


def _plain_rows(q, seen):
    """Plain attention of each slot's query heads over its own list of (key, value) rows."""
    S, H, Dh = q.shape
    want = np.zeros((S, H, Dh), np.float32)
    for s, rows in enumerate(seen):
        keys, vals = (np.stack(a, axis=1) for a in zip(*rows))                          # [Hkv, n, Dh]
        Hkv = keys.shape[0]
        scores = np.einsum("hrd,hnd->hrn", q[s].reshape(Hkv, H // Hkv, Dh) * Dh ** -0.5, keys)
        p = np.exp(scores - scores.max(-1, keepdims=True))
        want[s] = np.einsum("hrn,hnd->hrd", p / p.sum(-1, keepdims=True), vals).reshape(H, Dh)
    return want


@functools.lru_cache(maxsize=None)
def _walk_case(dtype):
    """The slots' pages as their histories leave them (layer 1 of 3, a shuffled table; every other
    row of the pool large, so a row read by mistake shows) and what plain attention gives."""
    from tony_tpu.ops.decode_attention import paged_decode_attention

    S, H, Hkv, Dh = len(WALK_SLOTS), 8, 2, 16
    P = S * WALK_PAGES + 1
    rng = np.random.default_rng(11)
    draw = lambda *shape: np.asarray(jnp.asarray(rng.standard_normal(shape), dtype).astype(jnp.float32))
    hist_k, hist_v = draw(S, WALK_MAX_LEN, Hkv, Dh), draw(S, WALK_MAX_LEN, Hkv, Dh)
    kp, vp = 50.0 + draw(WALK_LAYERS, P, Hkv, WALK_PAGE, Dh), 50.0 + draw(WALK_LAYERS, P, Hkv, WALK_PAGE, Dh)
    table = (1 + rng.permutation(P - 1)).reshape(S, WALK_PAGES).astype(np.int32)
    q, cur_k, cur_v = draw(S, H, Dh), draw(S, Hkv, Dh), draw(S, Hkv, Dh)
    sk, sv = draw(S, WALK_STAGED, Hkv, Dh), draw(S, WALK_STAGED, Hkv, Dh)
    seen, seen_unstaged = [], []
    for s, (written, length, count) in enumerate(WALK_SLOTS.values()):
        for p in range(written):
            kp[1, table[s, p // WALK_PAGE], :, p % WALK_PAGE] = hist_k[s, p]
            vp[1, table[s, p // WALK_PAGE], :, p % WALK_PAGE] = hist_v[s, p]
        pool_len, lo = max(length - count, 0), max(length + 1 - WALK_WINDOW, 0)
        cached = lambda lo, hi: [(hist_k[s, p], hist_v[s, p]) for p in range(lo, hi)]
        staged = [(sk[s, j], sv[s, j]) for j in range(count) if pool_len + j >= lo]
        seen.append(cached(lo, pool_len) + staged + [(cur_k[s], cur_v[s])])
        # the same pages with no staging: the step's length is what the tenant has written
        seen_unstaged.append(cached(max(written + 1 - WALK_WINDOW, 0), written) + [(cur_k[s], cur_v[s])])
    lengths, counts = (jnp.asarray([c[i] for c in WALK_SLOTS.values()], jnp.int32) for i in (1, 2))
    operands = dict(q=jnp.asarray(q, dtype), kp=jnp.asarray(kp, dtype), vp=jnp.asarray(vp, dtype), lengths=lengths,
                    page_table=jnp.asarray(table), cur_k=jnp.asarray(cur_k, dtype), cur_v=jnp.asarray(cur_v, dtype),
                    staged_k=jnp.asarray(sk, dtype), staged_v=jnp.asarray(sv, dtype), staged_count=counts)
    got = paged_decode_attention(layer=jnp.int32(1), window=WALK_WINDOW, **operands).astype(jnp.float32)
    return operands, np.asarray(got), _plain_rows(q, seen), _plain_rows(q, seen_unstaged), (hist_k, hist_v)


class TestPageWalk:
    @pytest.mark.parametrize("case,dtype", [(c, d) for d in ("float32", "bfloat16") for c in WALK_SLOTS]
                             + [(c, "float32") for c in (
                                 "the-same-slots-in-another-order-give-the-same-rows",
                                 "one-slot-alone",
                                 "layer-index-traced-under-cond-in-a-scan",
                                 "no-staging",
                                 "no-window",
                                 "a-dense-cache-through-the-same-walk")])
    def test_paged_decode_attention(self, case, dtype):
        from tony_tpu.ops.decode_attention import paged_decode_attention, ragged_decode_attention

        operands, got, want, want_unstaged, (hist_k, hist_v) = _walk_case(dtype)
        names = list(WALK_SLOTS)
        call = functools.partial(paged_decode_attention, window=WALK_WINDOW)
        per_slot = lambda rows: {k: v if k in ("kp", "vp") else v[rows] for k, v in operands.items()}
        written = jnp.asarray([c[0] for c in WALK_SLOTS.values()], jnp.int32)
        if case in WALK_SLOTS:
            s = names.index(case)
            # bfloat16: the same rows, so what is left is the output's own rounding (2 ** -9 of values under 4)
            np.testing.assert_allclose(got[s], want[s], atol=1e-5 if dtype == "float32" else 1e-2, rtol=0)
        elif case.startswith("the-same-slots"):
            # other neighbours, other buffers, other hand-overs (the idle slots now lie elsewhere): a slot's row
            # is its own arithmetic whatever was fetched around it
            for seed in (0, 1):
                order = np.random.default_rng(seed).permutation(len(names))
                again = call(layer=jnp.int32(1), **per_slot(order))
                np.testing.assert_array_equal(np.asarray(again), got[order], err_msg=str(order))
        elif case == "one-slot-alone":
            for s in (names.index("window-skips-three-pages-four-slabs"), names.index("every-position-staged-count-5")):
                alone = call(layer=jnp.int32(1), **per_slot(np.asarray([s])))
                np.testing.assert_array_equal(np.asarray(alone)[0], got[s])
        elif case.startswith("layer-index"):
            # as exaone_moe._decode_one hands it: a scan's slice, the call in one branch of a cond
            at = lambda layer: call(layer=layer, **operands)

            def body(_, xs):
                layer, is_full = xs
                return None, jax.lax.cond(is_full == 1, at, lambda layer: jnp.zeros(got.shape, dtype), layer)

            layers, kinds = jnp.asarray([0, 0, 1, 2], jnp.int32), jnp.asarray([1, 0, 1, 1], jnp.int32)
            outs = np.asarray(jax.lax.scan(body, None, (layers, kinds))[1])
            np.testing.assert_array_equal(outs[2], got)
            np.testing.assert_array_equal(outs[1], 0)
            np.testing.assert_array_equal(outs[3], np.asarray(at(jnp.int32(2))))
            assert not np.array_equal(outs[0], got) and not np.array_equal(outs[3], got)   # the layers do differ
        elif case == "no-staging":
            # no staged operands at all: the fold is the current token's alone
            unstaged = {k: v for k, v in operands.items() if not k.startswith("staged")} | {"lengths": written}
            np.testing.assert_allclose(np.asarray(call(layer=jnp.int32(1), **unstaged)), want_unstaged, atol=1e-5, rtol=0)
        elif case == "no-window":
            # every cached page from the first: up to 8 slabs a slot; against the windowed rows where the window
            # hides nothing, and not equal to them where it does
            whole = np.asarray(paged_decode_attention(layer=jnp.int32(1), window=0, **operands))
            for s, (_, length, _) in enumerate(WALK_SLOTS.values()):
                if length + 1 <= WALK_WINDOW:
                    np.testing.assert_allclose(whole[s], want[s], atol=1e-5, rtol=0, err_msg=names[s])
                else:
                    assert np.abs(whole[s] - want[s]).max() > 1e-3, names[s]
        else:
            # ragged_decode_attention runs the same body over a cache a slot: slab c is positions c * chunk ..
            # of the slot's own rows, and the fetch handed on is the next slot's
            cache = lambda hist: jnp.asarray(np.where(
                np.arange(WALK_MAX_LEN)[None, None, :, None] < np.asarray(written)[:, None, None, None],
                hist.transpose(0, 2, 1, 3), 50.0))
            dense = ragged_decode_attention(operands["q"], cache(hist_k), cache(hist_v), written, cur_k=operands["cur_k"],
                                            cur_v=operands["cur_v"], window=WALK_WINDOW, chunk=WALK_PAGE)
            np.testing.assert_allclose(np.asarray(dense), want_unstaged, atol=1e-5, rtol=0)


# ---------------------------------------------------------------------------
# A window layer's ring, read whole: against plain attention over the explicit
# list of positions a step may see
# ---------------------------------------------------------------------------
RING, RING_WINDOW, RING_STAGED, RING_LAYERS, RING_MAX_LEN = 24, 16, 8, 3, 128

#: a slot each: (positions its tenant has written to the ring, the step's length, staged rows)
RING_SLOTS = {
    "idle": (0, 0, 0),
    "not-yet-full-over-the-last-tenants-rows": (10, 10, 0),
    "staged-rows-only": (0, 3, 3),
    "lapped-four-times-count-7": (93, 100, 7),
    "window-wraps-the-rings-end-count-0": (50, 50, 0),
    "count-3": (44, 47, 3),
    "lo-lands-mid-ring-count-7": (33, 40, 7),
    # the chunk began at 125 and is 5 steps in: the caller clips 130 to max_len - 1, so the
    # ring's newest rows (122..124) lie past the pool's part of the slot and are not read
    "length-clipped-at-max-len": (125, RING_MAX_LEN - 1, 5),
    "exactly-one-lap": (24, 24, 0),
    "window-starts-at-position-0": (15, 15, 0),
}


@functools.lru_cache(maxsize=None)
def _ring_case(dtype):
    """The ten slots' rings as their histories leave them, layer 1 of 3, and what plain
    attention over each slot's visible positions gives. Every row starts as the last tenant's
    (large values: a row read by mistake shows), then position p lands in row p % ring."""
    from tony_tpu.ops.decode_attention import ring_decode_attention

    S, H, Hkv, Dh = len(RING_SLOTS), 8, 2, 16
    rng = np.random.default_rng(5)
    draw = lambda *shape: np.asarray(jnp.asarray(rng.standard_normal(shape), dtype).astype(jnp.float32))
    hist_k, hist_v = draw(S, RING_MAX_LEN, Hkv, Dh), draw(S, RING_MAX_LEN, Hkv, Dh)
    rk, rv = 50.0 + draw(RING_LAYERS, S, Hkv, RING, Dh), 50.0 + draw(RING_LAYERS, S, Hkv, RING, Dh)
    q, cur_k, cur_v = draw(S, H, Dh), draw(S, Hkv, Dh), draw(S, Hkv, Dh)
    sk, sv = draw(S, RING_STAGED, Hkv, Dh), draw(S, RING_STAGED, Hkv, Dh)
    want = np.zeros((S, H, Dh), np.float32)
    for s, (written, length, count) in enumerate(RING_SLOTS.values()):
        for p in range(written):
            rk[1, s, :, p % RING], rv[1, s, :, p % RING] = hist_k[s, p], hist_v[s, p]
        pool_len, lo = max(length - count, 0), max(length + 1 - RING_WINDOW, 0)
        seen = [(hist_k[s, p], hist_v[s, p]) for p in range(lo, pool_len)]
        seen += [(sk[s, j], sv[s, j]) for j in range(count) if pool_len + j >= lo]
        seen.append((cur_k[s], cur_v[s]))
        keys, vals = (np.stack(a, axis=1) for a in zip(*seen))                      # [Hkv, n, Dh]
        scores = np.einsum("hrd,hnd->hrn", q[s].reshape(Hkv, H // Hkv, Dh) * Dh ** -0.5, keys)
        p = np.exp(scores - scores.max(-1, keepdims=True))
        want[s] = np.einsum("hrn,hnd->hrd", p / p.sum(-1, keepdims=True), vals).reshape(H, Dh)
    operands = (jnp.asarray(q, dtype), jnp.asarray(rk, dtype), jnp.asarray(rv, dtype),
                jnp.asarray([c[1] for c in RING_SLOTS.values()], jnp.int32))
    kw = dict(cur_k=jnp.asarray(cur_k, dtype), cur_v=jnp.asarray(cur_v, dtype), window=RING_WINDOW,
              staged_k=jnp.asarray(sk, dtype), staged_v=jnp.asarray(sv, dtype),
              staged_count=jnp.asarray([c[2] for c in RING_SLOTS.values()], jnp.int32))
    return operands, kw, np.asarray(ring_decode_attention(*operands, jnp.int32(1), **kw).astype(jnp.float32)), want


class TestRingKernel:
    @pytest.mark.parametrize("case,dtype", [(c, d) for d in ("float32", "bfloat16") for c in RING_SLOTS]
                             + [("layer-index-traced-under-cond-in-a-scan", "float32"),
                                ("a-slot-count-the-block-does-not-divide", "float32"),
                                ("agrees-with-the-page-walk-over-ring-table", "float32")])
    def test_ring_decode_attention(self, case, dtype):
        from tony_tpu.models.paged_cache import ring_table
        from tony_tpu.ops.decode_attention import RING_BLOCK, paged_decode_attention, ring_decode_attention

        operands, kw, got, want = _ring_case(dtype)
        if case in RING_SLOTS:
            s = list(RING_SLOTS).index(case)
            # bfloat16: the same rows, so what is left is the output's own rounding (2 ** -9 of values under 4)
            np.testing.assert_allclose(got[s], want[s], atol=1e-5 if dtype == "float32" else 1e-2, rtol=0)
        elif case.startswith("layer-index"):
            # as exaone_moe._decode_one hands it: a scan's slice, the call in one branch of a cond
            call = lambda layer: ring_decode_attention(*operands, layer, **kw)

            def body(_, xs):
                layer, is_window = xs
                return None, jax.lax.cond(is_window == 1, call, lambda layer: jnp.zeros(got.shape, dtype), layer)

            layers, kinds = jnp.asarray([0, 0, 1, 2], jnp.int32), jnp.asarray([1, 0, 1, 1], jnp.int32)
            outs = np.asarray(jax.lax.scan(body, None, (layers, kinds))[1])
            np.testing.assert_array_equal(outs[2], got)
            np.testing.assert_array_equal(outs[1], 0)
            np.testing.assert_array_equal(outs[3], np.asarray(call(jnp.int32(2))))
            assert not np.array_equal(outs[0], got) and not np.array_equal(outs[3], got)   # the layers do differ
        elif case.startswith("a-slot-count"):
            # the first n slots alone, n the first count above the block that it does not divide: smaller blocks
            n = next(n for n in range(RING_BLOCK + 1, len(RING_SLOTS)) if n % RING_BLOCK)
            q, rk, rv, lengths = operands
            alone = ring_decode_attention(q[:n], rk[:, :n], rv[:, :n], lengths[:n], jnp.int32(1),
                                          **{k: v if k == "window" else v[:n] for k, v in kw.items()})
            np.testing.assert_array_equal(np.asarray(alone), got[:n])
        else:
            # the call it replaced: the same rings as pages of `ring` rows, a logical page of slot s page s
            q, rk, rv, lengths = operands
            paged = paged_decode_attention(q, rk, rv, lengths, ring_table(len(RING_SLOTS), RING_MAX_LEN, RING),
                                           jnp.int32(1), **kw)
            np.testing.assert_allclose(got, np.asarray(paged), atol=1e-5, rtol=0)


# ---------------------------------------------------------------------------
# The chunk's one pool write: in place, by page, equal to the plain scatter
# ---------------------------------------------------------------------------
class TestChunkWrite:
    L, P, HKV, DH = 3, 12, 2, 8
    PLEN, N, MAX_PAGES = SHORT = (16, 8, 3)  # (page_len, n, max_pages): the engine's case, n <= page_len
    MAX_T = MAX_PAGES * PLEN
    LONG = (8, 12, 5)                        # a chunk longer than a page

    @staticmethod
    @jax.jit
    def _plain_scatter(pk, stage, len0, page_table):
        """The form ``decode_steps`` had before the in-place write, kept here
        as the plain reference: two index arrays, one scatter."""
        Lc, S, n, Hkv, Dh = stage.shape
        page_len = pk.shape[3]
        max_t = page_table.shape[1] * page_len
        steps = jnp.arange(n, dtype=jnp.int32)[None, :]
        pos = jnp.where(len0[:, None] > 0, jnp.minimum(len0[:, None] + steps, max_t - 1), 0)
        pages = jnp.take_along_axis(page_table, pos // page_len, axis=1).reshape(-1)
        offs = (pos % page_len).reshape(-1)
        cols = stage.transpose(1, 2, 0, 3, 4).reshape(S * n, Lc, Hkv, Dh)
        return pk.at[:, pages, :, offs, :].set(cols)

    @pytest.mark.parametrize("geometry,len0_case", [
        pytest.param(SHORT, PLEN, id="page_start"),              # offset 0 of its second page
        pytest.param(SHORT, PLEN - N, id="fits_to_page_end"),    # offset page_len - n: the last that fits
        pytest.param(SHORT, 2 * PLEN - 3, id="crossing"),        # 3 rows in one page, 5 in the next
        pytest.param(SHORT, PLEN - 1, id="last_row"),            # 1 row, then 7 in the next
        pytest.param(SHORT, 0, id="idle"),
        pytest.param(SHORT, MAX_T - 1, id="clamped_at_end"),     # one live position, 7 steps past the end
        pytest.param(SHORT, MAX_T - 4, id="overshoot"),          # 4 live positions, 4 steps past the end
        pytest.param(LONG, 13, id="three_pages"),                # 3 + 8 + 1 rows in three pages
    ])
    def test_in_place_write_equals_plain_scatter(self, geometry, len0_case):
        from tony_tpu.models.paged_cache import write_decode_chunk

        L, P, Hkv, Dh = self.L, self.P, self.HKV, self.DH
        PLEN, n, max_pages = geometry
        MAX_T = max_pages * PLEN
        # the slot under test between two bystanders: one mid-page, one idle
        len0 = np.array([PLEN + 5, len0_case, 0], np.int32)
        S = len(len0)
        rng = np.random.default_rng(5)
        own = rng.permutation(np.arange(1, P))      # page 0 is never a slot's own
        pt = np.zeros((S, max_pages), np.int32)
        pt[0], pt[1] = own[:max_pages], own[max_pages:2 * max_pages]
        if len0_case == 0:
            pt[1] = 0                                # a flushed slot's row is zeros
        pk = rng.standard_normal((L, P, Hkv, PLEN, Dh)).astype(np.float32)
        pv = rng.standard_normal((L, P, Hkv, PLEN, Dh)).astype(np.float32)
        sk = rng.standard_normal((L, S, n, Hkv, Dh)).astype(np.float32)
        sv = rng.standard_normal((L, S, n, Hkv, Dh)).astype(np.float32)

        got_k, got_v = jax.jit(write_decode_chunk)(
            jnp.asarray(pk), jnp.asarray(pv), jnp.asarray(sk), jnp.asarray(sv),
            jnp.asarray(len0), jnp.asarray(pt))
        got_k, got_v = np.asarray(got_k), np.asarray(got_v)
        ref_k = np.asarray(self._plain_scatter(
            jnp.asarray(pk), jnp.asarray(sk), jnp.asarray(len0), jnp.asarray(pt)))

        # every position a request can read: step j of a live slot, inside max_len
        want_k, want_v = pk.copy(), pv.copy()
        live = np.zeros((P, PLEN), bool)
        for s in range(S):
            for j in range(n):
                q = int(len0[s]) + j
                if len0[s] > 0 and q < MAX_T:
                    page, off = pt[s, q // PLEN], q % PLEN
                    want_k[:, page, :, off] = sk[:, s, j]
                    want_v[:, page, :, off] = sv[:, s, j]
                    # the plain scatter piles the steps past the end onto the last
                    # position too; which of them it keeps there is not defined
                    live[page, off] = q < MAX_T - 1 or j == n - 1
        assert live.sum() == sum(
            min(n, MAX_T - int(x)) - (int(x) + n > MAX_T) for x in len0 if x > 0)
        np.testing.assert_array_equal(got_k.transpose(1, 3, 0, 2, 4)[live],
                                      ref_k.transpose(1, 3, 0, 2, 4)[live])
        # and nothing else moved: other slots' pages, the rest of its own pages
        # and the sacrificial page are byte for byte what they were
        np.testing.assert_array_equal(got_k, want_k)
        np.testing.assert_array_equal(got_v, want_v)
        assert not live[0].any() and np.array_equal(got_k[:, 0], pk[:, 0])



    def test_single_step_and_chunk_of_one_write_the_same_pool(self):
        """The two paged programs over `_decode_one`: `decode_step` writes its
        one column at once (a chunk of one), `decode_steps` stages it and
        writes after the scan. One step either way: same token, same pool,
        through the same read (whole pool + layer index)."""
        from tony_tpu.models import serving
        from tony_tpu.models.paged_cache import PagedCache, init_paged_cache

        params, cfg, S, plen = _params(), LLAMA_TINY, 3, 16
        blank = init_paged_cache(cfg, S, 64, plen, 9)
        ks = jax.random.split(jax.random.PRNGKey(11), 2)

        def cache():  # fresh buffers each call: both programs donate theirs
            return PagedCache(
                jax.random.normal(ks[0], blank.k.shape, jnp.float32).astype(blank.k.dtype),
                jax.random.normal(ks[1], blank.v.shape, jnp.float32).astype(blank.v.dtype),
                jnp.array([plen - 1, 0, plen + 3], jnp.int32),      # a page's last row, idle, mid-page
                jnp.array([[1, 2, 3, 4], [0, 0, 0, 0], [5, 6, 7, 8]], jnp.int32))

        tokens, key = jnp.array([3, 4, 5], jnp.int32), jax.random.PRNGKey(0)
        nxt1, one = serving.decode_step(params, cache(), tokens, key, cfg)
        nxt8, _, chunk = serving.decode_steps(params, cache(), tokens, key, cfg, 1)
        assert nxt1[0] == nxt8[0] and nxt1[2] == nxt8[2]      # live slots
        np.testing.assert_array_equal(np.asarray(one.lengths), np.asarray(chunk.lengths))
        np.testing.assert_array_equal(np.asarray(one.k, np.float32), np.asarray(chunk.k, np.float32))
        np.testing.assert_array_equal(np.asarray(one.v, np.float32), np.asarray(chunk.v, np.float32))


# ---------------------------------------------------------------------------
# Allocator invariants
# ---------------------------------------------------------------------------
class TestPageAllocator:
    def test_page_zero_never_allocated(self):
        a = PageAllocator(6)
        got = a.alloc(5)
        assert 0 not in got and sorted(got) == [1, 2, 3, 4, 5]
        with pytest.raises(RuntimeError, match="exhausted"):
            a.alloc(1)

    def test_release_unkeyed_returns_to_free(self):
        a = PageAllocator(4)
        p = a.alloc(1)[0]
        a.release(p)
        assert a.available() == 3 and p in a.alloc(3)

    def test_refcount_sharing(self):
        a = PageAllocator(4)
        keys = prefix_keys([1, 2, 3, 4], 2)  # two full pages
        pages = a.alloc(2)
        for p, k in zip(pages, keys):
            a.register(p, k)
        shared = a.match_prefix(keys)
        assert shared == pages  # both matched and pinned (ref 2)
        for p in pages:
            a.release(p)  # first holder retires
        assert a.available() == 1  # still live via the second holder
        for p in pages:
            a.release(p)  # second holder retires → reuse pool
        assert a.available() == 3
        assert a.match_prefix(keys) == pages  # resurrected from reuse pool
        for p in pages:
            a.release(p)

    def test_lru_eviction_of_reuse_pool(self):
        a = PageAllocator(4)  # 3 usable
        keys = prefix_keys([9, 9, 8, 8, 7, 7], 2)
        pages = a.alloc(3)
        for p, k in zip(pages, keys):
            a.register(p, k)
        for p in pages:
            a.release(p)  # all parked in the reuse pool
        fresh = a.alloc(2)  # evicts the two LRU pages
        assert set(fresh) == set(pages[:2])
        assert a.match_prefix(keys) == []  # chain broken at evicted page 0
        assert a.match_prefix(keys[1:2]) == []  # keys are cumulative chains


# ---------------------------------------------------------------------------
# Engine: parity, sharing, capacity
# ---------------------------------------------------------------------------
class TestPagedEngine:
    @pytest.mark.slow  # ~10 s full-engine decode parity sweep
    def test_greedy_parity_with_dense_engine(self):
        params = _params()
        dense = ContinuousBatcher(params, LLAMA_TINY, num_slots=3, max_len=128,
                                  decode_chunk=4)
        paged = ContinuousBatcher(params, LLAMA_TINY, num_slots=3, max_len=128,
                                  decode_chunk=4, kv="paged", page_len=32)
        prompts = [[1, 2, 3], [7, 8, 9, 10, 11], list(range(1, 40))]
        rd = [dense.submit(p, max_new_tokens=8) for p in prompts]
        rp = [paged.submit(p, max_new_tokens=8) for p in prompts]
        outd, outp = dense.run(), paged.run()
        for a, b in zip(rd, rp):
            assert outd[a] == outp[b]

    @pytest.mark.slow  # ~8 s full-engine prefix-cache burst
    def test_shared_prefix_burst_prefills_once(self):
        """VERDICT done-when (a): N same-prefix slots ~1 prefill cost."""
        params = _params()
        paged = ContinuousBatcher(params, LLAMA_TINY, num_slots=4, max_len=128,
                                  decode_chunk=4, kv="paged", page_len=32)
        prefix = list(range(3, 3 + 64))  # exactly 2 full pages
        reqs = [paged.submit(prefix + [100 + i], max_new_tokens=4) for i in range(4)]
        out = paged.run()
        # 3 of the 4 requests reuse both prefix pages: 3 × 64 skipped tokens
        assert paged.prefix_hit_tokens == 3 * 64
        dense = ContinuousBatcher(params, LLAMA_TINY, num_slots=4, max_len=128,
                                  decode_chunk=4)
        rd = [dense.submit(prefix + [100 + i], max_new_tokens=4) for i in range(4)]
        outd = dense.run()
        for a, b in zip(rd, reqs):
            assert outd[a] == out[b]

    def test_late_arrival_reuses_resident_prefix(self):
        params = _params()
        paged = ContinuousBatcher(params, LLAMA_TINY, num_slots=2, max_len=128,
                                  decode_chunk=4, kv="paged", page_len=32)
        prefix = list(range(5, 5 + 32))
        r1 = paged.submit(prefix + [70], max_new_tokens=3)
        out1 = paged.run()
        # first request retired; its full prompt page parks in the reuse pool
        r2 = paged.submit(prefix + [71], max_new_tokens=3)
        out2 = paged.run()
        assert paged.prefix_hit_tokens == 32
        assert len(out2[r2]) == 3 and len(out1[r1]) == 3

    def test_small_pool_overcommit_waits_and_serves(self):
        """VERDICT done-when (b): pool smaller than slots × max_pages —
        admission waits for pages, every request still completes."""
        params = _params()
        paged = ContinuousBatcher(params, LLAMA_TINY, num_slots=4, max_len=128,
                                  decode_chunk=4, kv="paged", page_len=32,
                                  num_pages=9)  # 8 usable vs 4 slots × 4 pages
        rids = [paged.submit([5 + i], max_new_tokens=30) for i in range(6)]
        out = paged.run()
        assert len(out) == 6 and all(len(v) == 30 for v in out.values())
        assert paged.allocator.live_pages() == 0  # everything reclaimed

    def test_oversized_request_rejected_at_submit(self):
        params = _params()
        paged = ContinuousBatcher(params, LLAMA_TINY, num_slots=2, max_len=128,
                                  decode_chunk=4, kv="paged", page_len=32,
                                  num_pages=3)  # 2 usable pages = 64 positions
        with pytest.raises(ValueError, match="pages"):
            paged.submit(list(range(1, 100)), max_new_tokens=20)

    @pytest.mark.slow  # ~10 s int8 engine parity sweep
    def test_int8_paged_matches_dense(self):
        """Composition: int8 weight-only trees decode through the paged
        cache identically to the dense engine (the cache stays bf16; only
        the _mm dispatch differs)."""
        from tony_tpu.ops import quant

        params = _params()
        qparams, _, _ = quant.quantize_tree(params, min_size=1 << 10)
        dense = ContinuousBatcher(qparams, LLAMA_TINY, num_slots=2, max_len=64,
                                  decode_chunk=4)
        paged = ContinuousBatcher(qparams, LLAMA_TINY, num_slots=2, max_len=64,
                                  decode_chunk=4, kv="paged", page_len=32)
        a = dense.submit([3, 4, 5], max_new_tokens=6)
        b = paged.submit([3, 4, 5], max_new_tokens=6)
        assert dense.run()[a] == paged.run()[b]

    @pytest.mark.slow  # ~10 s mixtral engine parity sweep
    def test_mixtral_paged_matches_dense(self):
        """Composition: the MoE decode FFN (all-expert + top-k combine)
        runs through the paged cache identically to dense."""
        import dataclasses

        from tony_tpu.models import mixtral

        # f32: in bf16 a 1-ulp cross-implementation difference gets amplified
        # by the MoE router into a greedy-token flip on knife-edge prompts
        # (same pin as test_serving.TestMixtralServing)
        mcfg = dataclasses.replace(mixtral.MIXTRAL_TINY, max_seq=64, dtype="float32")
        params = mixtral.init(jax.random.PRNGKey(2), mcfg)
        dense = ContinuousBatcher(params, mcfg, num_slots=2, max_len=64,
                                  decode_chunk=4)
        paged = ContinuousBatcher(params, mcfg, num_slots=2, max_len=64,
                                  decode_chunk=4, kv="paged", page_len=32)
        a = dense.submit([5, 6, 7, 8], max_new_tokens=6)
        b = paged.submit([5, 6, 7, 8], max_new_tokens=6)
        assert dense.run()[a] == paged.run()[b]

    @pytest.mark.slow  # ~9 s SWA engine parity sweep
    def test_swa_window_smaller_than_chunk_matches_dense(self):
        """The staged fold's out-of-window mask only fires when the sliding
        window is SMALLER than the decode chunk (staged positions can fall
        below the band) — lock that case in."""
        import dataclasses

        cfg = dataclasses.replace(LLAMA_TINY, sliding_window=3)
        params = init(jax.random.PRNGKey(4), cfg)
        dense = ContinuousBatcher(params, cfg, num_slots=2, max_len=128,
                                  decode_chunk=8)
        paged = ContinuousBatcher(params, cfg, num_slots=2, max_len=128,
                                  decode_chunk=8, kv="paged", page_len=32)
        prompt = list(range(2, 2 + 20))
        a = dense.submit(prompt, max_new_tokens=12)
        b = paged.submit(prompt, max_new_tokens=12)
        assert dense.run()[a] == paged.run()[b]

    @pytest.mark.slow  # ~8 s SWA engine parity sweep
    def test_swa_paged_matches_dense(self):
        import dataclasses

        cfg = dataclasses.replace(LLAMA_TINY, sliding_window=48)
        params = init(jax.random.PRNGKey(1), cfg)
        dense = ContinuousBatcher(params, cfg, num_slots=2, max_len=128,
                                  decode_chunk=4)
        paged = ContinuousBatcher(params, cfg, num_slots=2, max_len=128,
                                  decode_chunk=4, kv="paged", page_len=32)
        prompt = list(range(2, 2 + 60))  # longer than the window
        a = dense.submit(prompt, max_new_tokens=10)
        b = paged.submit(prompt, max_new_tokens=10)
        assert dense.run()[a] == paged.run()[b]


# ---------------------------------------------------------------------------
# The order of a pass: admission in the shadow of the chunk in flight, the next chunk, then that chunk's tokens
# ---------------------------------------------------------------------------
def record(eng, monkeypatch):
    """Every program the engine dispatches and every host read of a chunk's
    tokens, in order: [(name, rids running at the call), ...]. The programs
    still run; `decode_chunk` hands its tokens back inside an object whose
    conversion to numpy is the read."""
    from tony_tpu.models import serving

    log = []

    class Tokens:
        def __init__(self, seq):
            self.seq = seq

        def __array__(self, dtype=None, copy=None):
            log.append(("read", []))
            return np.asarray(self.seq)

    def named(name, fn):
        def call(*args, **kw):
            log.append((name, sorted(r.rid for r in eng.running.values())))
            out = fn(*args, **kw)
            return (out[0], Tokens(out[1]), out[2]) if name == "decode_chunk" else out
        return call

    names = {"init_staging": "init_staging", "prefill_chunk": "prefill_chunk", "insert": "insert",
             "insert_dense": "insert", "decode_chunk": "decode_chunk", "decode_chunk_bucketed": "decode_chunk",
             "release": "release", "gather_prefix": "gather_prefix"}
    eng.programs = eng.programs._replace(**{
        field: named(name, getattr(eng.programs, field)) for field, name in names.items()
        if getattr(eng.programs, field) is not None})
    monkeypatch.setattr(serving, "_set_slot_token", named("set_token", serving._set_slot_token))
    return log


def in_flight(log):
    """[(name, chunks dispatched and not yet read when it was called), ...]:
    what the device had to run while the host did that."""
    out, flying = [], 0
    for name, _ in log:
        out.append((name, flying))
        flying += {"decode_chunk": 1, "read": -1}.get(name, 0)
    return out


def never_leaves_the_device_idle(log):
    """Between the first chunk and the last read, whatever the host did, a
    chunk was in flight: every read but the last found the next chunk
    dispatched already (two in flight), and everything of admission went out
    behind a chunk."""
    first = [name for name, _ in log].index("decode_chunk")
    reads = [n for name, n in in_flight(log) if name == "read"]
    rest = [n for name, n in in_flight(log)[first + 1:] if name != "read"]
    return reads[:-1] == [2] * (len(reads) - 1) and reads[-1] == 1 and min(rest) >= 1


def chunks(log):
    return [rids for name, rids in log if name == "decode_chunk"]


def _llama_case(**kw):
    from tony_tpu.models import generate

    # float32: the engine's plumbing against generate(); in bfloat16 the paged kernel's online softmax
    # and generate's whole one round a tied logit differently (on the parent commit too)
    cfg = dataclasses.replace(LLAMA_TINY, dtype="float32")
    params = init(jax.random.PRNGKey(0), cfg)

    def alone(prompt, n):
        return [int(t) for t in np.asarray(generate.generate(params, jnp.asarray([prompt]), cfg, max_new_tokens=n)[0])]

    return params, cfg, dict(max_len=128, **kw), alone


def _sala_case():
    from tony_tpu.models import minicpm_sala

    cfg = minicpm_sala.SALA_TINY
    params = minicpm_sala.init(jax.random.PRNGKey(3), cfg)
    kw = dict(max_len=128, kv="paged", page_len=8, prefill_chunk=32)
    ref = ContinuousBatcher(params, cfg, num_slots=1, decode_chunk=4, **kw)

    def alone(prompt, n):  # one request at a time through an engine of its own
        rid = ref.submit(prompt, n)
        return ref.run()[rid]

    return params, cfg, kw, alone


def _prompts(lengths, shared=0):
    rng = np.random.default_rng(12)
    head = rng.integers(1, 250, shared).tolist()
    return [head + rng.integers(1, 250, n - shared).tolist() for n in lengths]


CASES = {
    "dense": lambda: (_llama_case(kv="dense"), _prompts((5, 9, 3, 12, 7))),
    "paged": lambda: (_llama_case(kv="paged", page_len=16), _prompts((5, 19, 3, 33, 7))),
    "paged-chunked-prefill": lambda: (_llama_case(kv="paged", page_len=16, prefill_chunk=16), _prompts((5, 19, 40, 33, 7))),
    "shared-prefix": lambda: (_llama_case(kv="paged", page_len=16), _prompts((40, 37, 45, 39, 50), shared=32)),
    "minicpm-sala": lambda: (_sala_case(), _prompts((50, 41, 70, 36, 44))),
}


class TestPassOrder:
    @pytest.mark.parametrize("case", sorted(CASES))
    def test_admission_runs_behind_the_chunk_and_every_request_answers_as_it_does_alone(self, case, monkeypatch):
        """Two slots, chunks of 4, one long request (30 tokens) beside a queue
        of short ones (5, 5, 9, 5). Each short one's budget ends inside a chunk
        the host can name at its dispatch, so the next is staged, prefilled and
        inserted behind that chunk and decodes from the one after: no chunk
        runs with a slot empty while a request waits, every chunk's tokens are
        read with the next chunk dispatched already, all of admission goes out
        behind a chunk in flight, and the tokens are those each request gets
        alone."""
        (params, cfg, kw, alone), prompts = CASES[case]()
        budgets = (30, 5, 5, 9, 5)
        eng = ContinuousBatcher(params, cfg, num_slots=2, decode_chunk=4, **kw)
        log = record(eng, monkeypatch)
        rids = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
        done = eng.run()
        L, A, B, C, D = rids
        if case in ("dense", "paged"):
            assert chunks(log) == [[L, A], [L, B], [L, C], [L, C], [L, D], [L], [L], [L]]
        # (elsewhere a request is ready later, and by design: a prompt of several prefill chunks advances
        # one a pass, and a prompt whose first page a staged request is about to register waits to reuse it)
        assert never_leaves_the_device_idle(log), in_flight(log)
        # ... and all of it was there, behind a chunk: every later request's insert ran with L decoding
        names = [name for name, _ in log]
        assert names.count("insert") == names.count("set_token") == 5
        assert names.count("init_staging") == 5 and names.count("prefill_chunk") >= 5
        if case == "shared-prefix":
            assert eng.prefix_hit_tokens >= 3 * 32 and "gather_prefix" in names
        for rid, p, n in zip(rids, prompts, budgets):
            assert done[rid] == alone(p, n), f"request {rid} of {case}"

    def test_a_slot_refilled_behind_the_chunk_is_not_read_as_its_old_owners(self, monkeypatch):
        """The pass in which B is inserted into A's slot while A's last chunk
        flies: the chunk's tokens go to A, who is done; B holds nothing on the
        host yet, its first token is still the device's."""
        (params, cfg, kw, alone), prompts = CASES["paged"]()
        eng = ContinuousBatcher(params, cfg, num_slots=2, decode_chunk=4, **kw)
        rids = [eng.submit(p, n) for p, n in zip(prompts[:3], (30, 5, 5))]
        eng.step()                     # nothing was running: L and A admitted, first tokens taken; chunk 1 goes out
        assert sorted(len(r.out) for r in eng._decoding()) == [1, 1]
        slot = eng.request(rids[1]).slot
        eng.step()                     # B inserted behind chunk 1, chunk 2 over L and B behind that; chunk 1 read
        b = eng.request(rids[2])       # in A's slot, and in its own last chunk already: in no slot's name by now
        assert b.slot == slot and b.out == [] and b.first is not None and b.slot_s == 0.0
        assert eng.done[rids[1]] == alone(prompts[1], 5)
        eng.step()                     # chunk 2 read: B's first token comes with its chunk's
        assert len(b.out) == 5 and b.first is None and b.slot_s > 0 and eng.done[rids[2]] == alone(prompts[2], 5)

    def test_pages_released_and_handed_on_inside_one_pass_give_the_right_tokens(self, monkeypatch):
        """A pool that holds L's pages and one short request's, no more: B's
        pages ARE A's, released on the host and reserved again in one pass, the
        one that finds A's last chunk in flight, and written by B's insert behind
        that chunk."""
        (params, cfg, kw, alone), prompts = CASES["paged"]()
        # L: 5 + 32 positions -> 3 pages of 16; A, B, C: 19 + 8 -> 2 pages; pool of 5 (and the sacrificial page)
        prompts = [prompts[0], prompts[1], prompts[1][::-1], prompts[1][1:] + [7]]
        eng = ContinuousBatcher(params, cfg, num_slots=2, decode_chunk=4, num_pages=6, **kw)
        log = record(eng, monkeypatch)
        rids = [eng.submit(p, n) for p, n in zip(prompts, (30, 5, 5, 5))]
        pages = {}
        while eng.step():
            pages.update({r.rid: tuple(eng._slot_pages[r.slot]) for r in eng._decoding()})
        assert chunks(log)[:3] == [[rids[0], rids[1]], [rids[0], rids[2]], [rids[0], rids[3]]]
        assert set(pages[rids[1]]) == set(pages[rids[2]]) == set(pages[rids[3]])
        assert never_leaves_the_device_idle(log), in_flight(log)
        for rid, p, n in zip(rids, prompts, (30, 5, 5, 5)):
            assert eng.done[rid] == alone(p, n)
        assert eng.allocator.live_pages() == 0

    @pytest.mark.parametrize("ending", ["budget", "eos", "cancel"])
    def test_a_slot_is_refilled_for_the_next_chunk_when_the_host_could_foresee_the_end(self, ending, monkeypatch):
        """A's end against the chunk B first decodes in. By budget the host
        knows at the dispatch of A's last chunk: B is inserted behind it, and no
        chunk is lost. Cancelled between two passes, here with its last chunk in
        flight and its slot handed back already: that chunk's tokens are
        dropped. An EOS it sees only in
        the chunk's tokens, by when the chunk behind has gone out with A in it:
        the slot is lost for that chunk, the stated cost (the serial engine lost
        the same chunk, with the slot empty)."""
        (params, cfg, kw, alone), prompts = CASES["dense"]()
        l_out, a_out, b_out = alone(prompts[0], 30), alone(prompts[1], 9), alone(prompts[2], 5)
        eos = a_out[2]                  # A's third token: its second of chunk 1
        assert eos not in l_out + b_out + a_out[:2], "the prompts no longer suit this test"
        eng = ContinuousBatcher(params, cfg, num_slots=2, decode_chunk=4, eos_id=eos if ending == "eos" else -1, **kw)
        log = record(eng, monkeypatch)
        L, A, B = (eng.submit(p, n) for p, n in zip(prompts, (30, 5 if ending == "budget" else 9, 5)))
        eng.step()                      # start-up: L and A admitted with nothing running, chunk 1 over them
        eng.step()                      # chunk 2 behind it, chunk 1 read
        if ending == "cancel":
            assert eng.cancel(A)
        done = eng.run()
        assert chunks(log)[:3] == {"budget": [[L, A], [L, B], [L]],
                                   "eos": [[L, A], [L, A], [L, B]],
                                   "cancel": [[L, A], [L, A], [L, B]]}[ending]
        assert never_leaves_the_device_idle(log), in_flight(log)
        assert (done[L], done[B]) == (l_out, b_out)
        assert done.get(A) == {"budget": a_out[:5], "eos": a_out[:3], "cancel": None}[ending]
