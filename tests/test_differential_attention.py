"""Differential attention over the one-map kernels (ops/attention.py, ops/decode_attention.py): queries widened to a
kv-head PAIR with zeros, so that both softmax maps of a head pair are ONE call of a one-map kernel over a cache of
pairs. Each form under the interpreter against the FOUR-CALL form (q1k1v1, q1k1v2, q2k2v1, q2k2v2 at heads of dh,
which reads every key and value twice) and against plain softmax; the one-map calls are the tests they were
(tests/test_paged.py, tests/test_exaone_moe.py). The family that runs them is tests/test_phi4_flash.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.ops import attention as A
from tony_tpu.ops import decode_attention as DA

H, HKV, DH = 8, 4, 16          # two stripes of 4 query heads over 2 kv heads each


@pytest.fixture(scope="module", autouse=True)
def _interpreted(interpreted):
    pass


def _stripes(a):
    return a[..., 0::2, :], a[..., 1::2, :]


def _pairs(a):
    """[.., Hkv, dh] -> [.., Hkv / 2, 2 dh]: kv heads 2p and 2p + 1 side by side."""
    return a.reshape(*a.shape[:-2], a.shape[-2] // 2, 2 * a.shape[-1])


def _plain(q, k, v, seen):
    """Both maps by plain softmax: q [T, H, dh]; k, v [Tk, Hkv, dh]; seen [T, Tk]. Returns [T, H, 2 dh], head 2a the
    first map of pair-row a and head 2a + 1 its second, as the widened one-map call lays them."""
    (q1, q2), (k1, k2), (v1, v2) = _stripes(q), _stripes(k), _stripes(v)
    vp = jnp.concatenate([v1, v2], axis=-1)
    rep = (q.shape[1] // 2) // (k.shape[1] // 2)

    def a_map(qs, ks):
        s = jnp.einsum("tad,kad->atk", qs, jnp.repeat(ks, rep, axis=1)) * DH ** -0.5
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("atk,kad->tad", p, jnp.repeat(vp, rep, axis=1))

    return jnp.stack([a_map(q1, k1), a_map(q2, k2)], axis=2).reshape(q.shape[0], q.shape[1], 2 * DH)


def _draw(seed, *shapes):
    return [jax.random.normal(k, s, jnp.float32) for k, s in zip(jax.random.split(jax.random.PRNGKey(seed), len(shapes)), shapes)]


def test_widened_queries_score_their_own_stripes_key():
    q, k = _draw(0, (3, H, DH), (3, HKV, DH))
    wide, pairs = A.differential_queries(q), _pairs(k)
    assert wide.shape == (3, H, 2 * DH) and pairs.shape == (3, HKV // 2, 2 * DH)
    for h in range(H):
        want = jnp.sum(q[:, h] * k[:, 2 * (h // 4) + h % 2], axis=-1) * DH ** -0.5       # head h: stripe h % 2, kv head (h // 2) // 2 of it
        got = jnp.sum(wide[:, h] * pairs[:, h // 4], axis=-1) * (2 * DH) ** -0.5
        assert np.allclose(got, want, rtol=1e-5, atol=1e-6), h


def test_the_combine_subtracts_norms_and_lays_the_rows_back_as_heads():
    (o,) = _draw(1, (5, H, 2 * DH))
    w = 1.0 + 0.1 * jnp.arange(2 * DH, dtype=jnp.float32)
    got = A.differential_combine(o, 0.37, 0.2, w, 1e-5)
    d = o[:, 0::2] - 0.37 * o[:, 1::2]
    want = d / jnp.sqrt(jnp.mean(d * d, axis=-1, keepdims=True) + 1e-5) * w * 0.8
    assert got.shape == (5, H // 2, 2 * DH) and np.allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("pos0,T,Tk", [(0, 32, 64), (32, 32, 64), (16, 16, 128)], ids=["first-chunk", "second-chunk", "a-short-chunk-mid-way"])
def test_chunk_prefill_one_call_is_the_four_call_form(pos0, T, Tk):
    q, k, v = _draw(2, (T, H, DH), (Tk, HKV, DH), (Tk, HKV, DH))
    staged_k, staged_v = (_pairs(a).transpose(1, 0, 2)[None, None] for a in (k, v))           # [1, 1, Hkv / 2, Tk, 2 dh]
    got = A.differential_chunk_prefill_attention(q, staged_k, staged_v, jnp.int32(pos0), jnp.int32(pos0 + T), jnp.int32(0))
    seen = jnp.arange(Tk)[None, :] <= (pos0 + jnp.arange(T))[:, None]
    assert np.abs(np.asarray(got) - np.asarray(_plain(q, k, v, seen))).max() < 2e-5
    # the four-call form: each stripe's map at heads of dh against each half of the values, every key read twice
    (q1, q2), (k1, k2), (v1, v2) = _stripes(q), _stripes(k), _stripes(v)
    call = lambda qs, ks, vs: A.chunk_prefill_attention(qs.transpose(1, 0, 2), ks.transpose(1, 0, 2), vs.transpose(1, 0, 2),
                                                         jnp.int32(pos0), jnp.int32(pos0 + T)).transpose(1, 0, 2)
    four = jnp.stack([jnp.concatenate([call(q1, k1, v1), call(q1, k1, v2)], -1), jnp.concatenate([call(q2, k2, v1), call(q2, k2, v2)], -1)], axis=2)
    assert np.abs(np.asarray(got) - np.asarray(four.reshape(T, H, 2 * DH))).max() < 2e-5


@pytest.mark.parametrize("pos0,take", [(0, 32), (32, 32), (32, 11)], ids=["a-sequences-start", "past-the-window", "a-padded-chunk"])
def test_window_prefill_sees_the_window_and_keeps_the_next_tail(pos0, take):
    W, T = 8, 32
    q, k, v = _draw(3, (T, H, DH), (pos0 + T, HKV, DH), (pos0 + T, HKV, DH))
    before = lambda a: jnp.pad(_pairs(a[:pos0]), ((max(W - pos0, 0), 0), (0, 0), (0, 0)))[-W:].transpose(1, 0, 2)     # positions pos0 - W .. pos0 - 1
    got, ek, ev = A.differential_window_prefill_attention(q, _pairs(k[pos0:]), _pairs(v[pos0:]), before(k), before(v), jnp.int32(pos0), W)
    qpos, kpos = pos0 + jnp.arange(T), jnp.arange(pos0 + T)
    seen = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] > qpos[:, None] - W)
    assert np.abs(np.asarray(got) - np.asarray(_plain(q, k, v, seen))).max() < 2e-5
    tail = jax.lax.dynamic_slice_in_dim(ek, take, W, axis=1)                               # the caller's next tail: positions pos0 + take - W ..
    want = jnp.pad(_pairs(k[:pos0 + take]), ((max(W - pos0 - take, 0), 0), (0, 0), (0, 0)))[-W:].transpose(1, 0, 2)
    assert np.array_equal(np.asarray(tail), np.asarray(want)) and ev.shape == ek.shape


def _paged_case(seed, lengths, page_len=16, pages=4, staged=4, counts=None):
    S = len(lengths)
    q, k, v, cur_k, cur_v = _draw(seed, (S, H, DH), (S, pages * page_len, HKV, DH), (S, pages * page_len, HKV, DH), (S, HKV, DH), (S, HKV, DH))
    table = 1 + np.arange(S * pages, dtype=np.int32).reshape(S, pages)
    pool = lambda a: jnp.zeros((1, 1 + S * pages, HKV // 2, page_len, 2 * DH)).at[0, 1:].set(
        _pairs(a).reshape(S * pages, page_len, HKV // 2, 2 * DH).transpose(0, 2, 1, 3))
    counts = np.zeros(S, np.int32) if counts is None else np.asarray(counts, np.int32)
    sk, sv = _draw(seed + 100, (S, staged, HKV, DH), (S, staged, HKV, DH))
    return q, k, v, cur_k, cur_v, jnp.asarray(table), pool, jnp.asarray(lengths, jnp.int32), sk, sv, jnp.asarray(counts)


def _decode_want(q, k, v, cur_k, cur_v, lengths, sk, sv, counts, window=0):
    """Plain softmax a slot over [the cache's positions below length - count ; the staged rows ; the current token]."""
    out = []
    for s in range(q.shape[0]):
        n, c = int(lengths[s]), int(counts[s])
        keys = jnp.concatenate([k[s, :n - c], sk[s, :c], cur_k[s][None]])
        vals = jnp.concatenate([v[s, :n - c], sv[s, :c], cur_v[s][None]])
        seen = jnp.arange(n + 1) > n - window if window else jnp.ones((n + 1,), bool)
        out.append(_plain(q[s][None], keys, vals, seen[None])[0])
    return jnp.stack(out)


@pytest.mark.parametrize("lengths,counts", [([40, 17, 0], None), ([40, 64, 5], [3, 0, 4])], ids=["pages-alone", "with-staged-rows"])
def test_paged_decode_one_call_reads_the_pairs_once(lengths, counts):
    q, k, v, cur_k, cur_v, table, pool, lengths, sk, sv, counts = _paged_case(4, lengths, counts=counts)
    got = DA.differential_paged_decode_attention(q, pool(k), pool(v), lengths, table, jnp.int32(0), cur_k=_pairs(cur_k), cur_v=_pairs(cur_v),
                                                 staged_k=_pairs(sk), staged_v=_pairs(sv), staged_count=counts)
    want = _decode_want(q, k, v, cur_k, cur_v, lengths, sk, sv, counts)
    assert got.shape == (len(lengths), H, 2 * DH) and np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5


def test_paged_decode_one_call_is_the_four_call_form():
    """Four calls of the one-map kernel at heads of dh over pools of stripes: each page is read twice."""
    q, k, v, cur_k, cur_v, table, pool, lengths, sk, sv, counts = _paged_case(5, [33, 50], counts=[2, 0])
    got = DA.differential_paged_decode_attention(q, pool(k), pool(v), lengths, table, jnp.int32(0), cur_k=_pairs(cur_k), cur_v=_pairs(cur_v),
                                                 staged_k=_pairs(sk), staged_v=_pairs(sv), staged_count=counts)
    S, pages, page_len = 2, 4, 16
    stripe_pool = lambda a: jnp.zeros((1, 1 + S * pages, HKV // 2, page_len, DH)).at[0, 1:].set(
        a.reshape(S * pages, page_len, HKV // 2, DH).transpose(0, 2, 1, 3))
    (q1, q2), (k1, k2), (v1, v2) = _stripes(q), _stripes(k), _stripes(v)
    (ck1, ck2), (cv1, cv2), (sk1, sk2), (sv1, sv2) = _stripes(cur_k), _stripes(cur_v), _stripes(sk), _stripes(sv)
    call = lambda qs, ks, vs, ck, cv, s_k, s_v: DA.paged_decode_attention(
        qs, stripe_pool(ks), stripe_pool(vs), lengths, table, jnp.int32(0), cur_k=ck, cur_v=cv, staged_k=s_k, staged_v=s_v, staged_count=counts)
    first = jnp.concatenate([call(q1, k1, v1, ck1, cv1, sk1, sv1), call(q1, k1, v2, ck1, cv2, sk1, sv2)], -1)
    second = jnp.concatenate([call(q2, k2, v1, ck2, cv1, sk2, sv1), call(q2, k2, v2, ck2, cv2, sk2, sv2)], -1)
    assert np.abs(np.asarray(got) - np.asarray(jnp.stack([first, second], axis=2).reshape(S, H, 2 * DH))).max() < 2e-5


@pytest.mark.parametrize("lengths,counts", [([5, 8, 0, 30], [0, 0, 0, 0]), ([12, 40, 9, 33], [3, 4, 0, 2])], ids=["rings-alone", "with-staged-rows"])
def test_ring_decode_one_call_masks_by_position_past_the_windows_edge(lengths, counts):
    from tony_tpu.models.paged_cache import RING_SLACK

    W, S = 8, len(lengths)
    ring = W + RING_SLACK
    q, k, v, cur_k, cur_v, sk, sv = _draw(6, (S, H, DH), (S, 48, HKV, DH), (S, 48, HKV, DH), (S, HKV, DH), (S, HKV, DH), (S, 4, HKV, DH), (S, 4, HKV, DH))
    counts = np.asarray(counts, np.int32)

    def rings(a):      # position p of slot s at row p % ring, the newest below the pool's part of the slot
        out = np.zeros((1, S, HKV // 2, ring, 2 * DH), np.float32)
        for s in range(S):
            for p in range(int(lengths[s]) - int(counts[s])):
                out[0, s, :, p % ring] = np.asarray(_pairs(a[s, p]))
        return jnp.asarray(out)

    got = DA.differential_ring_decode_attention(q, rings(k), rings(v), jnp.asarray(lengths, jnp.int32), jnp.int32(0), cur_k=_pairs(cur_k), cur_v=_pairs(cur_v),
                                                window=W, staged_k=_pairs(sk), staged_v=_pairs(sv), staged_count=jnp.asarray(counts))
    want = _decode_want(q, k, v, cur_k, cur_v, lengths, sk, sv, counts, window=W)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5


def test_the_one_map_calls_lower_as_they_did_beside_the_new_names():
    """The differential forms are callers of the one-map kernels: nothing of `paged_decode_attention`,
    `ring_decode_attention` or `chunk_prefill_attention` takes a new argument."""
    import inspect

    assert list(inspect.signature(DA.paged_decode_attention).parameters)[-3:] == ["staged_k", "staged_v", "staged_count"]
    assert "scale" not in inspect.signature(A.chunk_prefill_attention).parameters
    assert "name" not in inspect.signature(DA.ring_decode_attention.__wrapped__).parameters
