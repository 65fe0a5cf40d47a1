"""Block-paged KV cache + shared-prefix reuse for the serving engine.

Dense slot caches cost HBM O(slots × max_len) regardless of occupancy, and
N requests with the same prompt prefix (the dominant production pattern)
prefill and store it N times. This module replaces the per-slot slab with a
PAGE POOL:

- storage: ``[L, P, Hkv, page_len, Dh]`` — P fixed-size pages shared by all
  slots; a slot's logical positions map through a per-slot page table. HBM
  tracks allocated pages, so mixed-length workloads fit ~max_len/avg_len
  more slots in the same footprint. The ragged decode kernel reads pages
  directly (ops/decode_attention.paged_decode_attention — same slab-DMA
  pipeline, one indirection).
- prefix cache: FULL prompt pages are content-addressed (the exact token
  prefix is the key). A new request reuses every matching full page —
  refcounted, never written after prefill (decode writes always land past
  the prompt), so sharing needs no copy-on-write — and prefills only the
  remainder. N same-prefix requests cost ~1 prefill.
- reservation: a request's worst-case pages (prompt + budget + chunk
  overshoot) are reserved at admission, so decode can never hit an empty
  pool mid-request; admission simply waits when pages are short, exactly
  like it waits for a free slot.

Host/device split follows the engine's: the allocator (free list,
refcounts, prefix chain, LRU reuse pool) is pure host bookkeeping between
steps; everything per-token stays in the jitted decode step.

Which layers READ a pool is the family's: ``L`` counts the layers that WRITE
keys and values, and most families give every layer that attends a layer of
the pool. models/phi4_flash.py has ``L = 1`` and eight readers (one layer's
keys and values, which seven later layers read again), and its rows hold a
PAIR of kv heads (``Hkv`` pairs of ``2 x head_dim``): the pool, the rings and
the chunk's write below know neither.

No reference counterpart (the reference does not serve); the engine-level
contract is tested against the dense-cache engine for parity and against
HBM/prefill accounting for the capacity and sharing wins.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import NamedTuple

import jax
import jax.numpy as jnp

from tony_tpu.models.llama import LlamaConfig
from tony_tpu.obs import metrics as obs_metrics

STATE_SNAPSHOTS = obs_metrics.counter(
    "tony_serve_state_snapshots_total",
    "snapshots of recurrent state at a prompt page's edge: taken at an insert, restored into a request that matched "
    "up to the page, dropped with the page's eviction or when their place in the store was given to a newer one",
    labelnames=("event",))


class PagedCache(NamedTuple):
    """Device state: page pools + per-slot views.

    k/v: [L, P, Hkv, page_len, Dh]; lengths: [S] cache positions;
    page_table: [S, max_pages] int32 — logical page j of slot s lives in
    physical page page_table[s, j]. Entries beyond a slot's live pages are
    never read (kernel loop bounds come from lengths)."""

    k: jax.Array
    v: jax.Array
    lengths: jax.Array
    page_table: jax.Array


def init_paged_cache(
    cfg: LlamaConfig, num_slots: int, max_len: int, page_len: int, num_pages: int
) -> PagedCache:
    if max_len % page_len:
        raise ValueError(f"max_len {max_len} must be a multiple of page_len {page_len}")
    max_pages = max_len // page_len
    return PagedCache(
        k=jnp.zeros((cfg.n_layers, num_pages, cfg.n_kv_heads, page_len, cfg.head_dim),
                    cfg.jdtype),
        v=jnp.zeros((cfg.n_layers, num_pages, cfg.n_kv_heads, page_len, cfg.head_dim),
                    cfg.jdtype),
        lengths=jnp.zeros((num_slots,), jnp.int32),
        page_table=jnp.zeros((num_slots, max_pages), jnp.int32),
    )


class PageAllocator:
    """Host-side page accounting: free list, refcounts, prefix chain.

    Pages move free → live (ref ≥ 1) → on release either back to free
    (unregistered) or into the REUSE POOL (registered full prompt pages,
    ref 0 but content valid — future prefix hits resurrect them; the pool
    is evicted LRU when fresh allocations outrun the free list)."""

    #: physical page 0 is SACRIFICIAL — never allocated. Idle slots (length
    #: 0, or retired-and-flushed with their page-table row reset to zeros)
    #: still run the decode step and write one garbage column per step;
    #: in the dense engine that lands in their own slab, here it must land
    #: somewhere that can never be another slot's live page.
    GARBAGE_PAGE = 0

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError(f"need >= 2 pages (page 0 is sacrificial), got {num_pages}")
        self.num_pages = num_pages
        self._free: list[int] = list(range(num_pages - 1, 0, -1))
        self._ref = [0] * num_pages
        self._chain: dict[tuple, int] = {}       # prefix key → page
        self._key_of: dict[int, tuple] = {}      # page → its chain key
        self._reusable: "OrderedDict[int, None]" = OrderedDict()  # ref==0, keyed
        # a family whose prefix leaves recurrent state beside its pages (models/olmo_hybrid.py) keeps that
        # state at some pages' edges in a store on the device: page -> its place there, oldest first
        self._state_at: "OrderedDict[int, int]" = OrderedDict()
        self._state_free: list[int] | None = None                 # places nothing is kept in (None: no store yet)

    # -- capacity ----------------------------------------------------------
    def available(self) -> int:
        return len(self._free) + len(self._reusable)

    def live_pages(self) -> int:
        return self.num_pages - 1 - self.available()  # page 0 never counts

    # -- allocation --------------------------------------------------------
    def alloc(self, n: int) -> list[int]:
        """n fresh pages (ref 1 each), evicting LRU reuse-pool pages as
        needed. Raises if the pool genuinely cannot supply them — callers
        check available() first (admission waits instead)."""
        if n > self.available():
            raise RuntimeError(
                f"page pool exhausted: want {n}, have {self.available()}"
            )
        out = []
        for _ in range(n):
            if self._free:
                p = self._free.pop()
            else:
                p, _ = self._reusable.popitem(last=False)  # LRU eviction
                del self._chain[self._key_of.pop(p)]
                self._drop_state(p)
            self._ref[p] = 1
            out.append(p)
        return out

    def release(self, page: int) -> None:
        self._ref[page] -= 1
        if self._ref[page] > 0:
            return
        if page in self._key_of:
            self._reusable[page] = None      # content stays valid for reuse
            self._reusable.move_to_end(page)
        else:
            self._free.append(page)
            self._drop_state(page)  # no key: nothing can match up to it

    # -- prefix chain ------------------------------------------------------
    def match_prefix(self, keys: list[tuple]) -> list[int]:
        """Longest chain of resident pages for cumulative prefix ``keys``;
        each matched page's refcount is taken (pinned) before returning."""
        got: list[int] = []
        for key in keys:
            p = self._chain.get(key)
            if p is None:
                break
            if self._ref[p] == 0:
                self._reusable.pop(p, None)  # resurrect from the reuse pool
            self._ref[p] += 1
            got.append(p)
        return got

    def has_key(self, key: tuple) -> bool:
        """Is this prefix page resident (live or reusable)? Cheap host
        lookup — the engine's burst dedup stops deferring followers the
        moment their leader registers."""
        return key in self._chain

    def register(self, page: int, key: tuple) -> None:
        """Content-address a LIVE full prompt page. First writer wins — a
        concurrent duplicate simply stays unregistered and frees normally."""
        owner = self._chain.get(key)
        if owner is None and page not in self._key_of:
            self._chain[key] = page
            self._key_of[page] = key
        elif owner is not None and page in self._state_at and owner not in self._state_at:
            # a duplicate of a resident page whose edge had lost its state (its place in the store given away):
            # the state kept at the duplicate's edge is the same prefix's, and the resident page is the one a
            # match finds
            self._state_at[owner] = self._state_at.pop(page)

    # -- state at a page's edge ----------------------------------------------
    # The state beside a chain of pages lives and dies with the chain's last
    # page: one manager and one eviction order. An entry goes when its page
    # is evicted from the reuse pool (or freed without a key) or when its
    # place in the store is given to a newer snapshot, the oldest first.
    def keep_state(self, page: int, store: int) -> int:
        """The state at live ``page``'s edge is about to be kept: its place in
        a store of ``store`` places (a free one, else the oldest entry's, which
        goes), or -1 where the page's edge has its state kept already."""
        if page in self._state_at:
            return -1
        if self._state_free is None:
            self._state_free = list(range(store - 1, -1, -1))
        if self._state_free:
            where = self._state_free.pop()
        else:
            _, where = self._state_at.popitem(last=False)
            STATE_SNAPSHOTS.inc(event="dropped")
        self._state_at[page] = where
        STATE_SNAPSHOTS.inc(event="taken")
        return where

    def state_at(self, page: int) -> int | None:
        return self._state_at.get(page)

    def deepest_state(self, pages: list[int]) -> int:
        """How many of a matched chain's pages a request may start from where
        state lies beside them: up to the deepest one whose edge has it kept."""
        return max((i + 1 for i, p in enumerate(pages) if p in self._state_at), default=0)

    def _drop_state(self, page: int) -> None:
        where = self._state_at.pop(page, None)
        if where is not None:
            self._state_free.append(where)
            STATE_SNAPSHOTS.inc(event="dropped")


def prefix_keys(prompt: list[int], page_len: int) -> list[tuple]:
    """Cumulative content keys for the prompt's FULL pages; page j's key
    covers tokens [0, (j+1)·page_len). Keys are (page_index, sha256-of-
    prefix) built INCREMENTALLY — one O(Tp) pass total, O(1) hashing per
    dict lookup — instead of materializing O(Tp²/page_len) token tuples
    (a 32k-token shared prefix is the stated workload). A 256-bit digest
    collision (~2⁻¹²⁸) is the standard paged-cache tradeoff."""
    import hashlib

    h = hashlib.sha256()
    out: list[tuple] = []
    for j in range(len(prompt) // page_len):
        page = prompt[j * page_len:(j + 1) * page_len]
        h.update(b"".join(t.to_bytes(8, "little", signed=True) for t in page))
        out.append((j, h.digest()))
    return out


# -- jitted device plumbing -------------------------------------------------

import functools


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("n",))
def gather_prefix_into_staging(
    staging,                             # KVCache [L, 1, Hkv, maxT, Dh] (donated)
    pk: jax.Array, pv: jax.Array,        # pools [L, P, Hkv, page_len, Dh]
    pages: jax.Array,                    # [n] matched physical pages
    n: int = 0,
):
    """Copy matched prefix pages into a request's dense staging cache (and
    set its length) so the remainder prefill writes at the right positions
    and attends the shared prefix. One HBM copy — negligible next to the
    prefill FLOPs it saves."""
    L, _, Hkv, page_len, Dh = pk.shape
    got_k = pk[:, pages]                 # [L, n, Hkv, page_len, Dh]
    got_v = pv[:, pages]
    flat_k = got_k.transpose(0, 2, 1, 3, 4).reshape(L, 1, Hkv, n * page_len, Dh)
    flat_v = got_v.transpose(0, 2, 1, 3, 4).reshape(L, 1, Hkv, n * page_len, Dh)
    sk = jax.lax.dynamic_update_slice(staging.k, flat_k, (0, 0, 0, 0, 0))
    sv = jax.lax.dynamic_update_slice(staging.v, flat_v, (0, 0, 0, 0, 0))
    return staging._replace(k=sk, v=sv, length=jnp.int32(n * page_len))


@functools.partial(jax.jit, static_argnames=("n",))
def gather_pages(pk: jax.Array, pv: jax.Array, pages: jax.Array, n: int = 0):
    """Read ``n`` physical pages out of the pools — the EXPORT half of the
    disaggregated KV handoff (serve/disagg.py): a prefill replica gathers
    its finished full-prompt pages into one [L, n, Hkv, page_len, Dh] pair
    to serialize toward the decode replica. One device gather, host copy at
    the caller (jax.device_get)."""
    return pk[:, pages], pv[:, pages]


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("n",))
def scatter_pages(
    cache: PagedCache,
    pages: jax.Array,                    # [n] destination physical pages
    vals_k: jax.Array, vals_v: jax.Array,  # [L, n, Hkv, page_len, Dh]
    n: int = 0,
):
    """Write ``n`` received pages into the pools in place (donated) — the
    ADOPT half of the KV handoff. The caller (engine thread) has already
    alloc()'d the destination pages, so nothing live is overwritten; a
    fori_loop of per-page dynamic_update_slice keeps the update aliasing
    the donated pool, same shape discipline as insert_paged_prefill."""
    L, _, Hkv, page_len, Dh = cache.k.shape

    def body(j, kv):
        k, v = kv
        k = jax.lax.dynamic_update_slice(
            k, jax.lax.dynamic_slice(vals_k, (0, j, 0, 0, 0),
                                     (L, 1, Hkv, page_len, Dh)),
            (0, pages[j], 0, 0, 0))
        v = jax.lax.dynamic_update_slice(
            v, jax.lax.dynamic_slice(vals_v, (0, j, 0, 0, 0),
                                     (L, 1, Hkv, page_len, Dh)),
            (0, pages[j], 0, 0, 0))
        return k, v

    k, v = jax.lax.fori_loop(0, n, body, (cache.k, cache.v))
    return cache._replace(k=k, v=v)


@functools.partial(jax.jit, donate_argnums=(0,))
def insert_paged_prefill(
    cache: PagedCache,
    sk: jax.Array, sv: jax.Array,        # staging [L, 1, Hkv, maxT, Dh]
    fresh_pages: jax.Array,              # [max_pages] physical pages, first n live
    pt_row: jax.Array,                   # [max_pages] the slot's full page table row
    slot: jax.Array, true_len: jax.Array,
    j0: jax.Array,                       # [] int32 — first NON-shared logical page
    n: jax.Array | int = 0,              # [] int32 — pages to copy (dynamic)
):
    """Admission commit: copy the slot's NON-shared prefill span (logical
    pages j0..j0+n) from staging into its fresh physical pages, and install
    the page-table row + length. Shared prefix pages (j < j0) are already
    resident — installing the row is all it takes to attach them.

    The copy is a dynamic-trip fori_loop of per-page dynamic_update_slice
    ops: the staging slice [L, 1, Hkv, page_len, Dh] is axis-for-axis the
    pool's per-page layout, so each dus aliases the DONATED pool in place
    with no transpose, and the traced trip count + fixed-width
    ``fresh_pages`` mean ONE compiled variant covers every page-count
    class. The previous one-shot index-array scatter
    (`.at[:, fresh_pages].set(span)`) materialized a pool-sized copy per
    admission — the entirety of the paged engine's admission-side deficit
    vs dense (builders' r5 run, older than this code)."""
    L, _, Hkv, page_len, Dh = cache.k.shape

    def body(j, kv):
        k, v = kv
        pk = jax.lax.dynamic_slice(
            sk, (0, 0, 0, (j0 + j) * page_len, 0), (L, 1, Hkv, page_len, Dh)
        )
        pv = jax.lax.dynamic_slice(
            sv, (0, 0, 0, (j0 + j) * page_len, 0), (L, 1, Hkv, page_len, Dh)
        )
        k = jax.lax.dynamic_update_slice(k, pk, (0, fresh_pages[j], 0, 0, 0))
        v = jax.lax.dynamic_update_slice(v, pv, (0, fresh_pages[j], 0, 0, 0))
        return k, v

    k, v = jax.lax.fori_loop(0, n, body, (cache.k, cache.v))
    return PagedCache(
        k=k, v=v,
        lengths=cache.lengths.at[slot].set(true_len),
        page_table=cache.page_table.at[slot].set(pt_row),
    )


# -- a window layer's cache: a ring a slot ---------------------------------------
#
# A layer whose queries see the last W positions needs W of them and the decode
# chunk in flight, whatever the context's length. Its cache is a pool of its own
# with ONE page a slot, `ring` positions long, used as a ring: position p of slot s
# lives at row p % ring of page s. A decode step reads a slot's ring whole, once
# (ops/decode_attention.ring_decode_attention: a block of slots is one rectangular
# block, so no table is needed to find it), and masks a row by the position it
# holds: the newest one below the pool's part of the slot that is congruent to the
# row, read iff it lies in [len + 1 - W, len - staged). That is right as long as
# the ring holds every position a step may read and the chunk's write lands on none
# of them: ring >= W + chunk - 1. An older position (an earlier lap, or the slot's
# last tenant) lies below the window's start and is masked by position.
# `ring_table`, in which every logical page of slot s is page s, serves the chunk's
# write only: write_decode_chunk writes the ring as it writes any pool.

#: rows of a ring beyond the window: the decode chunk may be this long (+ 1)
RING_SLACK = 16


def init_window_rings(layers: int, num_slots: int, kv_heads: int, window: int, head_dim: int, dtype):
    """(k, v) [layers, slots, Hkv, window + RING_SLACK, Dh]: memory that does not grow with max_len."""
    shape = (layers, num_slots, kv_heads, window + RING_SLACK, head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def ring_table(num_slots: int, max_len: int, ring: int) -> jax.Array:
    """[S, pages that max_len positions span + 1]: every logical page of slot s is physical page s."""
    return jnp.broadcast_to(jnp.arange(num_slots, dtype=jnp.int32)[:, None], (num_slots, -(-max_len // ring) + 1))


def insert_window_rings(rk: jax.Array, rv: jax.Array, tail_k: jax.Array, tail_v: jax.Array, slot, true_len):
    """Admission: a request's last W prefilled positions into its slot's rings.
    tail_k/v [Lw, Hkv, W, Dh] hold positions true_len - W .. true_len - 1 in
    order (those below 0 do not exist). The slot's whole page is written, one
    contiguous update of the donated rings: rows of positions the tail does
    not hold are zeroed, and no step reads them (they lie below the window of
    every later position)."""
    ring, w = rk.shape[3], tail_k.shape[2]
    row = jnp.arange(ring)
    pos = true_len - 1 - (true_len - 1 - row) % ring          # the newest position below true_len that lives in the row
    at = pos - (true_len - w)
    held = ((pos >= 0) & (at >= 0))[None, None, :, None]

    def page(tail):
        return jnp.where(held, jnp.take(tail, jnp.clip(at, 0, w - 1), axis=2), 0)[:, None].astype(rk.dtype)

    return (jax.lax.dynamic_update_slice(rk, page(tail_k), (0, slot, 0, 0, 0)),
            jax.lax.dynamic_update_slice(rv, page(tail_v), (0, slot, 0, 0, 0)))


def write_decode_chunk(
    pk: jax.Array, pv: jax.Array,        # pools [L, P, Hkv, page_len, Dh] (donated by the caller's jit)
    stage_k: jax.Array, stage_v: jax.Array,  # [L, S, n, Hkv, Dh] — the chunk's staged columns
    len0: jax.Array,                     # [S] int32 — each slot's length when the chunk began
    page_table: jax.Array,               # [S, max_pages]
):
    """A decode chunk's ONE pool write, in place: staged column (slot s, step
    j) lands at position ``len0[s] + j`` of slot s. Called inside
    ``serving.decode_steps``' jit, which donates the pools.

    A slot's ``n`` positions lie in at most two pages (``n <= page_len``; a
    longer chunk touches more, a window a page), so each slot is two
    read-modify-write windows of ``n`` rows, one a page: slice the window
    out of the page, take the staged row wherever the window's position is
    one of the chunk's, and write it back. The window is shaped like the
    pool (``[L, 1, Hkv, n, Dh]`` at ``(0, page, 0, offset, 0)``), so every
    ``dynamic_update_slice`` aliases the donated pool with no transpose, and
    nothing of the pool's size is made. (The one-shot form,
    ``pool.at[:, pages, :, offs, :].set(cols)``, has index arrays on two
    dimensions that are not adjacent: XLA transposes the whole pool, scatters
    and transposes it back, four pool-sized copies a chunk. A loop of one-ROW
    updates makes it relayout the pool around the loop instead.)

    Edges. A window never runs off its page: the first is pulled back to
    ``page_len - n`` where it would (``dynamic_update_slice`` clamps its
    start and does not fail, so the rows are placed by position, not by
    trust) and rewrites rows of its own page unchanged. An idle slot
    (``len0 == 0``) and positions past ``maxT - 1`` take no staged row: both
    windows write back what they read. A write therefore lands only in the
    pages a live slot's own table gives for positions ``len0 ..
    len0 + n - 1``: past the prompt, so never in a shared prefix page."""
    L, _, Hkv, page_len, Dh = pk.shape
    S, n = stage_k.shape[1:3]
    max_pages = page_table.shape[1]
    max_t = max_pages * page_len
    w = min(n, page_len)                              # rows a window
    windows = (n + page_len - 2) // page_len + 1      # pages n positions can touch
    start = jnp.minimum(len0, max_t - 1)
    first_page = start // page_len
    rows = jnp.arange(w, dtype=jnp.int32)
    window = (L, 1, Hkv, w, Dh)

    def padded(stage):
        # [L, S, Hkv, 3n, Dh]: the pool's axis order, n rows of padding on
        # either side, so a window's staged rows are one slice at n + shift
        return jnp.pad(stage.transpose(0, 1, 3, 2, 4), ((0, 0), (0, 0), (0, 0), (n, n), (0, 0)))

    stage_k, stage_v = padded(stage_k), padded(stage_v)

    def write_slot(s, kv):
        for i in range(windows):
            logical = first_page[s] + i
            off = jnp.minimum(start[s] % page_len, page_len - w) if i == 0 else jnp.int32(0)
            first = logical * page_len + off          # position of the window's row 0
            shift = jnp.clip(first - len0[s], -n, n)  # staged step of the window's row 0
            take = (len0[s] > 0) & (rows + shift >= 0) & (rows + shift < n) & (first + rows < max_t)
            take = take[None, None, None, :, None]
            at = (0, page_table[s, jnp.minimum(logical, max_pages - 1)], 0, off, 0)
            kv = tuple(
                jax.lax.dynamic_update_slice(
                    pool,
                    jnp.where(
                        take,
                        jax.lax.dynamic_slice(stage, (0, s, 0, n + shift, 0), window),
                        jax.lax.dynamic_slice(pool, at, window),
                    ),
                    at,
                )
                for pool, stage in zip(kv, (stage_k, stage_v))
            )
        return kv

    return jax.lax.fori_loop(0, S, write_slot, (pk, pv))


# -- a latent cache: one row a position for all heads -------------------------------------------
#
# A latent-attention layer keeps no keys and values a head: it keeps ONE row a position (the normed latent and
# the rope key the heads share), and a layer with an indexer a second, narrower row beside it (the index key).
# So its pools are [L, P, page_len, width], one a kind of row, filled and written together: `pools` below is a
# tuple of them, `staged` / `stages` the matching tuple of what goes in. The host side (PageAllocator, page
# tables, reservation) is the one above. A window layer's rows live in a ring a slot, as a key-value window
# layer's do: [L, slots, ring, width], one page a slot, written by the same chunk write through `ring_table`
# and read whole by position (`latent_ring_valid`).


def init_latent_pools(layers: int, num_pages: int, page_len: int, widths: tuple, dtype) -> tuple:
    """A pool a width: [layers, num_pages, page_len, width]."""
    return tuple(jnp.zeros((layers, num_pages, page_len, w), dtype) for w in widths)


def latent_ring_len(window: int) -> int:
    """Rows of a latent ring: the window, the decode chunk in flight, up to whole tiles of 128."""
    return -(-(window + RING_SLACK) // 128) * 128


def insert_latent_prefill(pools: tuple, staged: tuple, fresh_pages: jax.Array, j0: jax.Array, n: jax.Array) -> tuple:
    """Admission: logical pages j0 .. j0 + n of a request's staged rows (each
    [L, max_len, width], at their true positions) into its fresh physical pages,
    in place on the donated pools: one dynamic_update_slice a page and pool, the
    trip count traced (one compiled variant for every page count)."""
    page_len = pools[0].shape[2]

    def body(j, pools):
        return tuple(
            jax.lax.dynamic_update_slice(
                pool, jax.lax.dynamic_slice_in_dim(rows, (j0 + j) * page_len, page_len, axis=1)[:, None],
                (0, fresh_pages[j], 0, 0))
            for pool, rows in zip(pools, staged))

    return jax.lax.fori_loop(0, n, body, tuple(pools))


def gather_latent_prefix(staged: tuple, pools: tuple, pages: jax.Array, n: jax.Array) -> tuple:
    """A prefix hit on latent pools: `insert_latent_prefill` the other way. The
    matched physical pages `pages[:n]` ([max_pages], the rest unread) are copied
    into a request's staged rows (each [L, max_len, width]) at logical pages
    0 .. n, in place on the donated staging, the trip count traced: one compiled
    variant for every length of prefix. `gather_prefix_into_staging` for rows
    without a head axis; where pages are all a prefix leaves behind (no ring, no
    index key, no recurrent state beside them) this is the whole of a hit."""
    page_len = pools[0].shape[2]

    def body(j, staged):
        return tuple(
            jax.lax.dynamic_update_slice(
                rows, jax.lax.dynamic_slice(pool, (0, pages[j], 0, 0), (pool.shape[0], 1, page_len, pool.shape[3]))[:, 0],
                (0, j * page_len, 0))
            for rows, pool in zip(staged, pools))

    return jax.lax.fori_loop(0, n, body, tuple(staged))


def insert_latent_rings(ring: jax.Array, tail: jax.Array, slot, true_len) -> jax.Array:
    """Admission: a request's last prefilled positions into its slot's rings.
    ring [Lw, S, R, width]; tail [Lw, W, width] holds positions true_len - W ..
    true_len - 1 in order (those below 0 do not exist). The slot's whole page is
    written: rows of positions the tail does not hold are zeroed, and no step
    reads them (`latent_ring_valid` masks by position)."""
    R, w = ring.shape[2], tail.shape[1]
    row = jnp.arange(R)
    pos = true_len - 1 - (true_len - 1 - row) % R             # the newest position below true_len that lives in the row
    at = pos - (true_len - w)
    held = ((pos >= 0) & (at >= 0))[None, :, None]
    page = jnp.where(held, jnp.take(tail, jnp.clip(at, 0, w - 1), axis=1), 0)[:, None].astype(ring.dtype)
    return jax.lax.dynamic_update_slice(ring, page, (0, slot, 0, 0))


def latent_ring_valid(ring_len: int, pool_len: jax.Array, pos: jax.Array, window: int) -> jax.Array:
    """bool [S, ring_len]: whether a decode step at position `pos` [S] reads a
    ring's row. Row rho holds the newest position below `pool_len` [S] (what was
    written before the chunk in flight) congruent to it; it is read iff that
    position exists and lies inside the window, which counts `pos` itself."""
    row = jnp.arange(ring_len)[None, :]
    held = pool_len[:, None] - 1 - (pool_len[:, None] - 1 - row) % ring_len
    return (held >= 0) & (held > pos[:, None] - window)


def write_latent_chunk(pools: tuple, stages: tuple, len0: jax.Array, page_table: jax.Array) -> tuple:
    """A decode chunk's one write into latent pools (or rings, through
    `ring_table`), in place: staged row (slot s, step j) of stages[i] [L, S, n,
    width] lands at position len0[s] + j of slot s in pools[i]. `write_decode_chunk`
    for rows without a head axis: the same two read-modify-write windows a slot,
    placed by position, an idle slot (len0 == 0) and positions past the table's
    end writing back what they read."""
    page_len = pools[0].shape[2]
    S, n = stages[0].shape[1:3]
    max_pages = page_table.shape[1]
    max_t = max_pages * page_len
    w = min(n, page_len)
    windows = (n + page_len - 2) // page_len + 1
    start = jnp.minimum(len0, max_t - 1)
    first_page = start // page_len
    rows = jnp.arange(w, dtype=jnp.int32)
    padded = tuple(jnp.pad(st, ((0, 0), (0, 0), (n, n), (0, 0))) for st in stages)        # [L, S, 3n, width]

    def write_slot(s, pools):
        for i in range(windows):
            logical = first_page[s] + i
            off = jnp.minimum(start[s] % page_len, page_len - w) if i == 0 else jnp.int32(0)
            first = logical * page_len + off
            shift = jnp.clip(first - len0[s], -n, n)
            take = ((len0[s] > 0) & (rows + shift >= 0) & (rows + shift < n) & (first + rows < max_t))[None, None, :, None]
            page = page_table[s, jnp.minimum(logical, max_pages - 1)]
            out = []
            for pool, stage in zip(pools, padded):
                window = (pool.shape[0], 1, w, pool.shape[3])
                at = (0, page, off, 0)
                new = jax.lax.dynamic_slice(stage, (0, s, n + shift, 0), window)
                out.append(jax.lax.dynamic_update_slice(pool, jnp.where(take, new, jax.lax.dynamic_slice(pool, at, window)), at))
            pools = tuple(out)
        return pools

    return jax.lax.fori_loop(0, S, write_slot, tuple(pools))
