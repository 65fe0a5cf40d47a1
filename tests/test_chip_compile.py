"""The kernels of the chip's main path, compiled for the chip without the chip.

The TPU's compiler is installed here and compiles for a v5e that is described,
not attached (on-chip-measurement guide, section 2). Interpret mode cannot see
what it refuses — a slice not aligned to the tiling, too much fast memory, a
kernel GSPMD cannot partition — so the kernels `chip_smoke.py` runs are
compiled here at llama-1b shapes, about two seconds each. A compile that
passes is not a chip run.

All of it lives in this one file and inside fixtures: only one process may
load the TPU's library, the driver runs the suite under several xdist workers,
and every worker imports every test file. Nothing here touches the topology
at import; the worker that is handed this file describes it in `topo`, every
test skips from there where it cannot be described, and the compiles run in
the test's own process.
"""

import contextlib
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from tony_tpu.models import llama
from tony_tpu.ops import attention as A
from tony_tpu.ops import decode_attention as DA
from tony_tpu.ops import moe_gemm as MG
from tony_tpu.ops import quant as Q

# llama-1b attention geometry (llama.LLAMA_1B) and chip_smoke's sizes
B, H, HKV, T, DH = 8, 16, 8, 2048, 128
SLOTS, MAX_LEN = 64, 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever stops the description is the reason
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@contextlib.contextmanager
def _compiling_for(topo):
    """One described chip to compile for; the kernels compile for real (the
    CPU suite's interpret switch is read when a kernel is traced) and nothing
    is written to a persistent compile cache that could not be read back."""
    with pytest.MonkeyPatch.context() as patch:
        patch.delenv("TONY_PALLAS_INTERPRET", raising=False)
        cache_was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture()
def chip(topo):
    with _compiling_for(topo) as one_chip:
        yield one_chip


def _s(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_calls(fn, *args) -> int:
    """Compile ``fn`` for the described chip; how many Mosaic kernels it holds."""
    return jax.jit(fn).lower(*args).compile().as_text().count("tpu_custom_call")


def _flash_grad(**kw):
    def loss(q, k, v, *seg):
        extra = {"segment_ids": seg[0]} if seg else {}
        return A.mha(q, k, v, causal=True, impl="flash", **kw, **extra).astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2))


class TestFlashAttentionTPU:
    """Was a numerics test that only a TPU backend ran (and the CPU suite
    skipped forever); the numerics are `bench.py --smoke`'s on the chip, what
    can be held here is that the public kernel compiles for it."""

    def test_flash_attention_compiles(self, chip):
        q = _s((3, 4, 512, 64), jnp.bfloat16, chip)
        assert _kernel_calls(functools.partial(A.flash_attention, causal=True), q, q, q) == 1


class TestFlashAtLlama1bShapes:
    def test_fwd_bwd(self, chip):
        q, kv = _s((B, H, T, DH), jnp.bfloat16, chip), _s((B, HKV, T, DH), jnp.bfloat16, chip)
        assert _kernel_calls(_flash_grad(), q, kv, kv) == 2  # fwd, the one bwd

    def test_fwd_bwd_at_twice_the_length(self, chip):
        # 2 x 4096 rows of q a kv head, 4096 keys resident with their float32 dk and dv
        q, kv = _s((2, H, 4096, DH), jnp.bfloat16, chip), _s((2, HKV, 4096, DH), jnp.bfloat16, chip)
        assert _kernel_calls(_flash_grad(), q, kv, kv) == 2

    def test_packed_segments(self, chip):
        q, kv = _s((B, H, T, DH), jnp.bfloat16, chip), _s((B, HKV, T, DH), jnp.bfloat16, chip)
        assert _kernel_calls(_flash_grad(), q, kv, kv, _s((B, T), jnp.int32, chip)) == 2

    def test_sliding_window(self, chip):
        q, kv = _s((B, H, T, DH), jnp.bfloat16, chip), _s((B, HKV, T, DH), jnp.bfloat16, chip)
        assert _kernel_calls(_flash_grad(window=512), q, kv, kv) == 2


class TestFlashAtTheTrainingCellsShape:
    """`mistral-7b.train_8k` / `train_fsdp4`, a chip's share: 2 x 32 query and
    8 kv heads of 128 over 8192 positions, band 4096, bfloat16 straight into
    the MXU. The backward is ONE call that holds a kv head's k, v and float32
    dk, dv whole in VMEM (25 MB double-buffered), so it lowers only with the
    VMEM it asks for. The trace finds the calls by these instruction names."""

    Q, KV = (2, 32, 8192, DH), (2, 8, 8192, DH)

    def _check(self, text):
        assert text.count("tpu_custom_call") == 2
        for name, calls in (("flash_fwd", 1), ("flash_bwd", 1), ("flash_bwd_dq", 0), ("flash_bwd_dkv", 0)):
            # jax wraps the name in its transforms' (%jvp_flash_fwd_.1, %transpose_jvp_flash_bwd__.1)
            assert len(re.findall(rf"%\w*{name}[_.\d]* = ", text)) == calls, name
        limits = re.findall(r"scoped_memory_configs.{0,80}?size.{0,4}?(\d+)", text)
        assert str(A._BWD_VMEM_LIMIT) in limits, limits

    def test_the_two_named_calls(self, chip):
        q, kv = _s(self.Q, jnp.bfloat16, chip), _s(self.KV, jnp.bfloat16, chip)
        self._check(jax.jit(_flash_grad(window=4096)).lower(q, kv, kv).compile().as_text())

    def test_the_two_named_calls_under_shard_map(self, topo, chip):
        """`train_fsdp4`'s form: four chips' batch (8 x 8192) over fsdp, each
        chip's share the bare call's shape, the kernel per shard under
        `mha_on_mesh`'s shard_map."""
        mesh = Mesh(list(topo.devices), ("fsdp",))
        sharded = NamedSharding(mesh, P("fsdp", None, None, None))
        q = _s((8, *self.Q[1:]), jnp.bfloat16, sharded)
        kv = _s((8, *self.KV[1:]), jnp.bfloat16, sharded)

        def loss(q, k, v):
            return A.mha_on_mesh(q, k, v, mesh=mesh, causal=True, impl="flash",
                                 window=4096).astype(jnp.float32).sum()

        self._check(jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile().as_text())


class TestDecodeAttentionAtServeShapes:
    def _common(self, chip):
        return (_s((SLOTS, H, DH), jnp.bfloat16, chip), _s((SLOTS,), jnp.int32, chip),
                _s((SLOTS, HKV, DH), jnp.bfloat16, chip))

    def test_ragged_decode(self, chip):
        q, lengths, cur = self._common(chip)
        cache = _s((SLOTS, HKV, MAX_LEN, DH), jnp.bfloat16, chip)

        def fn(q, ck, cv, lengths, cur_k, cur_v):
            return DA.ragged_decode_attention(q, ck, cv, lengths, cur_k=cur_k, cur_v=cur_v)

        assert _kernel_calls(fn, q, cache, cache, lengths, cur, cur) == 1

    @pytest.mark.parametrize("page_len", [256, 128, 32])
    def test_paged_decode_with_chunk_staging(self, chip, page_len):
        """The engine's decode step: the whole paged pool, a traced layer
        index and the chunk's staged columns."""
        q, lengths, cur = self._common(chip)
        max_pages = MAX_LEN // page_len
        pool = _s((2, SLOTS * max_pages + 1, HKV, page_len, DH), jnp.bfloat16, chip)
        table = _s((SLOTS, max_pages), jnp.int32, chip)
        staged = _s((SLOTS, 8, HKV, DH), jnp.bfloat16, chip)
        layer = _s((), jnp.int32, chip)

        def fn(q, kp, vp, lengths, table, layer, cur_k, cur_v, sk, sv, count):
            return DA.paged_decode_attention(
                q, kp, vp, lengths, table, layer, cur_k=cur_k, cur_v=cur_v,
                staged_k=sk, staged_v=sv, staged_count=count)

        assert _kernel_calls(fn, q, pool, pool, lengths, table, layer, cur, cur,
                             staged, staged, lengths) == 1

    @pytest.mark.parametrize("pool,slots,heads,max_pages,window", [
        ((8, 384, HKV, 256, DH), 64, 32, 16, 4096), ((1, 4096, HKV, 256, DH), 256, 64, 24, 0),
    ], ids=["mistral-7b-serve-batch", "k-exaone-236b-serve-reason"])
    def test_paged_decode_at_the_cells_shapes(self, chip, pool, slots, heads, max_pages, window):
        """The call as the serving cells make it: ONE Mosaic kernel whose operand is the whole
        pool (the slab buffers and semaphores a fetch is handed through are the call's scratch,
        not operands), nothing else of the pool's or a layer's shape, and next to no temporaries."""
        q, cur = _s((slots, heads, DH), jnp.bfloat16, chip), _s((slots, HKV, DH), jnp.bfloat16, chip)
        lengths, layer = _s((slots,), jnp.int32, chip), _s((), jnp.int32, chip)
        staged = _s((slots, 8, HKV, DH), jnp.bfloat16, chip)

        def fn(q, kp, vp, lengths, table, layer, cur_k, cur_v, sk, sv, count):
            return DA.paged_decode_attention(
                q, kp, vp, lengths, table, layer, cur_k=cur_k, cur_v=cur_v, window=window,
                staged_k=sk, staged_v=sv, staged_count=count)

        kp = _s(pool, jnp.bfloat16, chip)
        compiled = jax.jit(fn).lower(q, kp, kp, lengths, _s((slots, max_pages), jnp.int32, chip), layer, cur, cur,
                                     staged, staged, lengths).compile()
        text = compiled.as_text()
        whole, one_layer = (f"bf16[{','.join(map(str, shape))}]" for shape in (pool, pool[1:]))
        calls = [line for line in text.splitlines() if "tpu_custom_call" in line and " custom-call(" in line]
        assert len(calls) == 1 and calls[0].count(whole) == 2, calls          # K's pool and V's, whole
        made = re.findall(r"^\s*(?:ROOT )?%?[\w.-]+ = (\(.*?\)|\S+) ([\w-]+)\(", text, re.M)
        assert not [(op, shape) for shape, op in made if op != "parameter" and (whole in shape or one_layer in shape)]
        assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2 ** 20


class TestSalaKernelsAtServedWidths:
    """MiniCPM-SALA's four kernels at the widths `minicpm-sala.serve_longdoc`
    serves: 32 query and 2 kv heads of 128, 16 slots of 50,688 positions, a
    page a 64-token block, prefill chunks of 2048."""

    S, H, HKV, D, PAGE, MAXLEN, CHUNK = 16, 32, 2, 128, 64, 50688, 2048

    def test_sparse_paged_decode(self, chip):
        c = self
        n, pages = 128, c.S * (c.MAXLEN // c.PAGE) + 1
        pool = _s((3, pages, c.HKV, c.PAGE, c.D), jnp.bfloat16, chip)
        lists = _s((c.S, c.HKV, n), jnp.int32, chip)
        slot = _s((c.S,), jnp.int32, chip)
        cur, staged = _s((c.S, c.HKV, c.D), jnp.bfloat16, chip), _s((c.S, 8, c.HKV, c.D), jnp.bfloat16, chip)

        def fn(q, kp, vp, layer, pages, logical, full, counts, lengths, win_lo, ck, cv, sk, sv, count):
            return DA.sparse_paged_decode_attention(q, kp, vp, layer, pages, logical, full, counts, lengths, win_lo,
                                                    cur_k=ck, cur_v=cv, staged_k=sk, staged_v=sv, staged_count=count)

        assert _kernel_calls(fn, _s((c.S, c.H, c.D), jnp.bfloat16, chip), pool, pool, _s((), jnp.int32, chip), lists,
                             lists, lists, _s((c.S, c.HKV), jnp.int32, chip), slot, slot, cur, cur, staged, staged,
                             slot) == 1

    def test_masked_prefill(self, chip):
        from tony_tpu.ops import sparse_attention as SA

        c = self
        q = _s((c.HKV, c.H // c.HKV, c.CHUNK, c.D), jnp.bfloat16, chip)
        kv = _s((c.HKV, c.MAXLEN, c.D), jnp.bfloat16, chip)
        mask = _s((c.HKV, c.CHUNK, c.MAXLEN), jnp.int8, chip)
        assert _kernel_calls(SA.masked_prefill_attention, q, kv, kv, mask, _s((), jnp.int32, chip)) == 1

    def test_block_select(self, chip):
        """A prefill chunk's block scores and their 64th largest: 2048 rows of
        32 heads against 3168 float32 compressed keys, 792 blocks, one call
        (products at full precision, a lane roll, the k-th search in VMEM)."""
        from tony_tpu.models.minicpm_sala import SalaConfig
        from tony_tpu.ops import sparse_attention as SA

        c, sp = self, SalaConfig().sparse
        q = _s((c.CHUNK, c.HKV, c.H // c.HKV, c.D), jnp.bfloat16, chip)
        kc = _s((c.MAXLEN // sp.stride, c.HKV, c.D), jnp.float32, chip)
        fn, n_ctx = functools.partial(SA.block_select, spec=sp), _s((c.CHUNK,), jnp.int32, chip)
        text = jax.jit(fn).lower(q, kc, n_ctx).compile().as_text()
        assert text.count("tpu_custom_call") == 1 and "block_select" in text      # the name the trace finds it by
        score, kth = jax.eval_shape(fn, q, kc, n_ctx)
        assert score.shape == (2048, 2, 792) and kth.shape == (2048, 2, 1)
        assert score.dtype == kth.dtype == jnp.float32

    def test_linear_chunk(self, chip):
        """A prefill chunk's linear attention: one call for the 32 heads, the
        one request's float32 state in and out."""
        from tony_tpu.ops import linear_attention as LA

        c = self
        qkv = _s((1, c.H, c.CHUNK, c.D), jnp.bfloat16, chip)
        state, slopes = _s((1, c.H, c.D, c.D), jnp.float32, chip), _s((c.H,), jnp.float32, chip)
        assert _kernel_calls(LA.linear_attention_chunk, qkv, qkv, qkv, state, slopes, _s((), jnp.int32, chip)) == 1


class TestPagedDecodeChunkTouchesThePoolOnlyByPage:
    """The whole `serving.decode_steps` program of a paged engine at Mistral
    widths (2 layers, 96 pages of 256, 64 slots, chunk 8: the serving cells'
    shapes at a quarter of their pool). Inside the chunk the pool is an
    operand that stays where it is: the kernel reads pages of it through a
    layer index and the chunk's one write lands in place, so nothing in the
    compiled program has the pool's shape, or one layer's pool's shape, but
    the pool itself. A slice of a layer's pool handed to the kernel was 38-41%
    of the serving chip and the scatter's transposes another 8-10% (PERF.md,
    PR 27); this holds the compiler to their absence, at no chip time."""

    LAYERS, PAGES, PAGE_LEN, CHUNK, MAX_PAGES = 2, 96, 256, 8, 16

    @pytest.fixture(scope="class")
    def compiled(self, topo):
        with _compiling_for(topo) as chip:
            return self._compile(chip)

    def _compile(self, chip):
        from tony_tpu.models import serving
        from tony_tpu.models.paged_cache import PagedCache

        cfg = llama.LlamaConfig(
            vocab_size=32_000, d_model=4096, n_layers=self.LAYERS, n_heads=32, n_kv_heads=8,
            d_ff=14_336, max_seq=4096, rope_theta=10_000.0, sliding_window=4096)
        on_chip = functools.partial(jax.tree.map, lambda a: _s(a.shape, a.dtype, chip))
        params = on_chip(jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg)))
        pool = _s((self.LAYERS, self.PAGES, cfg.n_kv_heads, self.PAGE_LEN, cfg.head_dim),
                  jnp.bfloat16, chip)
        cache = PagedCache(pool, pool, _s((SLOTS,), jnp.int32, chip),
                           _s((SLOTS, self.MAX_PAGES), jnp.int32, chip))
        samp = (_s((SLOTS,), jnp.float32, chip), _s((SLOTS,), jnp.int32, chip),
                _s((SLOTS,), jnp.float32, chip))
        key = on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
        return serving.decode_steps.lower(
            params, cache, _s((SLOTS,), jnp.int32, chip), key, cfg, self.CHUNK,
            0.0, 0, "ragged", samp).compile()

    def test_no_pool_shaped_value_but_the_pool(self, compiled):
        import re

        pool = f"bf16[{self.LAYERS},{self.PAGES},{HKV},{self.PAGE_LEN},{DH}]"
        one_layer = f"bf16[{self.PAGES},{HKV},{self.PAGE_LEN},{DH}]"
        # an instruction line: `[ROOT ]%name = <result shape> opcode(operands...)`
        made = re.compile(r"^\s*(?:ROOT )?%?[\w.-]+ = (\(.*?\)|\S+) ([\w-]+)\(", re.M)
        text = compiled.as_text()
        # the pool passes through as it is: parameters, tuples, the loops that
        # carry it, and the updates that alias it. A `fusion` of the pool's shape
        # is the two pools' updates fused into one call; its body's lines are
        # read here like any others, so a fused copy or select would still show
        carries = {"parameter", "get-tuple-element", "tuple", "while", "dynamic-update-slice", "fusion"}
        offenders = [
            (name, shape) for shape, name in made.findall(text)
            # by opcode: the text also carries source names, a test's among them
            if (name not in carries and pool in shape) or one_layer in shape or "scatter" in name
        ]
        assert not offenders, offenders[:10]
        assert text.count("tpu_custom_call") >= 1  # the Mosaic kernel is in there

    def test_temporaries_stay_under_one_layers_pool(self, compiled):
        one_layer = self.PAGES * HKV * self.PAGE_LEN * DH * 2  # 50 MB
        assert compiled.memory_analysis().temp_size_in_bytes < one_layer


class TestOtherKernels:
    def test_int8_matmul_d_model_by_d_ff(self, chip):
        """d_ff 5504 = 43 x 128: the default 256-wide block does not tile it
        (int8_matmul then takes the XLA reference, by design), so the kernel
        is asked for with a block that does."""
        cfg = llama.LLAMA_1B
        x = _s((512, cfg.d_model), jnp.bfloat16, chip)
        qt = Q.QTensor(_s((cfg.d_model, cfg.d_ff), jnp.int8, chip), _s((cfg.d_ff,), jnp.float32, chip))
        fn = functools.partial(Q.int8_matmul, block_m=256, block_n=128, block_k=512)
        assert _kernel_calls(fn, x, qt) == 1

    def test_moe_grouped_gemm_fwd_and_bwd(self, chip):
        """Both kernels ask the compiler for 100 MB of fast memory."""
        E, D, F, rows, tile = 8, 1024, 2048, 8192, 128
        xs = _s((rows, D), jnp.bfloat16, chip)
        wg, wd = _s((E, D, F), jnp.bfloat16, chip), _s((E, F, D), jnp.bfloat16, chip)
        tg = _s((rows // tile,), jnp.int32, chip)

        def fwd(xs, wg, wu, wd, tg):
            return MG.moe_swiglu_grouped(xs, wg, wu, wd, tg, tile)

        def loss(xs, wg, wu, wd, tg):
            return fwd(xs, wg, wu, wd, tg).astype(jnp.float32).sum()

        assert _kernel_calls(fwd, xs, wg, wg, wd, tg) == 1
        assert _kernel_calls(jax.grad(loss, argnums=(0, 1, 2, 3)), xs, wg, wg, wd, tg) >= 1


class TestExaoneMoeKernelsAtServedWidths:
    """K-EXAONE's served widths (hidden 6144, experts of 2048, 16 of 128 held, 64 q / 8 kv heads of 128,
    window 128, 256 slots): the grouped product with its weight blocks cut along the expert width
    (a whole slab is 75 MB, 151 double-buffered, over the chip's fast memory), the decode kernel for a
    window layer's rings, and the two flash forms a prefill chunk runs."""

    D, F, HELD, LAYERS, HQ, WINDOW, SLOTS = 6144, 2048, 16, 4, 64, 128, 256

    @pytest.mark.parametrize("tile,rows", [(128, 4096), (256, 20480)], ids=["a-decode-step", "a-2048-row-chunk"])
    def test_grouped_swiglu_over_stacked_banks(self, chip, tile, rows):
        xs = _s((rows, self.D), jnp.bfloat16, chip)
        up = _s((self.LAYERS, self.HELD, self.D, self.F), jnp.bfloat16, chip)
        down = _s((self.LAYERS, self.HELD, self.F, self.D), jnp.bfloat16, chip)
        tg, scalar = _s((rows // tile,), jnp.int32, chip), _s((), jnp.int32, chip)

        def fn(xs, wg, wu, wd, tg, live, layer):
            return MG.moe_swiglu_rows(xs, wg, wu, wd, tg, tile, live, layer, name="moe_swiglu_decode")

        compiled = jax.jit(fn).lower(xs, up, up, down, tg, scalar, scalar).compile()
        assert compiled.as_text().count("tpu_custom_call") == 1 and "moe_swiglu_decode" in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20       # no copy of a layer's bank (403 MB)
        assert MG.width_block(self.D, self.F, 2) == 512

    @pytest.mark.parametrize("cell,tokens,d,f,held,layers,top_k", [
        ("k-exaone-236b", 256, 6144, 2048, 16, 4, 8), ("granite-4.0-h-small", 64, 4096, 768, 36, 10, 10),
        ("dots3-note-prev", 24, 5120, 1536, 32, 4, 8), ("k-exaone-236b-a-512-row-bucket", 512, 6144, 2048, 16, 4, 8)])
    def test_grouped_swiglu_that_gathers_and_sums(self, chip, cell, tokens, d, f, held, layers, top_k):
        """A decode step's form (`moe_swiglu_tokens`): the call takes the step's tokens whole, a tile's tokens and
        gates beside the weight blocks, and returns `y [T, D]`: one kernel under the decode step's name with the
        banks as operands, no buffer at the static row bound (43 MB at granite, 50 at K-EXAONE) and no copy of a bank
        beside it. K-EXAONE's 512 x 6144 (a prefill bucket) is the most it holds in VMEM: 37.7 MB of tokens, sum and
        result beside 38 MB of weight blocks, of the 100 MB the call asks for; 24 tokens are padded to 32."""
        from tony_tpu.parallel.expert import held_form

        tile = 128
        bound = (-(-tokens * top_k // tile) + held) * tile
        x = _s((tokens, d), jnp.bfloat16, chip)
        up, down = _s((layers, held, d, f), jnp.bfloat16, chip), _s((layers, held, f, d), jnp.bfloat16, chip)
        tok, gate = _s((bound,), jnp.int32, chip), _s((bound,), jnp.float32, chip)
        tg, scalar = _s((bound // tile,), jnp.int32, chip), _s((), jnp.int32, chip)

        def fn(x, tok, gate, wg, wu, wd, tg, live, layer):
            return MG.moe_swiglu_tokens(x, tok, gate, wg, wu, wd, tg, tile, live, layer, name="moe_swiglu_decode")

        assert held_form(tokens, d, 2) == "in_kernel"
        compiled = jax.jit(fn).lower(x, tok, gate, up, up, down, tg, scalar, scalar).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == 1 and "moe_swiglu_decode" in text
        assert f"bf16[{layers},{held},{d},{f}]" in text and f"bf16[{bound},{d}]" not in text
        assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2 ** 20

    @pytest.mark.parametrize("cell,tokens,d,f,held,experts,layers,top_k,tile", [
        ("solar-open2-250b", 2048, 4096, 1280, 40, 320, 4, 8, 128), ("solar-open2-250b-a-1024-row-bucket", 1024, 4096, 1280, 40, 320, 4, 8, 128),
        ("granite-4.0-h-small", 2048, 4096, 768, 36, 72, 10, 10, 256), ("k-exaone-236b", 2048, 6144, 2048, 16, 128, 4, 8, 256),
        ("dots3-note-prev", 2048, 5120, 1536, 32, 256, 4, 8, 128), ("mistral-small-4-119b", 2048, 4096, 2048, 32, 128, 4, 4, 128)])
    def test_grouped_swiglu_that_fetches_its_rows(self, chip, cell, tokens, d, f, held, experts, layers, top_k, tile):
        """A long prefill chunk's form (`moe_swiglu_fetched` and `moe_choices_sum`), at every routed cell's widths:
        the tokens and the rows' outputs stay in HBM as float32 slabs `[D / 128, 128]` a row (whole tiles of 8
        sublanes: a DMA of one row of `[T, D]` is refused), four and five vectors of the static bound are scalars in
        SMEM, the grouped product keeps the prefill chunk's name and the banks as operands, and no bfloat16 buffer
        at the static row bound exists (176 MB a layer at solar-open2, 243 at granite)."""
        from tony_tpu.parallel.expert import MoEConfig, held_form, held_tile

        assert held_form(tokens, d, 2, top_k) == "fetched"
        assert held_tile(MoEConfig(num_experts=experts, top_k=top_k, held=(0, held)), tokens * top_k, MG.TILE_M) == tile
        bound = (-(-tokens * top_k // tile) + held) * tile
        x = _s((tokens, d), jnp.bfloat16, chip)
        up, down = _s((layers, held, d, f), jnp.bfloat16, chip), _s((layers, held, f, d), jnp.bfloat16, chip)
        tok, dest, gates = _s((bound,), jnp.int32, chip), _s((tokens * top_k,), jnp.int32, chip), _s((tokens * top_k,), jnp.float32, chip)
        tg, scalar = _s((bound // tile,), jnp.int32, chip), _s((), jnp.int32, chip)

        def fn(x, tok, real, dest, gates, wg, wu, wd, tg, live, layer):
            ys = MG.moe_swiglu_fetched(x, tok, real, wg, wu, wd, tg, tile, live, layer, name="moe_swiglu_prefill")
            return MG.moe_choices_sum(ys, dest, gates, top_k, d, x.dtype)

        compiled = jax.jit(fn).lower(x, tok, tg, dest, gates, up, up, down, tg, scalar, scalar).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == 2 and len(re.findall(r"%moe_swiglu_prefill[.\d]* = ", text)) == 1
        assert len(re.findall(r"%moe_choices_sum[.\d]* = ", text)) == 1
        assert f"bf16[{layers},{held},{d},{f}]" in text and f"bf16[{bound},{d}]" not in text
        assert f"f32[{bound * d // 128},128]" in text and f"f32[{tokens * d // 128},128]" in text
        # the slabs of the rows' outputs (float32 at the bound, of which the live tiles are written) and of the tokens
        assert compiled.memory_analysis().temp_size_in_bytes < (bound + tokens) * d * 4 + 8 * 2 ** 20

    def test_decode_attention_on_a_window_layers_ring(self, chip):
        """Every window layer's rings are one operand with a layer index; a block of slots goes
        through the call's own pipeline: one kernel, found by its name, and no copy of a layer's
        rings (75 MB each of K and V) beside it."""
        from tony_tpu.models.paged_cache import RING_SLACK

        q, cur = _s((self.SLOTS, self.HQ, DH), jnp.bfloat16, chip), _s((self.SLOTS, HKV, DH), jnp.bfloat16, chip)
        ring = _s((self.LAYERS, self.SLOTS, HKV, self.WINDOW + RING_SLACK, DH), jnp.bfloat16, chip)
        lengths, layer = _s((self.SLOTS,), jnp.int32, chip), _s((), jnp.int32, chip)
        staged = _s((self.SLOTS, 8, HKV, DH), jnp.bfloat16, chip)

        def fn(q, rk, rv, lengths, layer, cur_k, cur_v, sk, sv, count):
            return DA.ring_decode_attention(q, rk, rv, lengths, layer, cur_k=cur_k, cur_v=cur_v, window=self.WINDOW,
                                            staged_k=sk, staged_v=sv, staged_count=count)

        compiled = jax.jit(fn).lower(q, ring, ring, lengths, layer, cur, cur, staged, staged, lengths).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == 1 and "ring_decode_attention" in text
        assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2 ** 20

    @pytest.mark.parametrize("chunk", [128, 2048])
    def test_a_prefill_chunks_attention(self, chip, chunk):
        """A window layer: flash with the band over [the last 128 positions ; the chunk], positions below 0
        a segment of their own. The full layer: the masked flash kernel over the staged keys."""
        from tony_tpu.ops.sparse_attention import masked_prefill_attention

        ext, max_len = self.WINDOW + chunk, 6144
        q, kv = _s((1, self.HQ, ext, DH), jnp.bfloat16, chip), _s((1, HKV, ext, DH), jnp.bfloat16, chip)
        seg = _s((1, ext), jnp.int32, chip)

        def band(q, k, v, seg):
            return A.flash_attention(q, k, v, causal=True, window=self.WINDOW, segment_ids=seg)

        assert _kernel_calls(band, q, kv, kv, seg) == 1
        qh, keys = _s((HKV, self.HQ // HKV, chunk, DH), jnp.bfloat16, chip), _s((HKV, max_len, DH), jnp.bfloat16, chip)
        mask, n = _s((HKV, chunk, max_len), jnp.int8, chip), _s((), jnp.int32, chip)
        assert _kernel_calls(masked_prefill_attention, qh, keys, keys, mask, n) == 1


class TestFlashOnAFourChipMesh:
    def test_flash_under_shard_map_lowers_where_the_bare_call_cannot(self, topo, chip):
        """GSPMD cannot partition a Mosaic kernel: under any data/fsdp/model
        mesh of real devices the bare call is refused, which no CPU run sees
        (there the kernel is plain XLA). `mha_on_mesh` — what the models call
        — runs it per shard under shard_map, batch over fsdp."""
        mesh = Mesh(list(topo.devices), ("fsdp",))
        sharded = NamedSharding(mesh, P("fsdp", None, None, None))
        q = _s((B, H, T, DH), jnp.bfloat16, sharded)
        kv = _s((B, HKV, T, DH), jnp.bfloat16, sharded)

        def bare(q, k, v):
            return A.mha(q, k, v, causal=True, impl="flash")

        def on_mesh(q, k, v):
            return A.mha_on_mesh(q, k, v, mesh=mesh, causal=True, impl="flash")

        def loss(q, k, v):
            return on_mesh(q, k, v).astype(jnp.float32).sum()

        with pytest.raises(NotImplementedError, match="shard_map"):
            jax.jit(bare).lower(q, kv, kv)
        assert _kernel_calls(on_mesh, q, kv, kv) == 1
        assert _kernel_calls(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv) == 2


class TestLatentAttentionAtTheNotesCellsShapes:
    """`dots3-note-prev.serve_notes` (one chip's share): the five Pallas calls of
    the latent-attention path at the published widths, found in a trace by these
    names: a 2048-row prefill chunk over 67,584 staged positions, a decode step
    of 32 slots over 2048 chosen rows and over the rings."""

    S, T, MAX, PAGE, TOPK = 32, 2048, 67584, 1024, 2048

    def _named(self, fn, name, *args):
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert text.count("tpu_custom_call") == 1 and len(re.findall(rf"%\w*{name}[\w.]* = ", text)) == 1, name

    @pytest.mark.parametrize("heads,r,dn,rows,row", [(128, 512, 128, 67584, 640), (64, 1024, 192, 2560, 1152)],
                             ids=["full-layer-under-the-indexers-mask", "window-layer-under-the-band"])
    def test_the_expanded_prefill(self, chip, heads, r, dn, rows, row):
        from tony_tpu.ops import latent_attention as LA

        bf, i32 = jnp.bfloat16, jnp.int32
        args = (_s((heads, self.T, dn), bf, chip), _s((heads, self.T, 64), bf, chip), _s((rows, row), bf, chip),
                _s((heads, r, dn), bf, chip), _s((heads, r, 128), bf, chip), _s((rows // 512, self.T, 512), jnp.int8, chip),
                _s((2,), i32, chip), _s((2,), i32, chip))
        self._named(functools.partial(LA.latent_prefill_attention, scale=0.07), "latent_prefill", *args)

    @pytest.mark.parametrize("name,heads,r,rows,row", [("latent_decode", 128, 512, (1, 32, 2048, 640), 640),
                                                         ("latent_ring_decode", 64, 1024, (3, 32, 640, 1152), 1152)])
    def test_the_absorbed_decode(self, chip, name, heads, r, rows, row):
        from tony_tpu.ops import latent_attention as LA

        bf = jnp.bfloat16
        args = (_s((self.S, heads, row), bf, chip), _s(rows, bf, chip), _s((), jnp.int32, chip), _s((self.S, rows[2]), jnp.bool_, chip),
                _s((self.S, 128, row), bf, chip), _s((self.S, 128), jnp.bool_, chip))
        self._named(functools.partial(LA.latent_rows_attention, r=r, scale=0.07, name=name), name, *args)

    def test_the_indexers_scores_and_choice(self, chip):
        from tony_tpu.ops import sparse_attention as SA

        bf, i32 = jnp.bfloat16, jnp.int32
        self._named(SA.index_scores_prefill, "index_scores_prefill", _s((self.T, 64, 128), bf, chip), _s((self.T, 64), jnp.float32, chip),
                    _s((self.MAX, 128), bf, chip), _s((), i32, chip))
        self._named(functools.partial(SA.index_select, topk=self.TOPK), "index_select",
                    _s((self.MAX // 512, self.T, 512), i32, chip), _s((), i32, chip))
        self._named(SA.index_scores_decode, "index_scores_decode", _s((self.S, 64, 128), bf, chip), _s((self.S, 64), jnp.float32, chip),
                    _s((2, 1201, self.PAGE, 128), bf, chip), _s((), i32, chip), _s((self.S, self.MAX // self.PAGE), i32, chip),
                    _s((self.S,), i32, chip))


class TestLatentAttentionAtTheDocqaCellsShapes:
    """`mistral-small-4-119b.serve_docqa` (one chip's share): the two Pallas
    calls of the dense latent path at the published widths (32 heads, a latent of
    256 + 64 laid out in 384), found in a trace by these names: a prefill chunk
    (a whole one of 2048 rows, and a question's 64) over 35,840 staged positions,
    and a decode step of 64 slots that walks each slot's pages of the pool."""

    S, MAX, PAGE, PAGES, ROW = 64, 35840, 1024, 577, 384
    _named = TestLatentAttentionAtTheNotesCellsShapes._named

    @pytest.mark.parametrize("rows", [2048, 64], ids=["a-whole-chunk", "a-questions-bucket"])
    def test_the_expanded_prefill(self, chip, rows):
        from tony_tpu.ops import latent_attention as LA

        bf, i32 = jnp.bfloat16, jnp.int32
        tiles = rows // LA.divisor(rows, 1024)
        args = (_s((32, rows, 64), bf, chip), _s((32, rows, 64), bf, chip), _s((self.MAX, self.ROW), bf, chip),
                _s((32, 256, 64), bf, chip), _s((32, 256, 128), bf, chip), _s((self.MAX // 512, rows, 512), jnp.int8, chip),
                _s((tiles,), i32, chip), _s((tiles,), i32, chip))
        self._named(functools.partial(LA.latent_prefill_attention, scale=0.195, block_q=LA.divisor(rows, 1024)), "latent_prefill", *args)

    def test_the_paged_absorbed_decode(self, chip):
        from tony_tpu.ops import latent_attention as LA

        bf, i32 = jnp.bfloat16, jnp.int32
        args = (_s((self.S, 32, self.ROW), bf, chip), _s((5, self.PAGES, self.PAGE, self.ROW), bf, chip), _s((), i32, chip),
                _s((self.S,), i32, chip), _s((self.S, self.MAX // self.PAGE), i32, chip), _s((self.S, 128, self.ROW), bf, chip),
                _s((), i32, chip))
        self._named(functools.partial(LA.latent_paged_decode, r=256, scale=0.195), "latent_paged_decode", *args)


class TestStateSpaceAtTheAssistCellsShapes:
    """`granite-4.0-h-small.serve_assist` (one chip's share of a pair): the Pallas
    calls of a `mamba` layer at the published widths (128 heads of 64 over a state
    of 128, 8448 convolution channels), found in a trace by these names: a
    prefill chunk's blocked recurrence (a whole chunk of 2048 rows and the
    smallest bucket's 256) from a request's float32 state `[128, 8192]`, a decode
    step of 64 slots that reads and writes each slot's state IN PLACE, and the
    convolution with its bias."""

    S, H, P, N = 64, 128, 64, 128
    _named = TestLatentAttentionAtTheNotesCellsShapes._named

    @pytest.mark.parametrize("rows", [2048, 256], ids=["a-whole-chunk", "the-smallest-bucket"])
    def test_the_blocked_recurrence(self, chip, rows):
        from tony_tpu.ops import ssd

        bf, f32 = jnp.bfloat16, jnp.float32
        args = (_s((rows, self.H, self.P), bf, chip), _s((rows, self.H), f32, chip), _s((rows, self.H), f32, chip),
                _s((rows, self.N), bf, chip), _s((rows, self.N), bf, chip), _s((self.H,), f32, chip),
                _s((self.N, self.H * self.P), f32, chip), _s((), jnp.int32, chip))
        self._named(ssd.ssd_chunk, "ssd_chunk", *args)

    def test_the_decode_step_updates_the_state_in_place(self, chip):
        from tony_tpu.ops import ssd

        bf, f32 = jnp.bfloat16, jnp.float32
        state = _s((self.S, self.N, self.H * self.P), f32, chip)
        args = (_s((self.S, self.H, self.P), bf, chip), _s((self.S, self.H), f32, chip), _s((self.S, self.H), f32, chip),
                _s((self.S, self.N), bf, chip), _s((self.S, self.N), bf, chip), _s((self.H,), f32, chip), state)
        compiled = jax.jit(ssd.ssd_step, donate_argnums=(6,)).lower(*args).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == 1 and len(re.findall(r"%\w*ssd_step[\w.]* = ", text)) == 1
        # the state goes out in the buffer it came in: no second 268 MB, no copy of it among the temporaries
        memory = compiled.memory_analysis()
        assert memory.alias_size_in_bytes >= 4 * self.S * self.N * self.H * self.P and memory.temp_size_in_bytes < 16 << 20

    def test_the_convolution_with_a_bias(self, chip):
        from tony_tpu.ops import delta_rule

        bf = jnp.bfloat16
        c = self.H * self.P + 2 * self.N
        self._named(delta_rule.short_conv_chunk, "short_conv", _s((2048, c), bf, chip), _s((3, c), bf, chip), _s((4, c), bf, chip),
                    _s((), jnp.int32, chip), _s((c,), bf, chip))


def _one_call_on_the_state(fn, name, state, *args):
    """`fn` compiles for the chip to ONE Mosaic call under `name` (how a trace's reader finds it) that takes `state`."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [line for line in text.splitlines() if re.search(rf"%\w*{name}[\w.]* = ", line)]
    assert text.count("tpu_custom_call") == 1 and len(calls) == 1 and state in calls[0].split("custom-call(")[1], name


class TestGatedDeltaRuleAtTheSessionsCellsShapes:
    """`olmo-hybrid-7b.serve_sessions`: a linear layer's blocked rule at the published
    widths (30 heads of 96 keys and 192 values), a 1024-row prefill chunk and the
    cell's smallest bucket (256), from a request's float32 state `[30, 96, 192]`: the
    call `delta_prefill_roofline_pct.serve` finds by this name and operand. Three
    heads a program (the most up to `delta_rule.CHUNK_HEADS` that divide 30), about 3.8 MB
    of blocks, states and live values: inside the default scoped VMEM."""

    H, DK, DV = 30, 96, 192

    @pytest.mark.parametrize("rows", [1024, 256], ids=["a-1024-row-chunk", "the-smallest-bucket"])
    def test_the_blocked_rule(self, chip, rows):
        from tony_tpu.ops import delta_rule

        bf, f32 = jnp.bfloat16, jnp.float32
        args = (_s((self.H, rows, self.DK), bf, chip), _s((self.H, rows, self.DK), bf, chip), _s((self.H, rows, self.DV), bf, chip),
                _s((self.H, rows), f32, chip), _s((self.H, rows), f32, chip), _s((self.H, self.DK, self.DV), f32, chip), _s((), jnp.int32, chip))
        _one_call_on_the_state(delta_rule.gated_delta_chunk, "delta_chunk", f"f32[{self.H},{self.DK},{self.DV}]", *args)


class TestChannelGatedDeltaRuleAtTheExtractCellsShapes:
    """`solar-open2-250b.serve_extract` (one chip's share of eight): the Pallas calls of
    a `kda` layer at the published widths (64 heads of 128 keys and 128 values, 24,576
    convolution channels), found in a trace by these names: a prefill chunk's blocked
    rule (a whole chunk of 2048 rows and the smallest bucket's 256) from a request's
    float32 state `[64, 128, 128]`, a decode step of 128 slots that reads and writes
    each slot's state IN PLACE with its key-side vectors as lane-dense columns, the
    convolution over q, k and v; and the routed FFN at sizes the fused call's blocks
    had never had: experts of 1280 (blocks of 640 along the width), 40 of 320 held."""

    S, H, DK, D, F, HELD, LAYERS, TOP_K = 128, 64, 128, 4096, 1280, 40, 4, 8
    _named = TestLatentAttentionAtTheNotesCellsShapes._named

    @pytest.mark.parametrize("rows", [2048, 256], ids=["a-whole-chunk", "the-smallest-bucket"])
    def test_the_blocked_rule(self, chip, rows):
        from tony_tpu.ops import kda

        bf, f32 = jnp.bfloat16, jnp.float32
        head_rows = lambda dtype: _s((self.H, rows, self.DK), dtype, chip)
        args = (head_rows(bf), head_rows(bf), head_rows(bf), head_rows(f32), _s((self.H, rows), f32, chip),
                _s((self.H, self.DK, self.DK), f32, chip), _s((), jnp.int32, chip))
        # four heads a program (`delta_rule.CHUNK_HEADS`): 4.2 MB of blocks, states and live values, inside the default scoped VMEM
        _one_call_on_the_state(kda.kda_chunk, "kda_chunk", f"f32[{self.H},{self.DK},{self.DK}]", *args)

    def test_the_decode_step_updates_the_state_in_place(self, chip):
        from tony_tpu.ops import kda

        bf, f32 = jnp.bfloat16, jnp.float32
        slot_heads = lambda dtype: _s((self.S, self.H, self.DK), dtype, chip)
        state = _s((self.S, self.H, self.DK, self.DK), f32, chip)
        args = (slot_heads(bf), slot_heads(bf), slot_heads(bf), slot_heads(f32), _s((self.S, self.H), f32, chip), state)
        compiled = jax.jit(kda.kda_step, donate_argnums=(5,)).lower(*args).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == 1 and len(re.findall(r"%\w*kda_step[\w.]* = ", text)) == 1
        # the key-side vectors reach the call as [slots, H / 32, dk, 128]: four vectors of 32 heads fill the lanes, nothing padded
        assert f"f32[{self.S},{self.H // kda.HEADS},{self.DK},{4 * kda.HEADS}]" in text
        # the state goes out in the buffer it came in: no second 537 MB, no copy of it among the temporaries
        memory = compiled.memory_analysis()
        assert memory.alias_size_in_bytes >= 4 * self.S * self.H * self.DK * self.DK and memory.temp_size_in_bytes < 64 << 20

    def test_the_convolution_over_q_k_and_v(self, chip):
        from tony_tpu.ops import delta_rule

        bf, c = jnp.bfloat16, 3 * self.H * self.DK
        self._named(delta_rule.short_conv_chunk, "short_conv", _s((2048, c), bf, chip), _s((3, c), bf, chip), _s((4, c), bf, chip),
                    _s((), jnp.int32, chip))

    @pytest.mark.parametrize("tokens,name", [(128, "moe_swiglu_decode"), (512, "moe_swiglu_prefill")], ids=["a-decode-step", "a-512-row-bucket"])
    def test_the_routed_ffn_that_gathers_and_sums_at_experts_of_1280(self, chip, tokens, name):
        from tony_tpu.parallel.expert import MoEConfig, held_ffn_form, held_tile

        cfg = MoEConfig(num_experts=320, top_k=self.TOP_K, scoring="sigmoid", held=(0, self.HELD))
        tile = held_tile(cfg, tokens * self.TOP_K, MG.TILE_M)
        bound = (-(-tokens * self.TOP_K // tile) + self.HELD) * tile
        x = _s((tokens, self.D), jnp.bfloat16, chip)
        up = _s((self.LAYERS, self.HELD, self.D, self.F), jnp.bfloat16, chip)
        down = _s((self.LAYERS, self.HELD, self.F, self.D), jnp.bfloat16, chip)
        tok, gate = _s((bound,), jnp.int32, chip), _s((bound,), jnp.float32, chip)
        tg, scalar = _s((bound // tile,), jnp.int32, chip), _s((), jnp.int32, chip)

        def fn(x, tok, gate, wg, wu, wd, tg, live, layer):
            return MG.moe_swiglu_tokens(x, tok, gate, wg, wu, wd, tg, tile, live, layer, name=name)

        with pytest.MonkeyPatch.context() as patch:
            patch.setenv("TONY_PALLAS_INTERPRET", "1")            # `_kernel_eligible` asks for a TPU backend or the interpreter: the CPU's answer
            assert held_ffn_form(cfg, tokens, self.D, self.F, jnp.bfloat16) == "in_kernel"
        assert MG.width_block(self.D, self.F, 2) == 640          # a slab of 31.5 MB, 63 double-buffered: two blocks of 640
        compiled = jax.jit(fn).lower(x, tok, gate, up, up, down, tg, scalar, scalar).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == 1 and name in text
        assert f"bf16[{self.LAYERS},{self.HELD},{self.D},{self.F}]" in text and f"bf16[{bound},{self.D}]" not in text
        assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2 ** 20

    def test_the_routed_ffn_over_a_2048_row_chunks_staged_rows(self, chip):
        from tony_tpu.parallel.expert import MoEConfig, held_form, held_tile

        rows_in = 2048
        tile = held_tile(MoEConfig(num_experts=320, top_k=self.TOP_K, held=(0, self.HELD)), rows_in * self.TOP_K, MG.TILE_M)
        rows = (-(-rows_in * self.TOP_K // tile) + self.HELD) * tile
        xs = _s((rows, self.D), jnp.bfloat16, chip)
        up = _s((self.LAYERS, self.HELD, self.D, self.F), jnp.bfloat16, chip)
        down = _s((self.LAYERS, self.HELD, self.F, self.D), jnp.bfloat16, chip)
        tg, scalar = _s((rows // tile,), jnp.int32, chip), _s((), jnp.int32, chip)

        def fn(xs, wg, wu, wd, tg, live, layer):
            return MG.moe_swiglu_rows(xs, wg, wu, wd, tg, tile, live, layer, name="moe_swiglu_prefill")

        # what ran at this cell until PR 62 (now `moe_swiglu_fetched`: TestExaoneMoeKernelsAtServedWidths), and what a width
        # no row travels alone at still runs; 51 rows an expert a chunk: under a tile, so not doubled
        assert held_form(rows_in, self.D, 2) == "fetched" and held_form(rows_in, self.D + 512, 2) == "staged" and tile == MG.TILE_M
        compiled = jax.jit(fn).lower(xs, up, up, down, tg, scalar, scalar).compile()
        assert compiled.as_text().count("tpu_custom_call") == 1 and "moe_swiglu_prefill" in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20       # no copy of a layer's bank (1.26 GB)

    @pytest.mark.parametrize("program", ["a-256-row-prefill-chunk", "a-decode-chunk-of-128-slots"])
    def test_the_familys_serving_programs_at_the_cells_sizes(self, chip, program):
        """The jitted programs the engine runs, whole, at the cell's sizes (one period of the published
        widths, 40 of 320 experts held, an eighth of the vocabulary, 128 slots of 6,144 positions): every
        Pallas call by its name, the slots' state aliased in and out."""
        from tony_tpu.models import solar_open2 as SO

        cfg = SO.SolarOpen2Config(vocab_size=24_576, layer_types=(SO.ATTENTION, SO.KDA, SO.KDA, SO.KDA), held=(0, 40), max_seq=6144)
        put = lambda tree: jax.tree.map(lambda a: _s(a.shape, a.dtype, chip), tree)
        params = put(jax.eval_shape(lambda: SO.init(jax.random.PRNGKey(0), cfg)))
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(jax, "default_backend", lambda: "tpu")          # `_kernel_eligible` asks the backend: the chip's answer, not this sandbox's
            if program.startswith("a-256"):
                staging = put(jax.eval_shape(lambda: SO._init_staging(cfg, 6144)))
                compiled = SO.prefill_chunk.lower(params, _s((1, 256), jnp.int32, chip), staging, _s((), jnp.int32, chip), cfg).compile()
                names = {"kda_chunk": 3, "short_conv": 3, "moe_swiglu_prefill": 4, "chunk_prefill_attention": 1}
            else:
                cache = put(jax.eval_shape(lambda: SO._init_cache(cfg, 128, 6144, 256, 2049)))
                compiled = SO.decode_steps.lower(params, cache, _s((128,), jnp.int32, chip), _s((2,), jnp.uint32, chip), cfg, 8).compile()
                names = {"kda_step": 3, "moe_swiglu_decode": 4, "paged_decode_attention": 1}
                assert compiled.memory_analysis().alias_size_in_bytes >= 3 * 4 * 128 * 64 * 128 * 128    # the state and the pool go out where they came in
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == sum(names.values())
        for name, calls in names.items():
            assert len(re.findall(rf"%{name}[.\d]* = ", text)) == calls, name
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


class TestParallelHybridAtTheRewriteCellsShapes:
    """`falcon-h1-34b.serve_rewrite` (one pipeline stage of eight): the Pallas calls of a
    layer that runs BOTH mixers, at the published widths, found in a trace by these
    names and operands: the blocked recurrence of 32 heads of 128 over a state of 256 in
    TWO B/C groups (a 1024-row chunk and the smallest bucket's 256) from a request's
    float32 state `[256, 4096]`, a decode step of 48 slots that reads and writes each
    slot's state IN PLACE with a span's columns its own group's, the convolution with a
    bias over 5,120 channels; the paged decode attention and the chunked prefill
    attention at FIVE query heads a kv head (20 over 4; blocks `[4, 5, 128]`, not a
    whole tile of 8 sublanes) over the pool and the staging of nine layers."""

    S, H, P, N, G, LAYERS, HQ, HKV, DH, PAGE, MAX_LEN = 48, 32, 128, 256, 2, 9, 20, 4, 128, 256, 3072
    _named = TestLatentAttentionAtTheNotesCellsShapes._named

    @pytest.mark.parametrize("rows", [1024, 256], ids=["a-whole-chunk", "the-smallest-bucket"])
    def test_the_blocked_recurrence_in_two_groups(self, chip, rows):
        from tony_tpu.ops import ssd

        bf, f32 = jnp.bfloat16, jnp.float32
        args = (_s((rows, self.H, self.P), bf, chip), _s((rows, self.H), f32, chip), _s((rows, self.H), f32, chip),
                _s((rows, self.G, self.N), bf, chip), _s((rows, self.G, self.N), bf, chip), _s((self.H,), f32, chip),
                _s((self.N, self.H * self.P), f32, chip), _s((), jnp.int32, chip))
        text = jax.jit(ssd.ssd_chunk).lower(*args).compile().as_text()
        calls = [line for line in text.splitlines() if " custom-call(" in line and "tpu_custom_call" in line]
        # ONE Mosaic call, under the name and on the operand `ssd_prefill_roofline_pct.serve` finds it by; both groups' B and C side by side
        assert len(calls) == 1 and re.search(r"%ssd_chunk[\w.]* = ", calls[0]), calls
        assert f"f32[{self.N},{self.H * self.P}]" in calls[0].split("custom-call(")[1] and f"bf16[{rows},{self.G * self.N}]" in calls[0]

    def test_the_decode_step_updates_the_state_in_place(self, chip):
        from tony_tpu.ops import ssd

        bf, f32 = jnp.bfloat16, jnp.float32
        state = _s((self.S, self.N, self.H * self.P), f32, chip)
        args = (_s((self.S, self.H, self.P), bf, chip), _s((self.S, self.H), f32, chip), _s((self.S, self.H), f32, chip),
                _s((self.S, self.G, self.N), bf, chip), _s((self.S, self.G, self.N), bf, chip), _s((self.H,), f32, chip), state)
        compiled = jax.jit(ssd.ssd_step, donate_argnums=(6,)).lower(*args).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == 1 and len(re.findall(r"%\w*ssd_step[\w.]* = ", text)) == 1
        # both groups' columns reach the call side by side, [slots, 2 x 256, 1]; the state goes out in the buffer
        # it came in: no second 201 MB, no copy of it among the temporaries
        assert f"f32[{self.S},{self.G * self.N},1]" in text
        memory = compiled.memory_analysis()
        assert memory.alias_size_in_bytes >= 4 * self.S * self.N * self.H * self.P and memory.temp_size_in_bytes < 16 << 20

    def test_the_convolution_with_a_bias(self, chip):
        from tony_tpu.ops import delta_rule

        bf = jnp.bfloat16
        c = self.H * self.P + 2 * self.G * self.N
        self._named(delta_rule.short_conv_chunk, "short_conv", _s((1024, c), bf, chip), _s((3, c), bf, chip), _s((4, c), bf, chip),
                    _s((), jnp.int32, chip), _s((c,), bf, chip))

    def test_paged_decode_at_five_query_heads_a_kv_head(self, chip):
        """The call as the cell makes it: ONE Mosaic kernel whose operand is the whole pool of nine layers, twice."""
        bf, i32 = jnp.bfloat16, jnp.int32
        pool = (self.LAYERS, self.S * (self.MAX_LEN // self.PAGE) + 1, self.HKV, self.PAGE, self.DH)
        q, cur = _s((self.S, self.HQ, self.DH), bf, chip), _s((self.S, self.HKV, self.DH), bf, chip)
        lengths, layer, staged = _s((self.S,), i32, chip), _s((), i32, chip), _s((self.S, 8, self.HKV, self.DH), bf, chip)

        def fn(q, kp, vp, lengths, table, layer, cur_k, cur_v, sk, sv, count):
            return DA.paged_decode_attention(q, kp, vp, lengths, table, layer, cur_k=cur_k, cur_v=cur_v,
                                             staged_k=sk, staged_v=sv, staged_count=count)

        kp = _s(pool, bf, chip)
        compiled = jax.jit(fn).lower(q, kp, kp, lengths, _s((self.S, self.MAX_LEN // self.PAGE), i32, chip), layer, cur, cur,
                                     staged, staged, lengths).compile()
        whole = f"bf16[{','.join(map(str, pool))}]"
        calls = [line for line in compiled.as_text().splitlines() if "tpu_custom_call" in line and " custom-call(" in line]
        assert len(calls) == 1 and calls[0].count(whole) == 2, calls
        assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2 ** 20

    @pytest.mark.parametrize("rows", [1024, 256], ids=["a-whole-chunk", "the-smallest-bucket"])
    def test_chunked_prefill_attention_at_five_query_heads_a_kv_head(self, chip, rows):
        bf, i32 = jnp.bfloat16, jnp.int32
        staging = _s((self.LAYERS, 1, self.HKV, self.MAX_LEN, self.DH), bf, chip)
        self._named(A.chunk_prefill_attention, "chunk_prefill_attention", _s((self.HQ, rows, self.DH), bf, chip), staging, staging,
                    _s((), i32, chip), _s((), i32, chip), _s((), i32, chip))


class TestSharedCacheAtTheManyshotCellsShapes:
    """`phi-4-mini-flash.serve_manyshot` (the whole model on one chip): the Pallas calls of the
    SambaY layers at the published widths, found in a trace by these names and operands: the
    selective scan of 5,120 channels over a state of 16, a 2,048-row chunk and the smallest
    bucket's 256 from a request's float32 state `[16, 5120]` with NOTHING of `[T, 5120, 16]`
    staged, and a decode step of 16 slots that reads and writes each slot's state in place;
    both maps of differential attention as ONE call of each one-map kernel over kv-head PAIRS
    (10 pairs of 128 under 40 widened query heads): the paged decode over the pool of ONE
    layer, the ring decode over eight window layers' rings of 528 rows, the chunked prefill
    over the staging of 36,864 positions and the window layers' band; and the whole decode
    chunk and prefill chunk of all 32 layers, which have to fit beside 7.7 GB of weights."""

    S, E, N, H, PAIRS, WIDE, PAGE, MAX_LEN, WINDOW = 16, 5120, 16, 40, 10, 128, 256, 36_864, 512
    _named = TestLatentAttentionAtTheNotesCellsShapes._named

    @pytest.mark.parametrize("rows", [2048, 256], ids=["a-whole-chunk", "the-smallest-bucket"])
    def test_the_selective_scan_walks_a_chunk_without_staging_it(self, chip, rows):
        from tony_tpu.ops import selective_scan as SS

        bf, f32 = jnp.bfloat16, jnp.float32
        args = (_s((rows, self.E), bf, chip), _s((rows, self.E), f32, chip), _s((self.N, self.E), f32, chip), _s((rows, self.N), bf, chip),
                _s((rows, self.N), bf, chip), _s((self.E,), f32, chip), _s((self.N, self.E), f32, chip), _s((), jnp.int32, chip))
        compiled = jax.jit(SS.selective_chunk).lower(*args).compile()
        text = compiled.as_text()
        calls = [line for line in text.splitlines() if " custom-call(" in line and "tpu_custom_call" in line]
        # ONE Mosaic call, under the name and on the operand `scan_prefill_roofline_pct.serve` finds it by
        assert len(calls) == 1 and re.search(r"%selective_chunk[\w.]* = ", calls[0]) and f"f32[{self.N},{self.E}]" in calls[0].split("custom-call(")[1], calls
        # an associative scan would stage rows x 5120 x 16 float32 (671 MB at 2,048 rows): nothing here is a tenth of it
        assert not re.search(rf"\[{rows},({self.E},{self.N}|{self.N},{self.E})\]", text)
        assert compiled.memory_analysis().temp_size_in_bytes < 4 * rows * self.E * self.N // 10

    def test_the_decode_step_updates_the_state_in_place(self, chip):
        from tony_tpu.ops import selective_scan as SS

        bf, f32 = jnp.bfloat16, jnp.float32
        args = (_s((self.S, self.E), bf, chip), _s((self.S, self.E), f32, chip), _s((self.N, self.E), f32, chip), _s((self.S, self.N), bf, chip),
                _s((self.S, self.N), bf, chip), _s((self.E,), f32, chip), _s((self.S, self.N, self.E), f32, chip))
        compiled = jax.jit(SS.selective_step, donate_argnums=(6,)).lower(*args).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == 1 and len(re.findall(r"%\w*selective_step[\w.]* = ", text)) == 1
        memory = compiled.memory_analysis()
        assert memory.alias_size_in_bytes >= 4 * self.S * self.N * self.E and memory.temp_size_in_bytes < 4 << 20

    def _decode_args(self, chip):
        bf, i32 = jnp.bfloat16, jnp.int32
        cur, staged = _s((self.S, self.PAIRS, self.WIDE), bf, chip), _s((self.S, 8, self.PAIRS, self.WIDE), bf, chip)
        return _s((self.S, self.H, self.WIDE // 2), jnp.float32, chip), _s((self.S,), i32, chip), _s((), i32, chip), cur, staged

    def test_both_maps_read_the_one_layers_pool_in_one_call(self, chip):
        """The call as the full layer and every cross layer make it: ONE Mosaic kernel whose operand is the pool of
        pairs, whole, twice (keys and values): a page moves once a reading layer."""
        q, lengths, layer, cur, staged = self._decode_args(chip)
        pool = (1, self.S * (self.MAX_LEN // self.PAGE) + 1, self.PAIRS, self.PAGE, self.WIDE)

        def fn(q, kp, vp, lengths, table, layer, cur_k, cur_v, sk, sv, count):
            return DA.differential_paged_decode_attention(q, kp, vp, lengths, table, layer, cur_k=cur_k, cur_v=cur_v,
                                                          staged_k=sk, staged_v=sv, staged_count=count)

        kp = _s(pool, jnp.bfloat16, chip)
        compiled = jax.jit(fn).lower(q, kp, kp, lengths, _s((self.S, self.MAX_LEN // self.PAGE), jnp.int32, chip), layer, cur, cur,
                                     staged, staged, lengths).compile()
        whole = f"bf16[{','.join(map(str, pool))}]"
        calls = [line for line in compiled.as_text().splitlines() if "tpu_custom_call" in line and " custom-call(" in line]
        assert len(calls) == 1 and calls[0].count(whole) == 2, calls
        assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2 ** 20

    def test_both_maps_read_a_ring_of_528_rows_in_one_call(self, chip):
        q, lengths, layer, cur, staged = self._decode_args(chip)
        rings = _s((8, self.S, self.PAIRS, self.WINDOW + 16, self.WIDE), jnp.bfloat16, chip)

        def fn(q, rk, rv, lengths, layer, cur_k, cur_v, sk, sv, count):
            return DA.differential_ring_decode_attention(q, rk, rv, lengths, layer, cur_k=cur_k, cur_v=cur_v, window=self.WINDOW,
                                                         staged_k=sk, staged_v=sv, staged_count=count)

        self._named(fn, "ring_decode_attention", q, rings, rings, lengths, layer, cur, cur, staged, staged, lengths)

    @pytest.mark.parametrize("rows", [2048, 256], ids=["a-whole-chunk", "the-smallest-bucket"])
    def test_both_maps_of_a_prefill_chunk_over_the_staging_of_pairs(self, chip, rows):
        i32 = jnp.int32
        staging = _s((1, 1, self.PAIRS, self.MAX_LEN, self.WIDE), jnp.bfloat16, chip)
        self._named(A.differential_chunk_prefill_attention, "chunk_prefill_attention", _s((rows, self.H, self.WIDE // 2), jnp.float32, chip),
                    staging, staging, _s((), i32, chip), _s((), i32, chip), _s((), i32, chip))

    @pytest.mark.parametrize("rows", [2048, 256], ids=["a-whole-chunk", "the-smallest-bucket"])
    def test_both_maps_of_a_window_layers_band(self, chip, rows):
        bf = jnp.bfloat16
        pairs, tail = _s((rows, self.PAIRS, self.WIDE), bf, chip), _s((self.PAIRS, self.WINDOW, self.WIDE), bf, chip)
        fn = lambda q, k, v, tk, tv, pos0: A.differential_window_prefill_attention(q, k, v, tk, tv, pos0, self.WINDOW)[0]
        text = jax.jit(fn).lower(_s((rows, self.H, self.WIDE // 2), jnp.float32, chip), pairs, pairs, tail, tail, _s((), jnp.int32, chip)).compile().as_text()
        assert text.count("tpu_custom_call") == 1

    @pytest.mark.parametrize("program", ["decode_chunk", "prefill_chunk"])
    def test_the_whole_programs_fit_beside_the_weights(self, chip, program):
        """All 32 layers at the published sizes (two scans of stacked periods, so a program compiles in seconds):
        3.85 B parameters, 7.7 GB; 16 slots' pool, rings, states and tails 3.4 GB; a request's staging 0.2 GB; the
        temporaries of a decode chunk and of a 2,048-row prefill chunk well under a GB."""
        from tony_tpu.models import phi4_flash as M

        cfg = M.Phi4FlashConfig()
        on = lambda tree: jax.tree.map(lambda a: _s(a.shape, a.dtype, chip), tree)
        params = on(jax.eval_shape(lambda k: M.init(k, cfg), jax.random.PRNGKey(0)))
        assert sum(a.size for a in jax.tree.leaves(params)) == 3_852_562_944
        if program == "decode_chunk":
            cache = on(jax.eval_shape(lambda: M._init_cache(cfg, self.S, self.MAX_LEN, self.PAGE, self.S * (self.MAX_LEN // self.PAGE) + 1)))
            lowered = M.decode_steps.lower(params, cache, _s((self.S,), jnp.int32, chip), _s((2,), jnp.uint32, chip), cfg, 8)
        else:
            staging = on(jax.eval_shape(lambda: M._init_staging(cfg, self.MAX_LEN)))
            lowered = M.prefill_chunk.lower(params, _s((1, 2048), jnp.int32, chip), staging, _s((), jnp.int32, chip), cfg)
        compiled = lowered.compile()
        memory = compiled.memory_analysis()
        assert memory.temp_size_in_bytes < 1 << 30 and memory.argument_size_in_bytes + memory.temp_size_in_bytes < 12.5e9
        text = compiled.as_text()
        assert ("selective_step" in text) == (program == "decode_chunk") and ("selective_chunk" in text) == (program == "prefill_chunk")
        assert not re.search(r"f32\[2048,(5120,16|16,5120)\]", text)
