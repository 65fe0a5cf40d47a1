"""The routed FFN's kernels under the interpreter: the grouped SwiGLU, a group with no rows, the decode
kernel that gathers its rows and sums its choices, and a long chunk's pair that fetch a row at a time.
Attention and the plain layers are tests/test_ops.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


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
    def _rows(cls, first, second, seed=0, D=None):
        T, D = len(first), D or cls.D
        x = np.array(jax.random.normal(jax.random.PRNGKey(seed), (T, D)) * 0.5)
        x[:, :cls.E] = 0
        x[np.arange(T), first] = 2
        x[np.arange(T), second] = 1
        return jnp.asarray(x, jnp.bfloat16), jnp.zeros((D, cls.E), jnp.float32).at[:cls.E].set(4 * jnp.eye(cls.E))

    @classmethod
    def _banks(cls, experts, seed=1):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        up = lambda k: (jax.random.normal(k, (experts, cls.D, cls.F)) / cls.D ** 0.5).astype(jnp.bfloat16)
        return up(ks[0]), up(ks[1]), (jax.random.normal(ks[2], (experts, cls.F, cls.D)) / cls.F ** 0.5).astype(jnp.bfloat16)

    @staticmethod
    def _blocks_in_range(call):
        """Every block index of the recorded pallas_call, at every grid step, names a block its operand has
        (an operand left in HBM has no blocks)."""
        spec, operands, scalars, out_shape = call
        for m in range(spec.grid[0]):
            for c in range(spec.grid[1]):
                for bs, operand in zip((*spec.in_specs, spec.out_specs), (*operands, out_shape)):
                    if bs.block_shape is None:
                        continue
                    index = [int(i) for i in bs.index_map(jnp.int32(m), jnp.int32(c), *scalars)]
                    blocks = [-(-dim // (b or 1)) for dim, b in zip(operand.shape, bs.block_shape)]
                    assert all(0 <= i < n for i, n in zip(index, blocks)), (m, c, index, blocks)

    def _held(self, monkeypatch, case, form="in_kernel"):
        """(y, rows) of the kernel's path and of ragged_dot's on the same values, and what the kernel was handed,
        in the form ``held_form`` chooses at 24 tokens (the kernel gathers and sums), in the one it chooses for
        a long chunk at a served width (the kernel fetches a live tile's real rows, a second sums the choices from
        the rows that exist), or staged through HBM by XLA."""
        from jax.experimental import pallas as pl

        from tony_tpu.ops import moe_gemm as MG
        from tony_tpu.parallel import expert as EX
        from tony_tpu.parallel.expert import MoEConfig, held_expert_ffn, held_form

        monkeypatch.setattr(MG, "TILE_M", self.TILE)
        if form == "staged":
            monkeypatch.setattr(EX, "HELD_IN_KERNEL_TOKENS", 0)
        elif form == "fetched":
            # a row travels alone at a width that is a multiple of 1024 (whole tiles of 8 sublanes); the interpreter takes any
            monkeypatch.setattr(EX, "held_form", lambda T, D, itemsize, top_k=8: "fetched")
            assert held_form(2048, 4096, 2) == "fetched" and held_form(2048, self.D, 2) == "staged"
        assert EX.held_form(self.T, self.D, 2) == form
        seen = {}
        rows_call, tokens_call, pallas_call = MG.moe_swiglu_rows, MG.moe_swiglu_tokens, pl.pallas_call
        fetched_call = MG.moe_swiglu_fetched.__wrapped__                 # under its jit the recorded operands would be tracers

        def recorded_rows(xs, wg, wu, wd, tile_group, tile, live, *rest):
            seen.update(tile_group=np.asarray(tile_group), live=int(live), tile=tile, rows=xs.shape[0], form="staged")
            return rows_call(xs, wg, wu, wd, tile_group, tile, live, *rest)

        def recorded_tokens(x, sort_tok, gate_sorted, wg, wu, wd, tile_group, tile, live, *rest):
            seen.update(tile_group=np.asarray(tile_group), live=int(live), tile=tile, rows=sort_tok.shape[0], form="in_kernel")
            return tokens_call(x, sort_tok, gate_sorted, wg, wu, wd, tile_group, tile, live, *rest)

        def recorded_fetched(x, sort_tok, real, wg, wu, wd, tile_group, tile, live, *rest):
            seen.update(tile_group=np.asarray(tile_group), live=int(live), tile=tile, rows=sort_tok.shape[0], form="fetched",
                        real=np.asarray(real), sort_tok=np.asarray(sort_tok))
            return fetched_call(x, sort_tok, real, wg, wu, wd, tile_group, tile, live, *rest)

        def recorded_pallas(kernel, *, grid_spec, out_shape, **kw):
            inner = pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shape, **kw)

            def run(*operands):
                scalars, operands = operands[:grid_spec.num_scalar_prefetch], operands[grid_spec.num_scalar_prefetch:]
                if len(grid_spec.grid) == 2:                             # the grouped product (the choices' sum has one axis)
                    seen["call"] = (grid_spec, operands, scalars, out_shape)
                return inner(*scalars, *operands)
            return run

        monkeypatch.setattr(MG, "moe_swiglu_rows", recorded_rows)
        monkeypatch.setattr(MG, "moe_swiglu_tokens", recorded_tokens)
        monkeypatch.setattr(MG, "moe_swiglu_fetched", recorded_fetched)
        monkeypatch.setattr(pl, "pallas_call", recorded_pallas)
        cfg = MoEConfig(num_experts=self.E, top_k=self.K, held=self.HELD)
        x, router = self._rows(*self.CASES[case])
        banks = tuple(b[None] for b in self._banks(self.HELD[1]))
        got = held_expert_ffn(x, router, None, *banks, jnp.int32(0), cfg)
        plain = held_expert_ffn(x.astype(jnp.float32), router, None, *(b.astype(jnp.float32) for b in banks), jnp.int32(0), cfg)
        assert seen["call"][1][-1].dtype == jnp.bfloat16                 # the kernel ran once (its banks): float32 rows take ragged_dot
        assert seen["form"] == form
        return got, plain, seen

    @pytest.mark.parametrize("case,form", [*((c, f) for c in CASES for f in ("in_kernel", "staged", "fetched")),
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
        if form == "fetched":
            # a tile's real rows are its first (what the kernel fetches); a tile past the groups has none
            want_real = np.concatenate([np.minimum(np.maximum(n - self.TILE * np.arange(t), 0), self.TILE) for n, t in zip(want_rows, tiles)]
                                       + [np.zeros(len(seen["real"]) - seen["live"], int)])
            assert seen["real"].tolist() == want_real.tolist() and seen["real"].sum() == want_rows.sum()
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
    expert, a held expert no row chose, an idle slot under ``count_mask``, T in no whole sublane group.
    And a long prefill chunk's form (``moe_gemm.moe_swiglu_fetched`` and ``moe_choices_sum``: the tokens stay
    in HBM, a live tile's real rows and a token's existing choices each come by one DMA) against the staged
    form at 1024 and 2048 rows of 1024 numbers, the least width a row travels alone at: one choice in eight
    on a held expert and one in two, a tile of 128 rows and the doubled one, and the same edges. There ``y``
    is equal TO THE BIT: both forms round a row's output and the gates alike and add a token's choices in
    float32 in choice order (the kernel's own form adds them in tile order, so it is held to a band)."""

    D, F, TILE = 128, 256, 16
    SHAPES = {  # T, experts, top_k, held (first, count), width blocks, scoring
        "serve_notes-like": (24, 32, 8, (4, 8), 1, "sigmoid"),
        "serve_docqa-like": (48, 16, 4, (8, 4), 2, "softmax"),
        "serve_assist-like": (64, 12, 10, (0, 6), 1, "softmax"),
        "serve_reason-like": (256, 16, 8, (2, 4), 2, "sigmoid"),
        "t-in-no-whole-sublane-group": (20, 8, 3, (0, 4), 1, "softmax"),
    }
    EDGES = ("no-held-expert-chosen", "a-held-expert-unchosen", "an-idle-slot-is-not-counted")
    FETCHED = {  # T, experts, top_k, held (first, count), the row tile ``held_tile`` gives, scoring
        "fetched-1024-rows-one-choice-in-eight": (1024, 96, 8, (12, 12), 128, "sigmoid"),
        "fetched-2048-rows-one-choice-in-eight": (2048, 160, 8, (20, 20), 128, "sigmoid"),
        "fetched-1024-rows-one-choice-in-two-a-doubled-tile": (1024, 12, 10, (0, 6), 256, "softmax"),
        "fetched-2048-rows-one-choice-in-two-a-doubled-tile": (2048, 12, 10, (6, 6), 256, "softmax"),
        "fetched-t-in-no-whole-step-of-the-sum": (1000, 64, 6, (8, 8), 128, "softmax"),
    }
    FETCHED_EDGES = tuple("fetched-" + edge for edge in EDGES)
    D_FETCHED, F_FETCHED = 1024, 128

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

    def _fetched_and_staged(self, monkeypatch, x, router, bias, banks, cfg, tile, count_mask=None):
        """(y, rows) in the form ``held_form`` chooses from these shapes, which is the fetched one, and staged."""
        from tony_tpu.ops import moe_gemm as MG
        from tony_tpu.parallel import expert as EX

        T, D = x.shape
        assert EX.held_ffn_form(cfg, T, D, banks[0].shape[-1], x.dtype) == "fetched"
        assert EX.held_tile(cfg, T * cfg.top_k, MG.TILE_M) == tile
        ran = []
        for name in ("moe_swiglu_tokens", "moe_swiglu_rows", "moe_swiglu_fetched", "moe_choices_sum"):
            monkeypatch.setattr(MG, name, lambda *a, _call=getattr(MG, name), _name=name, **kw: (ran.append(_name), _call(*a, **kw))[1])
        call = lambda: EX.held_expert_ffn(x, router, bias, *banks, jnp.int32(1), cfg, count_mask=count_mask, name="moe_swiglu_prefill")
        got = call()
        monkeypatch.setattr(EX, "held_form", lambda T, D, itemsize, top_k=8: "staged")
        want = call()
        assert ran == ["moe_swiglu_fetched", "moe_choices_sum", "moe_swiglu_rows"]
        return got, want

    def _banks(self, count, seed=1, D=None, F=None):
        D, F = D or self.D, F or self.F
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        up = lambda k: (jax.random.normal(k, (2, count, D, F)) / D ** 0.5).astype(jnp.bfloat16)
        return up(ks[0]), up(ks[1]), (jax.random.normal(ks[2], (2, count, F, D)) / F ** 0.5).astype(jnp.bfloat16)

    @staticmethod
    def _equal(y, y_staged):
        y, y_staged = np.asarray(y, np.float32), np.asarray(y_staged, np.float32)
        assert y.shape == y_staged.shape and np.isfinite(y).all() and np.array_equal(y, y_staged)

    @staticmethod
    def _close(y, y_staged):
        """Within bfloat16 rounding: both forms round a tile's output and the gates alike and sum a
        token's choices wide, in another order, then round once (an ulp of bfloat16 is 2 ** -8 of the value)."""
        y, y_staged = np.asarray(y, np.float32), np.asarray(y_staged, np.float32)
        assert y.shape == y_staged.shape and np.isfinite(y).all()
        np.testing.assert_allclose(y, y_staged, rtol=2 ** -7, atol=2 ** -7 * max(np.abs(y_staged).max(), 1e-3))

    @pytest.mark.parametrize("case", [*SHAPES, *EDGES, *FETCHED, *FETCHED_EDGES])
    def test_the_two_forms_agree(self, monkeypatch, case):
        from tony_tpu.parallel.expert import MoEConfig

        if case in self.SHAPES or case in self.FETCHED:
            fetched = case in self.FETCHED
            T, E, K, held, blocks_or_tile, scoring = (self.FETCHED if fetched else self.SHAPES)[case]
            D, F = (self.D_FETCHED, self.F_FETCHED) if fetched else (self.D, self.F)
            ks = jax.random.split(jax.random.PRNGKey(len(case)), 3)
            x = (jax.random.normal(ks[0], (T, D)) * 0.5).astype(jnp.bfloat16)
            router = jax.random.normal(ks[1], (D, E), jnp.float32) / D ** 0.5
            bias = 0.1 * jax.random.normal(ks[2], (E,)) if scoring == "sigmoid" else None
            cfg = MoEConfig(num_experts=E, top_k=K, held=held, scoring=scoring, routed_scale=2.5 if scoring == "sigmoid" else 1.0)
            banks = self._banks(held[1], D=D, F=F)
            (y, rows), (y_staged, rows_staged) = (self._fetched_and_staged if fetched else self._both)(
                monkeypatch, x, router, bias, banks, cfg, blocks_or_tile)
            assert np.array_equal(np.asarray(rows), np.asarray(rows_staged)) and int(rows.sum()) > 0
            assert np.abs(np.asarray(y_staged, np.float32)).max() > 0.05
            if fetched:
                share = int(rows.sum()) / (T * K)                          # the choices that have a row here
                assert abs(share - held[1] / E) < 0.03
            return (self._equal if fetched else self._close)(y, y_staged)
        # the edges, choices set by hand as TestAGroupWithNoRows sets them: top-2 of 8, experts 1 .. 5 held
        first, second = {
            "no-held-expert-chosen": ([0] * 24, [7] * 24),
            "a-held-expert-unchosen": ([2] * 20 + [4] * 4, [7] * 20 + [5] * 4),
            "an-idle-slot-is-not-counted": ([1 + t % 5 for t in range(24)], [1 + (t + 2) % 5 for t in range(24)]),
        }[case.removeprefix("fetched-")]
        cfg = MoEConfig(num_experts=8, top_k=2, held=(1, 5))
        if case in self.FETCHED_EDGES:
            # the same choices 43 times over: 1,032 tokens, an expert's 256 rows or more, so the doubled tile
            first, second = first * 43, second * 43
            x, router = TestAGroupWithNoRows._rows(first, second, D=self.D_FETCHED)
            live = jnp.arange(len(first)) % 3 != 1 if case.endswith("an-idle-slot-is-not-counted") else None
            (y, rows), (y_staged, rows_staged) = self._fetched_and_staged(
                monkeypatch, x, router, None, self._banks(5, D=self.D_FETCHED, F=self.F_FETCHED), cfg, 256, count_mask=live)
            self._equal(y, y_staged)
            case = case.removeprefix("fetched-")
        else:
            x, router = TestAGroupWithNoRows._rows(first, second)
            live = jnp.arange(24) % 3 != 1 if case == "an-idle-slot-is-not-counted" else None
            (y, rows), (y_staged, rows_staged) = self._both(monkeypatch, x, router, None, self._banks(5), cfg, count_mask=live)
            self._close(y, y_staged)
        assert np.array_equal(np.asarray(rows), np.asarray(rows_staged))
        chosen = np.array([first, second]).T                               # [T, 2]
        counted = chosen if live is None else chosen[np.asarray(live)]
        assert np.asarray(rows).tolist() == np.bincount(counted[(counted >= 1) & (counted < 6)] - 1, minlength=5).tolist()
        if case == "no-held-expert-chosen":
            assert not np.asarray(y, np.float32).any() and not np.asarray(rows).any()      # live == 0: y is zero
        elif case == "a-held-expert-unchosen":
            assert np.asarray(rows).tolist() == [n * (len(first) // 24) for n in (0, 20, 0, 4, 4)] and np.abs(np.asarray(y, np.float32)).max() > 0.1
        else:
            # an idle slot's row is computed like any (y is the unmasked call's) and counted as none
            assert int(rows.sum()) == 2 * int(live.sum()) and np.abs(np.asarray(y, np.float32)[1]).max() > 0.05

    def test_the_form_follows_the_shapes(self):
        """In the kernel at the four cells' decode batches at their widths and at a 512-row bucket, fetched a
        row at a time at a 1024- and a 2048-row chunk, and once out of the kernel never in it again as T grows.
        Staged by XLA only where no row travels alone (a width that is no multiple of 1024) or VMEM is short."""
        from tony_tpu.parallel.expert import held_form

        for T, D in ((24, 5120), (48, 4096), (64, 4096), (256, 6144), (512, 6144)):
            assert held_form(T, D, 2) == "in_kernel", (T, D)
        for D in (4096, 5120, 6144):
            assert held_form(2048, D, 2) == "fetched" and held_form(1024, D, 2) == "fetched" and held_form(2048, D, 2, top_k=10) == "fetched"
            forms = [held_form(T, D, 2) for T in range(8, 4097, 8)]
            switch = forms.index("fetched")
            assert switch > 0 and set(forms[:switch]) == {"in_kernel"} and set(forms[switch:]) == {"fetched"}
        for D in (128, 2560, 4096 + 512):                                                             # a slab of D / 128 sublanes is no whole tiles of 8
            forms = [held_form(T, D, 2) for T in range(8, 4097, 8)]
            switch = forms.index("staged")
            assert switch > 0 and set(forms[:switch]) == {"in_kernel"} and set(forms[switch:]) == {"staged"}
        assert held_form(64, 16384, 2) == "in_kernel" and held_form(512, 16384, 2) == "staged"      # what VMEM holds
        assert held_form(2048, 8192, 2) == "fetched" and held_form(2048, 8192, 2, top_k=16) == "staged"
