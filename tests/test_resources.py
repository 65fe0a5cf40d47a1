"""TPU-slice resource model tests: topology parsing, ICI-contiguous rectangle
allocation (the GPU-scheduling analog of TestTaskScheduler, SURVEY.md §4)."""

import os
import time

import pytest

from tony_tpu.cluster.resources import (
    AllocationError,
    ChipGrid,
    LocalResourceManager,
    Resources,
    SliceSpec,
    squarish_topology,
)


class TestSliceSpec:
    @pytest.mark.parametrize(
        "spec,accel,topo",
        [
            ("v5e-64", "v5e", (8, 8)),
            ("v5e-8", "v5e", (2, 4)),
            ("v5e-256", "v5e", (16, 16)),
            ("v5e,4x8", "v5e", (4, 8)),
            ("cpu", "cpu", (0, 0)),
        ],
    )
    def test_parse(self, spec, accel, topo):
        s = SliceSpec.parse(spec)
        assert (s.accelerator, s.topology) == (accel, topo)

    def test_chips(self):
        assert SliceSpec.parse("v5e-64").chips == 64
        assert SliceSpec.parse("cpu").chips == 0

    def test_squarish(self):
        assert squarish_topology(12) == (3, 4)
        assert squarish_topology(7) == (1, 7)


class TestChipGrid:
    def test_rect_allocation_contiguous(self):
        g = ChipGrid((4, 4))
        coords = g.allocate_rect((2, 2))
        rows = {r for r, _ in coords}
        cols = {c for _, c in coords}
        assert len(coords) == 4
        # contiguity: the rectangle spans consecutive rows/cols (ICI affinity)
        assert rows == set(range(min(rows), max(rows) + 1))
        assert cols == set(range(min(cols), max(cols) + 1))

    def test_exhaustion(self):
        g = ChipGrid((2, 2))
        assert g.allocate_rect((2, 2)) is not None
        assert g.allocate_rect((1, 1)) is None

    def test_release_reuses(self):
        g = ChipGrid((2, 2))
        coords = g.allocate_rect((2, 2))
        g.release(coords)
        assert g.allocate_rect((2, 2)) is not None

    def test_orientation_fallback(self):
        g = ChipGrid((2, 4))
        assert g.allocate_rect((4, 2)) is not None  # rotated to fit

    def test_allocate_chips_prefers_square(self):
        g = ChipGrid((8, 8))
        coords = g.allocate_chips(16)
        rows = {r for r, _ in coords}
        cols = {c for _, c in coords}
        assert (len(rows), len(cols)) == (4, 4)

    def test_fragmentation_respected(self):
        g = ChipGrid((2, 4))
        g.allocate_rect((2, 2))
        assert g.allocate_chips(4) is not None   # 2x2 fits in the remainder
        assert g.allocate_chips(2) is None       # full now


class TestLocalResourceManager:
    def test_allocate_sets_device_env(self):
        rm = LocalResourceManager("local:v5e-8")
        c = rm.allocate("worker", 0, Resources(chips=4))
        env = c.device_env()
        assert env["TPU_CHIPS_PER_TASK"] == "4"
        assert env["TPU_SLICE_NAME"] == "v5e-8"
        assert len(env["TPU_CHIP_COORDS"].split(";")) == 4
        # what the TPU runtime itself reads: the chips' host indices (row-major
        # in the slice grid) and "one process, this rectangle"
        rows, cols = c.slice_topology
        assert env["TPU_VISIBLE_CHIPS"] == ",".join(
            str(i) for i in sorted(r * cols + col for r, col in c.chip_coords))
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "2,2,1"
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
        one = LocalResourceManager("local:v5e-4").allocate("worker", 0, Resources(chips=1))
        assert one.device_env()["TPU_VISIBLE_CHIPS"] == "0"
        assert one.device_env()["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert "TPU_VISIBLE_CHIPS" not in LocalResourceManager("local:cpu").allocate(
            "worker", 0, Resources()).device_env()

    def test_chip_exhaustion_raises(self):
        rm = LocalResourceManager("local:v5e-4")
        rm.allocate("worker", 0, Resources(chips=4))
        with pytest.raises(AllocationError):
            rm.allocate("worker", 1, Resources(chips=1))

    def test_release_returns_chips(self):
        rm = LocalResourceManager("local:v5e-4")
        c = rm.allocate("worker", 0, Resources(chips=4))
        rm.release(c)
        rm.allocate("worker", 1, Resources(chips=4))

    def test_memory_accounting(self):
        rm = LocalResourceManager("local:cpu", host_memory="4g")
        rm.allocate("worker", 0, Resources(memory_bytes=3 * 1024**3))
        with pytest.raises(AllocationError):
            rm.allocate("worker", 1, Resources(memory_bytes=2 * 1024**3))

    def test_cpu_pool_rejects_chip_asks(self):
        rm = LocalResourceManager("local:cpu")
        with pytest.raises(AllocationError):
            rm.allocate("worker", 0, Resources(chips=4))


class TestMultiSlicePool:
    def _rm(self, spec="pool:v5e-8x2"):
        from tony_tpu.cluster.resources import MultiSliceResourceManager

        return MultiSliceResourceManager(spec)

    def test_spec_parse_and_env(self):
        rm = self._rm("pool:v5e-8x4")
        assert rm.num_slices == 4
        assert rm.slices[0].spec.chips == 8
        c = rm.allocate("worker", 0, Resources(chips=4))
        assert c.slice_name == "v5e-8"
        assert rm.slice_of(c) in range(4)

    def test_bad_specs_rejected(self):
        import pytest as _pytest

        for bad in ("pool:v5e-8", "pool:x", "pool:v5e-0x2"):
            with _pytest.raises(ValueError):
                self._rm(bad)

    def test_best_fit_packs_one_slice_first(self):
        rm = self._rm("pool:v5e-8x2")
        a = rm.allocate("worker", 0, Resources(chips=4))
        b = rm.allocate("worker", 1, Resources(chips=4))
        # both fit slice 0 exactly — best-fit must co-locate them
        assert rm.slice_of(a) == rm.slice_of(b)

    def test_spill_to_second_slice(self):
        rm = self._rm("pool:v5e-8x2")
        cs = [rm.allocate("worker", i, Resources(chips=4)) for i in range(4)]
        slices = {rm.slice_of(c) for c in cs}
        assert slices == {0, 1}  # 4x4 chips over two 8-chip slices

    def test_task_larger_than_slice_rejected(self):
        rm = self._rm("pool:v5e-8x2")
        with pytest.raises(AllocationError, match="span DCN"):
            rm.allocate("worker", 0, Resources(chips=16))

    def test_pool_exhaustion(self):
        rm = self._rm("pool:v5e-4x2")
        rm.allocate("w", 0, Resources(chips=4))
        rm.allocate("w", 1, Resources(chips=4))
        with pytest.raises(AllocationError, match="no slice"):
            rm.allocate("w", 2, Resources(chips=1))

    def test_release_refills_slice(self):
        rm = self._rm("pool:v5e-4x2")
        a = rm.allocate("w", 0, Resources(chips=4))
        rm.allocate("w", 1, Resources(chips=4))
        rm.release(a)
        c = rm.allocate("w", 2, Resources(chips=4))
        assert rm.slice_of(c) == 0 or rm.slice_of(c) == 1

    def test_slice_env_injected_at_start(self, tmp_path):
        import sys as _sys

        rm = self._rm("pool:v5e-4x2")
        c = rm.allocate("w", 0, Resources(chips=4))
        rm.allocate("w", 1, Resources(chips=4))  # spills → gang spans 2 slices
        out = tmp_path / "env.txt"
        rm.start_container(
            c,
            [_sys.executable, "-c",
             "import os;open(r'%s','w').write(os.environ['TPU_SLICE_ID']+' '+os.environ['TPU_NUM_SLICES'])" % out],
            {"PATH": os.environ.get("PATH", "")},
            str(tmp_path / "logs"),
        )
        for _ in range(100):
            if rm.poll_exited():
                break
            time.sleep(0.05)
        assert out.read_text() == "0 2"
        rm.shutdown()

    def test_hosts_per_slice(self):
        rm = self._rm("pool:v5e-8x2")
        assert len(rm.slices[0].hosts) == 2  # 8 chips / 4 per host
        c = rm.allocate("w", 0, Resources(chips=8))
        assert c.host.startswith("slice")

    def test_gang_span_not_pool_size(self, tmp_path):
        # a gang packed into ONE slice of a 4-slice pool is all-ICI: its env
        # must say num_slices=1 (pool size would force a bogus hybrid mesh)
        import sys as _sys

        rm = self._rm("pool:v5e-8x4")
        a = rm.allocate("w", 0, Resources(chips=4))
        b = rm.allocate("w", 1, Resources(chips=4))
        assert rm.gang_slice_span() == [rm.slice_of(a)]
        out = tmp_path / "env.txt"
        rm.start_container(
            b,
            [_sys.executable, "-c",
             "import os;open(r'%s','w').write(os.environ['TPU_SLICE_ID']+' '+os.environ['TPU_NUM_SLICES'])" % out],
            {"PATH": os.environ.get("PATH", "")},
            str(tmp_path / "logs"),
        )
        for _ in range(100):
            if rm.poll_exited():
                break
            time.sleep(0.05)
        assert out.read_text() == "0 1"
        rm.shutdown()

    def test_gang_span_appends_across_launch_waves(self):
        # dependency-gated type B allocated AFTER type A started may land on
        # a new slice: the span must grow (appending, so A's indices stay
        # valid) rather than crash on a frozen snapshot
        rm = self._rm("pool:v5e-4x2")
        a = [rm.allocate("a", i, Resources(chips=4)) for i in range(1)]
        assert rm.gang_slice_span() == [rm.slice_of(a[0])]
        # wave 2: slice of wave 1 is full → lands on the other slice
        b = rm.allocate("b", 0, Resources(chips=4))
        span = rm.gang_slice_span()
        assert span[0] == rm.slice_of(a[0]) and set(span) == {0, 1}
        # release everything → span resets for a restarted gang
        for c in a + [b]:
            rm.release(c)
        c2 = rm.allocate("a", 0, Resources(chips=4))
        assert rm.gang_slice_span() == [rm.slice_of(c2)]
        rm.shutdown()
