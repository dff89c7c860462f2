"""Device-resident tile storage (ISSUE 5): block homes are physical.

Covers the tentpole's acceptance surface: (a) after ``from_array`` on a
mesh, every tile is committed to the device ``placement.device_assignment``
maps its home to — on ``dist.single_device_mesh()`` in-process and on a
forced-host 2-device mesh in a subprocess; (b) the *measured* cross-device
bytes (``TileTraffic``, reported as ``RuntimeStats.bytes_moved``) equal the
footprint-predicted ``cross_home_bytes`` on striped gemm when homes and
devices coincide, with ``bytes_staged == 0`` — wave dispatches never stage
operands through a non-home device; (c) sharded-vs-sequential bit-equality
holds with tiles physically distributed.  Plus the memory-layer unit
surface: TileStore swapping, destination-aware ``materialize``/``gather``,
and the contention-aware owner override (``rebalance_owners`` +
``RuntimeConfig.owner_skew_threshold``).
"""
import pathlib
import subprocess
import sys

import numpy as np
import pytest
import jax

from repro import dist
from repro.core import RuntimeConfig, TaskRuntime, task
from repro.core.blocks import (BlockArray, DeviceTileStore, HostTileStore,
                               TileTraffic, device_of)
from repro.core.placement import (assign_homes, device_assignment,
                                  rebalance_owners)


@task(inout="c", in_=("a", "b"))
def _gemm(c, a, b):
    return c + a @ b


def _gemm_program(rt, a, b, tile=32):
    """Run tiled gemm; returns (result, stats-before-gather)."""
    n = a.shape[0]
    g = n // tile
    with rt.scope():
        A = rt.from_array(a, (tile, tile), name="A")
        B = rt.from_array(b, (tile, tile), name="B")
        C = rt.zeros((n, n), (tile, tile), name="C")
        for i in range(g):
            for j in range(g):
                for k in range(g):
                    _gemm(C[i, j], A[i, k], B[k, j])
        rt.barrier()
        s = rt.stats()
        return np.asarray(C.gather()), s


# ---------------------------------------------------------------------------
class TestTileStore:
    def test_default_store_is_host(self):
        ba = BlockArray.from_array(np.zeros((8, 8), np.float32), (4, 4))
        assert isinstance(ba.store, HostTileStore)
        assert ba.store.device_for((0, 0)) is None
        assert ba.tile_device((0, 0)) is None     # uncommitted host tile

    def test_device_store_places_tiles_on_homes(self):
        """from_array through a sharded runtime under a mesh: every tile
        committed to device_assignment[home] (acceptance item (a) on the
        single-device mesh)."""
        with dist.use_mesh(dist.single_device_mesh()) as ctx:
            with TaskRuntime(executor="sharded", placement="striped") as rt:
                A = rt.from_array(np.ones((16, 16), np.float32), (4, 4))
                devmap = device_assignment(rt.n_controllers, ctx)
                assert isinstance(A.store, DeviceTileStore)
                for idx in A.block_indices():
                    assert A.tile_device(idx) == \
                        devmap[A.home[idx] % len(devmap)]

    def test_use_store_migration_not_charged(self):
        """Homing tiles at registration is placement, not traffic."""
        with dist.use_mesh(dist.single_device_mesh()):
            with TaskRuntime(executor="sharded") as rt:
                rt.from_array(np.ones((16, 16), np.float32), (4, 4))
                assert rt.traffic.tile_moves == 0
                assert rt.traffic.bytes_moved == 0

    def test_no_mesh_keeps_host_store(self):
        with TaskRuntime(executor="sharded") as rt:
            A = rt.from_array(np.ones((8, 8), np.float32), (4, 4))
            assert isinstance(A.store, HostTileStore)

    def test_non_sharded_executors_keep_host_store(self):
        with dist.use_mesh(dist.single_device_mesh()):
            for ex in ("sequential", "staged"):
                with TaskRuntime(executor=ex) as rt:
                    A = rt.zeros((8, 8), (4, 4))
                    assert isinstance(A.store, HostTileStore)

    def test_set_tile_recommits_to_home(self):
        """A write re-commits to the home device regardless of where the
        value was produced."""
        with dist.use_mesh(dist.single_device_mesh()) as ctx:
            with TaskRuntime(executor="sharded") as rt:
                A = rt.zeros((8, 8), (4, 4))
                devmap = device_assignment(rt.n_controllers, ctx)
                A.set_tile((0, 0), jax.numpy.ones((4, 4)))
                assert A.tile_device((0, 0)) == devmap[A.home[(0, 0)] % len(devmap)]


class TestDestinationAwareAssembly:
    def test_materialize_accepts_destination(self):
        ba = BlockArray.from_array(np.arange(64, dtype=np.float32)
                                   .reshape(8, 8), (4, 4))
        dev = jax.devices()[0]
        out = ba[0:2, 0:2].materialize(device=dev)
        assert out.shape == (8, 8)
        np.testing.assert_array_equal(
            np.asarray(out), np.arange(64, dtype=np.float32).reshape(8, 8))

    def test_gather_accepts_destination(self):
        arr = np.arange(64, dtype=np.float32).reshape(8, 8)
        ba = BlockArray.from_array(arr, (4, 4))
        np.testing.assert_array_equal(
            np.asarray(ba.gather(device=jax.devices()[0])), arr)

    def test_single_device_assembly_charges_nothing(self):
        """Uncommitted host tiles never count as traffic."""
        ba = BlockArray.from_array(np.ones((8, 8), np.float32), (4, 4))
        ba.traffic = TileTraffic()
        ba.whole.materialize()
        ba.gather()
        assert ba.traffic.tile_moves == 0
        assert ba.traffic.bytes_staged == 0

    def test_committed_local_read_counts_local(self):
        with dist.use_mesh(dist.single_device_mesh()):
            with TaskRuntime(executor="sharded") as rt:
                A = rt.from_array(np.ones((8, 8), np.float32), (4, 4))
                A.whole.materialize(device=jax.devices()[0])
                assert rt.traffic.bytes_local > 0
                assert rt.traffic.tile_moves == 0


class TestOwnerOverride:
    def test_rebalance_disabled_returns_input(self):
        owners, spilled = rebalance_owners([0, 0, 0, 0], 4, 0.0)
        assert owners == [0, 0, 0, 0] and spilled == 0

    def test_rebalance_spills_hot_home(self):
        owners, spilled = rebalance_owners([0] * 8, 4, 1.5)
        assert spilled > 0
        load = [owners.count(h) for h in range(4)]
        assert max(load) <= 1.5 * (8 / 4)

    def test_rebalance_balanced_wave_untouched(self):
        owners, spilled = rebalance_owners([0, 1, 2, 3] * 4, 4, 1.5)
        assert spilled == 0
        assert owners == [0, 1, 2, 3] * 4

    def test_rebalance_deterministic(self):
        a = rebalance_owners([0, 0, 0, 1, 0, 0], 4, 1.2)
        b = rebalance_owners([0, 0, 0, 1, 0, 0], 4, 1.2)
        assert a == b

    def test_config_knob_validates(self):
        with pytest.raises(ValueError, match="owner_skew_threshold"):
            RuntimeConfig(owner_skew_threshold=-1.0).validate()

    def test_override_counted_and_numerics_hold(self):
        """Single-home placement with the override on: tasks spill, the
        stats say so, numerics stay bit-identical to sequential."""
        rng = np.random.default_rng(7)
        a = rng.standard_normal((64, 64), dtype=np.float32)
        b = rng.standard_normal((64, 64), dtype=np.float32)
        ref, _ = _gemm_program(TaskRuntime(executor="sequential"), a, b)
        with dist.use_mesh(dist.single_device_mesh()):
            rt = TaskRuntime(executor="sharded", placement="single",
                             owner_skew_threshold=1.5)
            got, s = _gemm_program(rt, a, b)
        np.testing.assert_array_equal(ref, got)
        assert s.owner_overrides and s.owner_overrides > 0
        # spilling away from the hot home makes some reads (and the
        # write-back) cross-home: the charge the override knowingly pays
        assert s.cross_home_bytes > 0

    def test_override_off_by_default(self):
        with dist.use_mesh(dist.single_device_mesh()):
            rt = TaskRuntime(executor="sharded", placement="single")
            rng = np.random.default_rng(8)
            a = rng.standard_normal((64, 64), dtype=np.float32)
            _gemm_program(rt, a, a)
        s = rt.stats()
        assert s.owner_overrides == 0
        assert s.cross_home_bytes == 0


class TestResidencyStats:
    def test_all_executors_report_residency_fields(self):
        """Same residency semantics everywhere: the counters exist (and
        are zero where nothing ever moves across devices)."""
        rng = np.random.default_rng(9)
        a = rng.standard_normal((64, 64), dtype=np.float32)
        for ex in ("sequential", "staged", "sharded"):
            rt = TaskRuntime(executor=ex)
            _gemm_program(rt, a, a)
            s = rt.stats()
            assert s.tile_moves == 0
            assert s.bytes_moved == 0
            assert s.bytes_staged == 0

    def test_sim_reports_predicted_tile_moves(self):
        sys.path.insert(0, ".")
        from benchmarks.apps import run_app
        s = run_app("matmul", "sim", app_kwargs={"n": 128, "tile": 32})
        # g^2 (g-1) cross-home A-reads under striped homes, g=4
        assert s.tile_moves and s.tile_moves > 0
        assert s.bytes_staged == 0

    def test_mesh_wave_dispatch_never_stages(self):
        """The acceptance criterion on the single-device mesh: every
        group runs as a program pinned to its owner device and stages
        zero operand bytes through a non-home device."""
        rng = np.random.default_rng(10)
        a = rng.standard_normal((128, 128), dtype=np.float32)
        with dist.use_mesh(dist.single_device_mesh()):
            rt = TaskRuntime(executor="sharded", placement="striped")
            _, s = _gemm_program(rt, a, a)
        assert s.sharded_dispatches == s.grouped_dispatches > 0
        assert s.bytes_staged == 0


# ---------------------------------------------------------------------------
def test_two_device_residency_and_accounting():
    """The real thing, in a forced-host 2-device subprocess: (a) tiles
    committed to device_assignment[home]; (b) measured bytes_moved ==
    footprint-predicted cross_home_bytes on striped gemm with homes ==
    devices, bytes_staged == 0 through every wave dispatch; (c) sharded
    results bit-identical to sequential."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, "src")
import jax, numpy as np
from repro import dist
from repro.core import TaskRuntime, task
from repro.core.blocks import DeviceTileStore
from repro.core.placement import device_assignment

assert jax.device_count() == 2
mesh = jax.sharding.Mesh(np.asarray(jax.devices()).reshape(2), ("data",))

@task(inout="c", in_=("a", "b"))
def gemm(c, a, b):
    return c + a @ b

rng = np.random.default_rng(0)
a = rng.standard_normal((128, 128), dtype=np.float32)
b = rng.standard_normal((128, 128), dtype=np.float32)

def prog(rt, tile=32):
    g = 128 // tile
    with rt.scope():
        A = rt.from_array(a, (tile, tile)); B = rt.from_array(b, (tile, tile))
        C = rt.zeros((128, 128), (tile, tile))
        for i in range(g):
            for j in range(g):
                for k in range(g):
                    gemm(C[i, j], A[i, k], B[k, j])
        rt.barrier()
        s = rt.stats()      # dispatch accounting, before the gather
        return np.asarray(C.gather()), s, (A, B, C)

ref, _, _ = prog(TaskRuntime(executor="sequential"))
with dist.use_mesh(mesh) as ctx:
    rt = TaskRuntime(executor="sharded", placement="striped",
                     n_controllers=2)
    got, s, arrays = prog(rt)
    devmap = device_assignment(2, ctx)

# (c) bit-equality with tiles physically distributed over 2 devices
np.testing.assert_array_equal(ref, got)
# (a) every tile lives on its home's device
for ba in arrays:
    assert isinstance(ba.store, DeviceTileStore)
    for idx in ba.block_indices():
        assert ba.tile_device(idx) == devmap[ba.home[idx] % 2], \
            (ba.name, idx)
# every wave ran one group program per owner device: 4 waves x 2
assert s.sharded_dispatches == s.grouped_dispatches == 8, s
# (b) zero staging; measured moves equal the footprint prediction
assert s.bytes_staged == 0, s.bytes_staged
assert s.bytes_moved == s.cross_home_bytes, (s.bytes_moved,
                                             s.cross_home_bytes)
# exact count: with 2 striped homes (g even) only the A[i,k] read
# crosses, and only when k and j differ in parity -> g^3/2 blocks
g, block_bytes = 4, 32 * 32 * 4
assert s.cross_home_bytes == g ** 3 // 2 * block_bytes, s.cross_home_bytes
assert s.tile_moves == g ** 3 // 2, s.tile_moves
# the gather read-back itself assembles on the destination: direct
# moves for the off-destination half of C's tiles, still zero staging
s2 = rt.stats()
assert s2.bytes_staged == 0, s2.bytes_staged
assert s2.bytes_moved == s.bytes_moved + g * g // 2 * block_bytes
print("RESIDENCY-2DEV-OK")
"""
    out = subprocess.run([sys.executable, "-c", code],
                         cwd=pathlib.Path(__file__).resolve().parent.parent,
                         capture_output=True, text=True, timeout=300)
    assert "RESIDENCY-2DEV-OK" in out.stdout, out.stderr[-2000:]
