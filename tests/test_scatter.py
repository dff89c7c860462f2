"""Input tiling in one device program: ``BlockArray.scatter`` and
``BlockArray.from_array`` cut a whole array into its tiles with the one
cached jit ``blocks._split_tiles`` and commit them with one
``TileStore.set_many``.

Covers: tiles bit-identical to per-tile slices (ranks 1-3, non-square
grids, a 1x1 grid), the shape check, the ``split_programs`` /
``tiles_split`` counters (on ``TileTraffic`` and in ``RuntimeStats``),
one trace per shape, the input left alive (never donated), the store's
batched commit, and, on a forced-host 2-device mesh in a subprocess,
tiles homed on their devices with traffic charged exactly as per-tile
``set`` charges it.
"""
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TaskRuntime
from repro.core.blocks import (BlockArray, HostTileStore, TileTraffic,
                               _split_tiles)

# (shape, block_shape): rank 1, a non-square rank-2 grid, rank 3, 1x1
CASES = [((12,), (4,)), ((8, 12), (4, 3)), ((4, 6, 4), (2, 3, 2)),
         ((8, 8), (8, 8))]


def _data(shape, seed=0):
    return np.random.default_rng(seed).standard_normal(shape).astype(
        np.float32)


def _assert_tiles_are_slices(ba, arr):
    for idx in ba.block_indices():
        sl = tuple(slice(i * b, (i + 1) * b)
                   for i, b in zip(idx, ba.block_shape))
        tile = np.asarray(ba.get_tile(idx))
        assert tile.shape == ba.block_shape
        np.testing.assert_array_equal(tile.view(np.uint32),
                                      arr[sl].view(np.uint32))


@pytest.mark.parametrize("path", ["scatter", "from_array"])
@pytest.mark.parametrize("shape,block", CASES)
def test_tiles_bit_identical_to_slices(path, shape, block):
    a = _data(shape)
    if path == "from_array":
        ba = BlockArray.from_array(a, block)
    else:
        ba = BlockArray.zeros(shape, block)
        ba.scatter(a)
    _assert_tiles_are_slices(ba, a)
    np.testing.assert_array_equal(np.asarray(ba.gather()), a)


def test_scatter_shape_mismatch_raises():
    ba = BlockArray.zeros((8, 8), (4, 4))
    with pytest.raises(ValueError, match="scatter shape mismatch"):
        ba.scatter(np.zeros((8, 4), np.float32))


def test_scatter_counts_one_program_and_its_tiles():
    ba = BlockArray.from_array(_data((8, 12)), (4, 3))
    ba.traffic = TileTraffic()
    ba.scatter(_data((8, 12), 1))
    assert (ba.traffic.split_programs, ba.traffic.tiles_split) == (1, 8)
    ba.scatter(_data((8, 12), 2))
    assert (ba.traffic.split_programs, ba.traffic.tiles_split) == (2, 16)
    assert ba.traffic.tile_moves == ba.traffic.bytes_moved == 0
    ba.traffic.reset()
    assert (ba.traffic.split_programs, ba.traffic.tiles_split) == (0, 0)


@pytest.mark.parametrize("executor", ["sequential", "staged", "sim"])
def test_runtime_stats_count_from_array_and_scatter(executor):
    a = _data((16, 16))
    with TaskRuntime(executor=executor) as rt:
        A = rt.from_array(a, (4, 4))
        assert (rt.stats().split_programs, rt.stats().tiles_split) == (1, 16)
        A.scatter(a + 1)
        s = rt.stats()
        assert (s.split_programs, s.tiles_split) == (2, 32)
        rt.zeros((16, 16), (4, 4))          # one shared tile: no split
        assert rt.stats().split_programs == 2
    _assert_tiles_are_slices(A, a + 1)


def test_repeated_scatter_traces_once():
    shape, block = (24, 40), (8, 8)            # a shape no other test uses
    ba = BlockArray.zeros(shape, block)
    before = _split_tiles._cache_size()
    ba.scatter(_data(shape))
    assert _split_tiles._cache_size() == before + 1
    for seed in range(3):
        ba.scatter(_data(shape, seed))
    BlockArray.from_array(_data(shape), block)
    assert _split_tiles._cache_size() == before + 1


def test_input_is_not_donated():
    a = jnp.asarray(_data((8, 8)))
    ba = BlockArray.zeros((8, 8), (4, 4))
    ba.scatter(a)
    ba.scatter(a)
    assert not a.is_deleted()
    np.testing.assert_array_equal(np.asarray(ba.gather()), np.asarray(a))


def test_host_store_set_many_assigns_each():
    store = HostTileStore()
    store.set_many({(0,): 1, (1,): 2})
    assert {i: store.get(i) for i in store.indices()} == {(0,): 1, (1,): 2}


def test_tiles_follow_input_commitment():
    """Uncommitted in, uncommitted tiles (free to move); committed in,
    tiles on the input's device, as eager slices would be."""
    ba = BlockArray.from_array(_data((8, 8)), (4, 4))
    assert ba.tile_device((0, 0)) is None
    dev = jax.devices()[0]
    ba.scatter(jax.device_put(_data((8, 8)), dev))
    assert all(ba.tile_device(i) == dev for i in ba.block_indices())


def test_two_device_scatter_homes_tiles_without_charge():
    """On a forced-host 2-device mesh: ``scatter`` leaves every tile on
    its home device and charges no traffic for an uncommitted input; an
    input committed to device 0 is charged exactly one move per tile
    homed on device 1, as per-tile ``set`` would charge it."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, "src")
import jax, numpy as np
from repro import dist
from repro.core import TaskRuntime
from repro.core.blocks import DeviceTileStore
from repro.core.placement import device_assignment

assert jax.device_count() == 2
mesh = jax.sharding.Mesh(np.asarray(jax.devices()).reshape(2), ("data",))
rng = np.random.default_rng(0)
a = rng.standard_normal((64, 64), dtype=np.float32)
b = rng.standard_normal((64, 64), dtype=np.float32)
with dist.use_mesh(mesh) as ctx:
    rt = TaskRuntime(executor="sharded", placement="striped",
                     n_controllers=2)
    A = rt.from_array(a, (16, 16), name="A")
    devmap = device_assignment(2, ctx)
    assert isinstance(A.store, DeviceTileStore)

    def homed():
        for idx in A.block_indices():
            assert A.tile_device(idx) == devmap[A.home[idx] % 2], idx

    homed()
    moves0, moved0 = rt.traffic.tile_moves, rt.traffic.bytes_moved
    assert (moves0, moved0) == (0, 0)
    A.scatter(b)
    homed()
    s = rt.stats()
    assert (s.tile_moves, s.bytes_moved) == (0, 0), (s.tile_moves,
                                                     s.bytes_moved)
    assert (s.split_programs, s.tiles_split) == (2, 32)
    np.testing.assert_array_equal(np.asarray(A.gather(jax.devices()[0])),
                                  b)
    # committed to device 0: the tiles homed on device 1 each move once
    # (counted from here: the gather above moved tiles too)
    moves0, moved0 = rt.traffic.tile_moves, rt.traffic.bytes_moved
    A.scatter(jax.device_put(a, jax.devices()[0]))
    homed()
    off = sum(devmap[A.home[i] % 2] != jax.devices()[0]
              for i in A.block_indices())
    assert off > 0
    s = rt.stats()
    assert s.tile_moves - moves0 == off, (s.tile_moves - moves0, off)
    assert s.bytes_moved - moved0 == off * A.tile_nbytes
    assert s.bytes_staged == 0
print("SCATTER-2DEV-OK")
"""
    out = subprocess.run([sys.executable, "-c", code],
                         cwd=pathlib.Path(__file__).resolve().parent.parent,
                         capture_output=True, text=True, timeout=300)
    assert "SCATTER-2DEV-OK" in out.stdout, out.stderr[-2000:]
