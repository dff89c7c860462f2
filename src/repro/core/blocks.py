"""Block-structured arrays: the BDDT custom allocator, in JAX.

BDDT-SCC splits all application memory into fixed-size *blocks* via a custom
allocator; blocks are the unit of dependence analysis and of placement across
the SCC's four memory controllers.  Here an array registered with the runtime
becomes a :class:`BlockArray` — a grid of tiles.  Tiles are the dependence
unit (``deps.py``), the scheduling-affinity unit (``scheduler.py``) and the
placement unit (``placement.py``: tile -> "memory controller" / mesh device).

Residency (§3.2/§5): tiles are held behind a :class:`TileStore` backend.
The default :class:`HostTileStore` keeps plain uncommitted ``jnp`` arrays —
the single-machine path.  :class:`DeviceTileStore` makes block *homes*
physical: every tile is committed to the device serving its home
(``placement.device_assignment``), writes re-commit to the home, and reads
that cross devices are *actual* transfers — counted in the array's attached
:class:`TileTraffic` so executors can report measured (not estimated)
cross-home movement.  Assembly (``gather`` / ``Region.materialize``) is
destination-aware: tiles are pulled directly onto the device that consumes
them, never staged through an intermediate device — the paper's
"avoid large core-to-core data transfers" rule applied to the mesh.
"""
from __future__ import annotations

import functools
import itertools
from dataclasses import dataclass, field
from typing import Any, Callable, Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "BlockArray",
    "FootprintSpec",
    "Region",
    "In",
    "Out",
    "InOut",
    "AccessMode",
    "ACCESS_MODES",
    "MODE_CLASSES",
    "coerce_mode",
    "TileTraffic",
    "TileStore",
    "HostTileStore",
    "DeviceTileStore",
    "device_of",
]


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def device_of(x):
    """The single device a *committed* jax array lives on, else None.

    Uncommitted arrays (eager results on a single-device platform) have no
    residency obligation — moving them is free in the residency model, so
    they report None and are never charged as transfers."""
    if not isinstance(x, jax.Array):
        return None
    if x.committed:
        devs = x.devices()
        if len(devs) == 1:
            return next(iter(devs))
    return None


@dataclass
class TileTraffic:
    """Measured tile movement, charged at the memory layer where transfers
    actually happen (executors read these into ``RuntimeStats``).

    * ``tile_moves`` / ``bytes_moved`` — cross-device tile transfers with a
      known destination (a consuming device or the tile's home).
    * ``bytes_staged`` — bytes harmonized onto a device *nobody declared*:
      the legacy mixed-device assembly that routes data through an
      intermediate hop.  The device-resident executors keep this at zero;
      a nonzero value means some path still stages.
    * ``bytes_local`` — reads served in place on the requesting device.
    * ``split_programs`` / ``tiles_split`` — whole arrays cut into their
      tiles by one device program (``scatter``, ``from_array``), and the
      tiles those programs produced.
    """
    tile_moves: int = 0
    bytes_moved: int = 0
    bytes_staged: int = 0
    bytes_local: int = 0
    split_programs: int = 0
    tiles_split: int = 0

    def reset(self) -> None:
        self.tile_moves = self.bytes_moved = 0
        self.bytes_staged = self.bytes_local = 0
        self.split_programs = self.tiles_split = 0


def _majority_device(tiles: list):
    """The committed device holding the most of ``tiles`` (deterministic
    tie-break on device id), or None if nothing is committed."""
    counts: dict = {}
    for t in tiles:
        d = device_of(t)
        if d is not None:
            counts[d] = counts.get(d, 0) + 1
    if not counts:
        return None
    return max(sorted(counts, key=lambda d: d.id),
               key=lambda d: counts[d])


def _pull_tiles(tiles: list, device, traffic: TileTraffic | None,
                tile_nbytes: int, staged: bool = False) -> list:
    """Bring every tile to ``device`` (None = the majority device, chosen
    only when tiles are committed to *different* devices), charging the
    attached traffic recorder.  One hop per off-destination tile — assembly
    happens ON the destination, never via an intermediate device."""
    if device is None:
        devs = {device_of(t) for t in tiles} - {None}
        if len(devs) <= 1:
            return tiles                  # nothing to harmonize
        device = _majority_device(tiles)
    else:
        staged = False                    # a declared destination is final
    out = []
    for t in tiles:
        src = device_of(t)
        if src == device:
            if traffic is not None:
                traffic.bytes_local += tile_nbytes
            out.append(t)
            continue
        if src is not None and traffic is not None:
            traffic.tile_moves += 1
            traffic.bytes_moved += tile_nbytes
            if staged:
                traffic.bytes_staged += tile_nbytes
        out.append(jax.device_put(t, device))
    return out


@functools.partial(jax.jit, static_argnames="block_shape")
def _split_tiles(arr, block_shape: tuple[int, ...]) -> tuple:
    """Every tile of ``arr`` in ``BlockArray.block_indices`` order, cut by
    one device program: static slices, so each tile is one copy and the
    whole split reads and writes the array once.  Cached by JAX on shape,
    dtype and ``block_shape``; the input is never donated, since callers
    reload the same array again and again."""
    grid = [s // b for s, b in zip(arr.shape, block_shape)]
    return tuple(
        arr[tuple(slice(i * b, (i + 1) * b)
                  for i, b in zip(idx, block_shape))]
        for idx in itertools.product(*map(range, grid)))


# ---------------------------------------------------------------------------
# tile storage backends
class TileStore:
    """Where a :class:`BlockArray`'s tiles physically live.

    The base class is the host backend: a dict of plain (uncommitted) jnp
    arrays, no residency obligations, no traffic accounting — exactly the
    single-machine behavior every non-mesh executor wants.
    """

    traffic: TileTraffic | None = None

    def __init__(self):
        self._tiles: dict[tuple[int, ...], Any] = {}

    def get(self, idx: tuple[int, ...]):
        return self._tiles[idx]

    def set(self, idx: tuple[int, ...], value) -> None:
        self._tiles[idx] = value

    def set_many(self, items: dict) -> None:
        """Commit many tiles at once (``{idx: value}``), charged exactly as
        one ``set`` per tile."""
        for idx, value in items.items():
            self.set(idx, value)

    def device_for(self, idx: tuple[int, ...]):
        """The residency target of tile ``idx`` (None = host/uncommitted)."""
        return None

    def indices(self):
        return self._tiles.keys()


class HostTileStore(TileStore):
    """Alias backend for readability: tiles as uncommitted host arrays."""


class DeviceTileStore(TileStore):
    """Device-resident tiles: every tile is committed to the device serving
    its home (``devmap[home % ndev]``, from ``placement.device_assignment``).

    Writes re-commit to the home device — a value produced elsewhere is one
    direct transfer home (counted in ``traffic``); a value produced on the
    home (owner-computes) commits in place.  This is what makes block homes
    *real*: a multi-device wave reads each tile where it lives instead of
    shipping everything through a staging device.
    """

    def __init__(self, array: "BlockArray", devmap: Sequence,
                 traffic: TileTraffic | None = None):
        super().__init__()
        self.array = array
        self.devmap = list(devmap)
        self.traffic = traffic

    def device_for(self, idx: tuple[int, ...]):
        home = self.array.home.get(idx, 0)
        return self.devmap[home % len(self.devmap)]

    def _charge(self, value, dest) -> None:
        src = device_of(value)
        if src is not None and src != dest and self.traffic is not None:
            self.traffic.tile_moves += 1
            self.traffic.bytes_moved += self.array.tile_nbytes

    def set(self, idx: tuple[int, ...], value) -> None:
        dest = self.device_for(idx)
        self._charge(value, dest)
        self._tiles[idx] = jax.device_put(value, dest)

    def set_many(self, items: dict) -> None:
        """One batched ``device_put`` homes every tile, in place of one
        call per tile; each tile is charged as ``set`` would charge it."""
        dests = [self.device_for(idx) for idx in items]
        for value, dest in zip(items.values(), dests):
            self._charge(value, dest)
        self._tiles.update(zip(items, jax.device_put(list(items.values()),
                                                     dests)))


# ---------------------------------------------------------------------------
class BlockArray:
    """An N-D array stored as a grid of tiles (BDDT "blocks").

    Tiles are held behind a :class:`TileStore` so that tasks touch only the
    blocks in their declared footprint — the software analogue of the SCC's
    block allocator, where a task's footprint names exactly the DRAM blocks
    it may access.  Swapping the store (``use_store``) changes *where* the
    tiles physically live without changing any program.
    """

    _next_id = itertools.count()

    def __init__(self, shape: Sequence[int], block_shape: Sequence[int],
                 dtype=jnp.float32, name: str | None = None):
        if len(shape) != len(block_shape):
            raise ValueError("shape and block_shape rank mismatch")
        for s, b in zip(shape, block_shape):
            if s % b != 0:
                raise ValueError(
                    f"shape {tuple(shape)} not divisible by block_shape "
                    f"{tuple(block_shape)}; pad the array first (the paper's "
                    "allocator likewise pads to block multiples)")
        self.shape = tuple(int(s) for s in shape)
        self.block_shape = tuple(int(b) for b in block_shape)
        self.dtype = dtype
        self.grid = tuple(s // b for s, b in zip(self.shape, self.block_shape))
        self.array_id = next(BlockArray._next_id)
        self.name = name or f"arr{self.array_id}"
        self._store: TileStore = HostTileStore()
        # tile index tuple -> home id (memory controller / device ordinal)
        self.home: dict[tuple[int, ...], int] = {}
        # measured tile movement; the owning runtime attaches its recorder
        self.traffic: TileTraffic | None = None

    @property
    def tile_nbytes(self) -> int:
        return int(np.prod(self.block_shape)) * jnp.dtype(self.dtype).itemsize

    # -- storage backend ---------------------------------------------------
    @property
    def store(self) -> TileStore:
        return self._store

    def use_store(self, store: TileStore) -> None:
        """Swap the storage backend, migrating existing tiles.  Initial
        placement is *not* charged as traffic — tiles are being homed, not
        moved between consumers."""
        old, self._store = self._store, store
        saved, store.traffic = store.traffic, None
        try:
            for idx in list(old.indices()):
                store.set(idx, old.get(idx))
        finally:
            store.traffic = saved

    def tile_device(self, idx: tuple[int, ...]):
        """The device the stored tile is actually committed to (None for
        host/uncommitted tiles)."""
        return device_of(self._store.get(idx))

    # -- construction -----------------------------------------------------
    @classmethod
    def from_array(cls, arr, block_shape: Sequence[int],
                   name: str | None = None,
                   traffic: TileTraffic | None = None) -> "BlockArray":
        """Tile ``arr`` (one split program, see ``scatter``); ``traffic``
        is the recorder the split is counted in, if any."""
        arr = jnp.asarray(arr)
        ba = cls(arr.shape, block_shape, arr.dtype, name=name)
        ba.traffic = traffic
        ba.scatter(arr)
        return ba

    @classmethod
    def full(cls, shape, block_shape, fill, dtype=jnp.float32,
             name: str | None = None) -> "BlockArray":
        ba = cls(shape, block_shape, dtype, name=name)
        tile = jnp.full(ba.block_shape, fill, dtype)
        for idx in ba.block_indices():
            ba._store.set(idx, tile)
        return ba

    @classmethod
    def zeros(cls, shape, block_shape, dtype=jnp.float32,
              name: str | None = None) -> "BlockArray":
        return cls.full(shape, block_shape, 0, dtype, name=name)

    # -- indexing ----------------------------------------------------------
    def block_indices(self) -> Iterator[tuple[int, ...]]:
        return itertools.product(*[range(g) for g in self.grid])

    def __getitem__(self, key) -> "Region":
        """``A[i, j]`` (one tile) or ``A[i0:i1, j]`` (tile range) -> Region.

        Indices are in *block* coordinates, exactly as OmpSs task footprints
        name array tiles.
        """
        if not isinstance(key, tuple):
            key = (key,)
        if len(key) != len(self.grid):
            raise IndexError(f"{self.name}: need {len(self.grid)} block "
                             f"indices, got {len(key)}")
        ranges = []
        for k, g in zip(key, self.grid):
            if isinstance(k, slice):
                start, stop, step = k.indices(g)
                if step != 1:
                    raise IndexError("block slices must be unit-stride")
                ranges.append(range(start, stop))
            else:
                k = int(k)
                if k < 0:
                    k += g
                if not 0 <= k < g:
                    raise IndexError(f"block index {k} out of range {g}")
                ranges.append(range(k, k + 1))
        return Region(self, tuple(ranges))

    @property
    def whole(self) -> "Region":
        return Region(self, tuple(range(g) for g in self.grid))

    # -- tile data access (used by the executors) ---------------------------
    def get_tile(self, idx: tuple[int, ...]):
        return self._store.get(idx)

    def set_tile(self, idx: tuple[int, ...], value) -> None:
        if tuple(value.shape) != self.block_shape:
            raise ValueError(
                f"{self.name}{list(idx)}: tile shape {tuple(value.shape)} != "
                f"block shape {self.block_shape}")
        self._store.set(idx, value)

    def gather(self, device=None):
        """Assemble the full array from tiles (the read-back at a barrier).

        Mixed-device tiles are assembled *on the destination* — ``device``
        if given, else the device already holding the most tiles — so each
        off-destination tile moves exactly once (no staging hop through an
        intermediate device)."""
        idxs = list(self.block_indices())
        tiles = _pull_tiles([self._store.get(idx) for idx in idxs], device,
                            self.traffic, self.tile_nbytes)
        nested = np.empty(self.grid, dtype=object)
        for idx, tile in zip(idxs, tiles):
            nested[idx] = tile
        if len(self.grid) == 1:
            return jnp.concatenate(list(nested), axis=0)
        return jnp.block(nested.tolist())

    def scatter(self, arr) -> None:
        """Overwrite all tiles from a full array: one split program cuts
        every tile, one ``set_many`` commits them.  The tiles are as
        committed as ``arr`` is, as per-tile slices would be."""
        arr = jnp.asarray(arr)
        if arr.shape != self.shape:
            raise ValueError("scatter shape mismatch")
        tiles = _split_tiles(arr, self.block_shape)
        self._store.set_many(dict(zip(self.block_indices(), tiles)))
        if self.traffic is not None:
            self.traffic.split_programs += 1
            self.traffic.tiles_split += len(tiles)

    def __repr__(self):
        return (f"BlockArray({self.name}, shape={self.shape}, "
                f"blocks={self.grid}x{self.block_shape}, dtype={self.dtype})")


@dataclass(frozen=True)
class FootprintSpec:
    """The static per-task tile view a wave kernel's ``BlockSpec`` is built
    from: element ``shape`` (the region's assembled extent), canonical
    ``dtype`` string, and the tile grid the region spans.  Produced by
    :meth:`Region.footprint_spec`; consumed by ``core/wavekernel.py`` for
    eligibility (rank/dtype homogeneity) and for sizing the per-task
    blocks of the fused pallas grid."""
    shape: tuple[int, ...]
    dtype: str
    tile_grid: tuple[int, ...]

    @property
    def rank(self) -> int:
        return len(self.shape)

    @property
    def n_tiles(self) -> int:
        return int(np.prod(self.tile_grid)) if self.tile_grid else 1


@dataclass(frozen=True)
class Region:
    """A rectangular range of tiles of one BlockArray — a task footprint item."""
    array: BlockArray
    ranges: tuple[range, ...]

    @property
    def block_ids(self) -> tuple[tuple[int, tuple[int, ...]], ...]:
        """Globally unique block ids: (array_id, tile index)."""
        return tuple((self.array.array_id, idx)
                     for idx in itertools.product(*self.ranges))

    @property
    def tile_indices(self) -> list[tuple[int, ...]]:
        return list(itertools.product(*self.ranges))

    @property
    def shape(self) -> tuple[int, ...]:
        return tuple(len(r) * b
                     for r, b in zip(self.ranges, self.array.block_shape))

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.shape)) * jnp.dtype(self.array.dtype).itemsize

    def footprint_spec(self) -> FootprintSpec:
        """The static tile-view description handed to wave-kernel
        ``BlockSpec`` construction (regions are rectangular tile ranges by
        construction, so shape/grid are exact, never bounding boxes)."""
        return FootprintSpec(self.shape, str(jnp.dtype(self.array.dtype)),
                             tuple(len(r) for r in self.ranges))

    def materialize(self, device=None):
        """Assemble this region's tiles into one array (task input value).

        ``device`` names the consuming device: tiles homed there are read
        in place, every other tile is pulled directly onto it (one hop,
        counted as a measured transfer).  Without a destination,
        mixed-device tiles harmonize onto the majority device and the
        moved bytes are charged as *staged* — the legacy double-hop the
        device-resident executors avoid by always naming the consumer."""
        idxs = self.tile_indices
        traffic = self.array.traffic
        nbytes = self.array.tile_nbytes
        if len(idxs) == 1:
            [tile] = _pull_tiles([self.array.get_tile(idxs[0])], device,
                                 traffic, nbytes, staged=True)
            return tile
        tiles = _pull_tiles([self.array.get_tile(i) for i in idxs], device,
                            traffic, nbytes, staged=True)
        grid = tuple(len(r) for r in self.ranges)
        nested = np.empty(grid, dtype=object)
        # tile_indices and the position product enumerate in the same
        # (row-major) order, so the flat tile list zips positionally
        for pos, tile in zip(itertools.product(*[range(g) for g in grid]),
                             tiles):
            nested[pos] = tile
        if len(grid) == 1:
            return jnp.concatenate(list(nested), axis=0)
        return jnp.block(nested.tolist())

    def store(self, value) -> None:
        """Split a produced value back into this region's tiles (task output).
        Each tile commits wherever the array's store homes it — for a
        device-resident store, tile-by-tile to the home device."""
        idxs = self.tile_indices
        if len(idxs) == 1:
            self.array.set_tile(idxs[0], value)
            return
        if tuple(value.shape) != self.shape:
            raise ValueError(f"store shape {tuple(value.shape)} != region "
                             f"shape {self.shape}")
        bs = self.array.block_shape
        for pos in itertools.product(*[range(len(r)) for r in self.ranges]):
            src = tuple(r[p] for r, p in zip(self.ranges, pos))
            sl = tuple(slice(p * b, (p + 1) * b) for p, b in zip(pos, bs))
            self.array.set_tile(src, value[sl])

    def __repr__(self):
        rs = ",".join(f"{r.start}:{r.stop}" if len(r) > 1 else str(r.start)
                      for r in self.ranges)
        return f"{self.array.name}[{rs}]"


class AccessMode:
    """OmpSs data-access attribute on a task argument (§3.1).

    The three concrete modes are reachable as enum-style members —
    ``AccessMode.IN`` / ``AccessMode.OUT`` / ``AccessMode.INOUT`` — and
    every API that takes a mode (``wait_on``, ``tasks_touching``, the
    ``@task(footprint=...)`` mapping form) accepts either a member or
    its plain-string spelling via :func:`coerce_mode`.
    """
    READS = False
    WRITES = False
    MODE = ""          # canonical string spelling, set on subclasses
    # enum-style member aliases, bound after the subclasses below
    IN: "type[AccessMode]"
    OUT: "type[AccessMode]"
    INOUT: "type[AccessMode]"

    def __init__(self, region: Region):
        if not isinstance(region, Region):
            raise TypeError(f"expected a Region (e.g. A[i, j]), got "
                            f"{type(region).__name__}")
        self.region = region

    def __repr__(self):
        return f"{type(self).__name__}({self.region!r})"


class In(AccessMode):
    READS = True
    MODE = "in"


class Out(AccessMode):
    WRITES = True
    MODE = "out"


class InOut(AccessMode):
    READS = True
    WRITES = True
    MODE = "inout"


AccessMode.IN = In
AccessMode.OUT = Out
AccessMode.INOUT = InOut

#: canonical mode spellings, and the class each one names
ACCESS_MODES = ("in", "out", "inout")
MODE_CLASSES: dict[str, type[AccessMode]] = {
    "in": In, "out": Out, "inout": InOut}


def coerce_mode(mode) -> str:
    """Normalize an access-mode spelling to ``"in"``/``"out"``/``"inout"``.

    Accepts the plain strings, the :class:`AccessMode` members
    (``AccessMode.IN`` — i.e. the ``In``/``Out``/``InOut`` classes), or
    an ``AccessMode`` instance; one helper so every mode-taking API
    raises the same ``ValueError`` listing the valid choices.
    """
    if isinstance(mode, type) and issubclass(mode, AccessMode):
        mode = mode.MODE
    elif isinstance(mode, AccessMode):
        mode = mode.MODE
    if mode not in MODE_CLASSES:
        raise ValueError(
            f"mode must be one of {ACCESS_MODES} (or AccessMode.IN/"
            f"OUT/INOUT), got {mode!r}")
    return mode
