"""Home-aware mesh execution: the ShardedExecutor.

The paper's central performance lesson is that memory locality dominates on
non cache-coherent machines: BDDT-SCC stripes application data across the
SCC's four memory controllers and keeps tasks near the controller serving
their blocks (§4.1-§4.2).  On a device mesh the same policy is
*owner-computes*: every block already has a home (``placement.assign_homes``),
:func:`~repro.core.placement.device_assignment` maps homes block-cyclically
onto the mesh's devices, and each task executes on the home device of its
*output* footprint.  Reads of blocks homed elsewhere are cross-home
transfers — the mesh analogue of the remote-controller accesses the DES
(``sim.py``) charges contention for — and this executor records them in
``RuntimeStats`` (``cross_home_bytes`` / ``local_home_bytes``) so the
benchmark tables can show what a placement policy saves.

Residency: blocks are *device-resident*.  :meth:`ShardedExecutor.make_store`
hands every registered ``BlockArray`` a
:class:`~repro.core.blocks.DeviceTileStore`, so each tile physically lives
on the device serving its home.  A dispatch assembles its operands *on the
owner device* (``Region.materialize(device=...)``): tiles a task owns never
move, a cross-home read transfers exactly once, and nothing routes through
a staging device — ``RuntimeStats.bytes_staged`` stays zero, and
``tile_moves``/``bytes_moved`` report the transfers that actually happened
(measured at the memory layer by :class:`~repro.core.blocks.TileTraffic`,
not estimated from footprints); without owner overrides they equal
``cross_home_bytes``.  Results commit tile-by-tile to the output's home:
a no-op when owner-computes held.

Dispatch reuses the staged executor's wavefront grouping and its group
program unchanged: tasks of one wavefront with the same function and
footprint/value structure form a group.  With a mesh context active
(:func:`repro.dist.use_mesh`) the group splits by owner device, and each
device's share runs as one group program pinned there
(:meth:`~repro.core.executor.StagedExecutor._group_call` with a
``device``), whatever the wave's width or its owners' balance.  With no
mesh at all every dispatch degrades to the plain staged path on the
default device — the single-device fallback tests and CI run.

When ``RuntimeConfig.owner_skew_threshold`` is set, a wave group whose
owner loads are badly skewed is rebalanced before dispatch
(:func:`~repro.core.placement.rebalance_owners`): surplus tasks of the
hottest home spill to the least-loaded one, and the spilled task's output
transfer home is charged for real by the device store — contention traded
against one counted copy, the override the paper's Fig 4 numbers argue for.
"""
from __future__ import annotations

from collections import defaultdict

import jax.numpy as jnp
import numpy as np

from repro.obs.profiler import trace_span

from .blocks import DeviceTileStore
from .executor import StagedExecutor
from .graph import TaskDescriptor
from .placement import device_assignment, rebalance_owners

__all__ = ["ShardedExecutor", "owner_home"]


def owner_home(td: TaskDescriptor) -> int:
    """Owner-computes: a task belongs to the home of its first output
    block (the paper's locality-aware scheduling keyed on where the task's
    result lives, not where its inputs came from)."""
    for m in td.args:
        if m.WRITES:
            return m.region.array.home.get(m.region.tile_indices[0], 0)
    return 0


class ShardedExecutor(StagedExecutor):
    """Staged wavefronts, placed home-aware on the ambient device mesh."""

    kind = "sharded"

    def __init__(self, graph, scheduler, group: bool = True,
                 n_homes: int = 4, owner_skew_threshold: float = 0.0,
                 kernel_backend: str = "xla"):
        super().__init__(graph, scheduler, group=group,
                         kernel_backend=kernel_backend)
        self.n_homes = n_homes
        self.owner_skew_threshold = owner_skew_threshold
        self.sharded_dispatches = 0    # group programs under a mesh
        self.cross_home_bytes = 0
        self.local_home_bytes = 0
        self.owner_overrides = 0

    # -- placement ----------------------------------------------------------
    def _mesh_ctx(self):
        from repro import dist
        return dist.current()

    def make_store(self, ba):
        """The runtime's residency hook: with a mesh active, give ``ba`` a
        device-resident store so its tiles live on their home devices from
        allocation onward (``from_array``/``zeros``/``full`` place each
        tile per ``device_assignment``).  Without a mesh the host store
        stays — the single-device fallback."""
        ctx = self._mesh_ctx()
        if ctx is None:
            return None
        return DeviceTileStore(ba, device_assignment(self.n_homes, ctx),
                               traffic=ba.traffic)

    def _account(self, td: TaskDescriptor, owner: int) -> None:
        """Charge every footprint block against the owner home: blocks
        homed elsewhere are cross-home traffic (what ``sim.py`` turns into
        controller contention), blocks at the owner are local.  The counts
        are policy-level — what owner-computes *must* move — independent
        of how many physical devices back the homes, so the single-device
        fallback reports the same numbers a real mesh would.  (The
        *measured* movement lives in the runtime's ``TileTraffic``.)"""
        for m in td.args:
            arr = m.region.array
            block_bytes = (int(np.prod(arr.block_shape))
                           * jnp.dtype(arr.dtype).itemsize)
            for idx in m.region.tile_indices:
                if arr.home.get(idx, 0) != owner:
                    self.cross_home_bytes += block_bytes
                else:
                    self.local_home_bytes += block_bytes

    def _owners(self, group: list[TaskDescriptor]) -> list[int]:
        owners = [owner_home(td) for td in group]
        if self.owner_skew_threshold > 0:
            base = None
            if self.obs.enabled:
                # the tracker's live per-home queue depth: work of this
                # wave still queued behind each home ("queued, not yet
                # dispatched" — this group was dequeued before placement,
                # so it is not double-counted)
                depths = self.obs.queue_depths()
                base = [max(0, depths.get(h, 0))
                        for h in range(self.n_homes)]
            owners, spilled = rebalance_owners(
                owners, self.n_homes, self.owner_skew_threshold,
                base_load=base)
            self.owner_overrides += spilled
            if spilled and self.obs.enabled:
                self.obs.emit("owner_override", wave=self._wave_id,
                              spilled=spilled)
        return owners

    # -- queue accounting (per owner-home channel) ----------------------------
    def _home_counts(self, tds: list[TaskDescriptor]):
        counts: dict[int, int] = defaultdict(int)
        for td in tds:
            counts[owner_home(td) % self.n_homes] += 1
        return counts

    def _enqueue_wave(self, wave: list[TaskDescriptor]) -> None:
        for home, n in sorted(self._home_counts(wave).items()):
            self.obs.queue(home, n)

    def _dequeue_group(self, group: list[TaskDescriptor]) -> None:
        # keyed on the raw owner home (pre-rebalance), matching enqueue
        for home, n in sorted(self._home_counts(group).items()):
            self.obs.queue(home, -n)

    # -- dispatch -----------------------------------------------------------
    def _place(self, group: list[TaskDescriptor]) -> list[int]:
        """Owner homes of ``group``, with every footprint charged."""
        owners = self._owners(group)
        for td, h in zip(group, owners):
            self._account(td, h)
        return owners

    def _run_group(self, group: list[TaskDescriptor]) -> None:
        ctx = self._mesh_ctx()
        if ctx is None:
            # single-device fallback: identical to the staged executor
            # (including its pallas wave-kernel attempt when
            # kernel_backend="pallas" — how the CPU matrix exercises it),
            # so the placement takes a stack span of its own
            with trace_span(self._stack_span, self.profile):
                self._place(group)
            return super()._run_group(group)
        if self.kernel_backend == "pallas":
            # under a live mesh the group runs as one group program per
            # owner device; a fused pallas grid would pin the whole wave
            # to one device and undo owner-computes, so the mesh path is
            # a named fallback, not a lowering attempt
            self._note_kernel_fallback(group, "sharded_mesh")
        with trace_span(self._stack_span, self.profile):
            calls = self._mesh_calls(group, self._place(group), ctx)
        self._dispatch(calls)

    def _mesh_calls(self, group: list[TaskDescriptor], owners: list[int],
                    ctx) -> list[tuple]:
        """Assemble the group's operands on the mesh: one ``(fn, operands,
        store)`` per dispatch (the staged contract of ``_calls``), each on
        its tasks' owner device."""
        devmap = device_assignment(self.n_homes, ctx)
        if not self.group:
            jfn = self._jitted(group[0].fn)
            return [self._task_call(td, jfn, device=devmap[h % len(devmap)])
                    for td, h in zip(group, owners)]
        by_dev = defaultdict(list)
        for td, h in zip(group, owners):
            by_dev[devmap[h % len(devmap)]].append(td)
        return [self._subgroup_call(sub, dev) for dev, sub in by_dev.items()]

    def _subgroup_call(self, group: list[TaskDescriptor], dev) -> tuple:
        """The group program pinned to one owner device (computation
        follows the placed operands)."""
        if len(group) == 1:
            return self._task_call(group[0], self._jitted(group[0].fn),
                                   device=dev)
        self._last_mode = "vmap_device"
        self.sharded_dispatches += 1
        return self._group_call(group, device=dev)
