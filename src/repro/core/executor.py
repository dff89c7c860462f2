"""Executors: how a discovered task graph actually runs.

* :class:`SequentialExecutor` — serial elision; the oracle for tests.
* :class:`HostExecutor` — the paper-faithful dynamic runtime: the host
  thread is the SCC master, worker threads drain MPB descriptor rings and
  execute jitted tile tasks.  Reproduces the paper's protocol including
  bounded slots, master-never-blocks spawns, lazy collection and release.
* :class:`StagedExecutor` — the TPU-idiomatic adaptation: the DAG is
  layered into wavefronts and each wavefront's identical tile tasks are
  fused into one batched (``vmap``-ed, jitted) dispatch.  On an SPMD
  machine there is no dynamic master->worker dispatch at run time, so the
  descriptor traffic of the paper is staged into the compiled program —
  the dependence analysis is unchanged, only the dispatch is ahead-of-time.
* :class:`repro.core.sharded.ShardedExecutor` — the staged wavefronts
  placed home-aware on a device mesh (owner-computes over
  ``BlockArray.home``); lives in its own module to keep mesh plumbing out
  of the single-machine path.
"""
from __future__ import annotations

import contextlib
import functools
import threading
import time
from collections import OrderedDict, defaultdict
from typing import Callable, Iterable, Protocol, Sequence, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.profiler import trace_span
from repro.obs.tracker import NULL_TRACKER

from . import wavekernel
from .api import suspend_runtime_scope
from .graph import TaskDescriptor, TaskGraph, TaskState, normalize_outputs
from .mpb import MPBQueue
from .scheduler import MasterScheduler

__all__ = ["Executor", "ExecutorBase", "SequentialExecutor", "HostExecutor",
           "StagedExecutor", "dependence_cone"]

_NO_SPAN = contextlib.nullcontext()


@runtime_checkable
class Executor(Protocol):
    """What the runtime front-end requires of an execution strategy.

    Implementations: :class:`SequentialExecutor` (serial elision),
    :class:`HostExecutor` (the paper's dynamic master/worker protocol),
    :class:`StagedExecutor` (wavefront batching for SPMD hardware),
    :class:`repro.core.sharded.ShardedExecutor` (home-aware wavefronts on
    a device mesh) and :class:`repro.core.sim.SimExecutor` (timing-only
    discrete-event prediction on the SCC cost model).
    """

    def on_spawn(self, td: TaskDescriptor, ready: bool) -> None:
        """A task was initiated; ``ready`` means no unresolved deps."""
        ...

    def barrier(self) -> None:
        """Global synchronization: return once every spawned task ran."""
        ...

    def wait_for(self, tds: Sequence[TaskDescriptor]) -> None:
        """Partial synchronization: return once ``tds`` (and hence their
        dependence cones) completed — unrelated tasks need not have run."""
        ...

    def reclaim(self) -> None:
        """Make progress so a descriptor can be recycled (pool exhausted)."""
        ...

    def shutdown(self) -> None:
        ...


def dependence_cone(targets: Iterable[TaskDescriptor]) -> set[TaskDescriptor]:
    """The incomplete transitive predecessors of ``targets`` (targets
    included) — exactly what must run before a wait on them returns."""
    cone: set[TaskDescriptor] = set()
    stack = [td for td in targets if not td.is_complete]
    while stack:
        td = stack.pop()
        if td in cone:
            continue
        cone.add(td)
        stack.extend(p for p in td.preds
                     if not p.is_complete and p not in cone)
    return cone


class ExecutorBase:
    """Shared defaults for :class:`Executor` implementations.

    Observability: the runtime hands every executor the tracker it owns
    (``obs``), its traffic recorder (``traffic``) and the profiler flag
    (``profile``) right after construction — class-level defaults keep
    executors constructed standalone (tests, the DES) working with zero
    event overhead.  Hot paths guard event construction on
    ``obs.enabled``, so the default ``NULL_TRACKER`` never even builds
    an event dict.
    """

    kind = "base"                 # the ``executor`` field of emitted events
    obs = NULL_TRACKER            # set by TaskRuntime.__init__
    traffic = None                # the runtime's TileTraffic recorder
    profile = False               # RuntimeConfig.profile_waves

    def on_spawn(self, td: TaskDescriptor, ready: bool) -> None:
        raise NotImplementedError

    def barrier(self) -> None:
        raise NotImplementedError

    def wait_for(self, tds: Sequence[TaskDescriptor]) -> None:
        """Conservative default: a full barrier satisfies any wait."""
        if any(not td.is_complete for td in tds):
            self.barrier()

    def reclaim(self) -> None:
        """Make progress so a descriptor can be recycled (pool exhausted)."""
        self.barrier()

    def shutdown(self) -> None:
        pass


# ---------------------------------------------------------------------------
class SequentialExecutor(ExecutorBase):
    """Serial elision: run each task at spawn, in program order.  Program
    order is a topological order of the dependence DAG by construction, so
    every dependence is satisfied."""

    kind = "sequential"

    def __init__(self, graph: TaskGraph, scheduler: MasterScheduler):
        self.graph = graph
        self.scheduler = scheduler

    def on_spawn(self, td: TaskDescriptor, ready: bool) -> None:
        assert ready, ("sequential spawn found an unresolved dependence; "
                       "program order must satisfy all deps")
        td.state = TaskState.RUNNING
        td.run()
        self.scheduler._collect(td)
        self.scheduler.release_all()

    def barrier(self) -> None:
        assert self.graph.quiescent

    def wait_for(self, tds) -> None:
        # every task ran at its spawn; nothing can be outstanding
        assert all(td.is_complete for td in tds)


# ---------------------------------------------------------------------------
class _Worker(threading.Thread):
    """A worker core: drains its MPB ring, executes tasks, marks slots
    completed (§3.5).  Cache invalidate/flush fences around the task body
    are no-ops on coherent CPython (charged for real in the DES).

    Pinned tile cache: each worker keeps up to ``cache_tiles`` assembled
    READS operands, keyed by region identity and validated by the
    *identity* of the constituent tile objects (jax arrays are immutable
    and the store swaps in a new object on every write, so object
    identity is exact freshness; the cached entry pins its tiles, ruling
    out id reuse).  A hit skips region reassembly — the SCC analogue of
    a worker keeping hot tiles resident in its own memory slice."""

    def __init__(self, wid: int, queue: MPBQueue, cache_tiles: int = 0):
        super().__init__(name=f"bddt-worker-{wid}", daemon=True)
        self.wid = wid
        self.queue = queue
        self.stop_flag = threading.Event()
        self.busy_s = 0.0
        self.tasks_run = 0
        self.cache_tiles = cache_tiles
        self.cache_hits = 0
        self.cache_misses = 0
        # region key -> (pinned tile objects, assembled value), LRU order
        self._cache: OrderedDict = OrderedDict()

    def _materialize(self, region):
        if not self.cache_tiles:
            return region.materialize()
        key = (region.array.array_id, region.ranges)
        tiles = tuple(region.array.get_tile(i) for i in region.tile_indices)
        hit = self._cache.get(key)
        if hit is not None and len(hit[0]) == len(tiles) and \
                all(a is b for a, b in zip(hit[0], tiles)):
            self.cache_hits += 1
            self._cache.move_to_end(key)
            return hit[1]
        self.cache_misses += 1
        value = region.materialize()
        self._cache[key] = (tiles, value)
        self._cache.move_to_end(key)
        while len(self._cache) > self.cache_tiles:
            self._cache.popitem(last=False)
        return value

    def run(self) -> None:
        while not self.stop_flag.is_set():
            td = self.queue.next_ready(timeout=0.05)
            if td is None:
                continue
            td.state = TaskState.RUNNING
            t0 = time.perf_counter()
            # read fence (L2 invalidate) | task body | write fence (L2 flush)
            td.run(materialize=self._materialize)
            self.busy_s += time.perf_counter() - t0
            self.tasks_run += 1
            self.queue.mark_completed(td)


class HostExecutor(ExecutorBase):
    """The paper's runtime: master = the spawning host thread."""

    kind = "host"

    def __init__(self, graph: TaskGraph, scheduler: MasterScheduler,
                 queues: list[MPBQueue], cache_tiles: int = 0):
        self.graph = graph
        self.scheduler = scheduler
        self.queues = queues
        self._cache_reported = False
        self.workers = [_Worker(q.worker_id, q, cache_tiles=cache_tiles)
                        for q in queues]
        for w in self.workers:
            w.start()

    def on_spawn(self, td: TaskDescriptor, ready: bool) -> None:
        if ready:
            # running mode: one attempt, never block (§3.4)
            self.scheduler.schedule_running(td)
        # dependent tasks stay in the task graph until released

    def barrier(self) -> None:
        # polling mode until every spawned task has been released
        while not self.graph.quiescent:
            self.scheduler.polling_step()
            if not self.graph.quiescent:
                time.sleep(0)  # yield to worker threads

    def wait_for(self, tds) -> None:
        """Polling mode scoped to ``tds``: the master polls/schedules/
        releases until the waited-on tasks completed, then returns to the
        main program — in-flight unrelated tasks keep running on their
        workers undisturbed."""
        while not all(td.is_complete for td in tds):
            self.scheduler.polling_step()
            if not all(td.is_complete for td in tds):
                time.sleep(0)

    def pump(self) -> None:
        """One non-blocking master step: poll worker rings, release
        completed tasks, dispatch newly-ready ones.  Serving loops call
        this between arrivals so completions surface without forcing a
        dependence-cone wait."""
        self.scheduler.polling_step()

    def reclaim(self) -> None:
        # §3.3: master blocks until a task completes, freeing a descriptor
        while self.scheduler.pool.free == 0:
            self.scheduler.polling_step()
            time.sleep(0)

    def shutdown(self) -> None:
        for w in self.workers:
            w.stop_flag.set()
        for w in self.workers:
            w.join(timeout=2.0)
        if self.obs.enabled and not self._cache_reported:
            self._cache_reported = True
            for w in self.workers:
                self.obs.emit("tile_cache", worker=w.wid,
                              hits=w.cache_hits, misses=w.cache_misses)


# ---------------------------------------------------------------------------
class StagedExecutor(ExecutorBase):
    """Wavefront staging: spawn only records; the barrier layers the DAG and
    dispatches each layer as batched jitted calls.

    Grouping: tasks in one wavefront with the same function and the same
    input/output signature run as one device program per group — the TPU
    analogue of handing each worker its MPB queue of identical tile tasks.
    The group program (:meth:`_group_program`, one jit per body, traced
    per group shape) takes the group's operand tiles as they are, stacks
    them, applies ``vmap(fn)`` and hands each output back as a tuple of
    per-task values: the stack and the per-task slices run inside the one
    dispatch, not as host-dispatched operations of their own.
    Firstprivate values are stacked on the host, one array per value
    position, as extra vmap operands, so index-parameterized tile tasks
    (same function, different offsets) share the dispatch too.  The
    stacked axis is the "worker" axis; on a mesh the sharded executor
    runs one group program per owner device.
    """

    kind = "staged"

    def __init__(self, graph: TaskGraph, scheduler: MasterScheduler,
                 group: bool = True, kernel_backend: str = "xla"):
        self.graph = graph
        self.scheduler = scheduler
        self.group = group
        self.kernel_backend = kernel_backend
        self.pending: list[TaskDescriptor] = []
        self._vjit: dict[Callable, Callable] = {}
        self._jit: dict[Callable, Callable] = {}
        # compiled wave kernels, or the WaveKernelError that refused one
        # (a refusal is cached too: the same group shape never recompiles)
        self._pjit: dict[tuple, Callable | wavekernel.WaveKernelError] = {}
        self.waves_run = 0
        self.grouped_dispatches = 0
        self.group_program_tasks = 0   # tasks run through a group program
        self.group_operand_tiles = 0   # READS operands passed to them
        self.group_distinct_tiles = 0  # distinct operands, per group
        self.kernel_dispatches = 0     # groups fused into one pallas grid
        self.kernel_fallbacks = 0      # pallas-requested groups gone XLA
        self.kernel_fallback_reasons: dict[str, int] = defaultdict(int)
        self._dispatches = 0           # all dispatch events this executor
        self._wave_id = 0              # current wave (events and spans)
        self._last_mode = "jit"        # how the last group dispatched
        # profiler span labels (``profile_waves``), formatted once here
        root = f"bddt/{self.kind}"
        self._wave_span = root + "/wave"
        (self._layer_span, self._stack_span, self._call_span,
         self._store_span, self._release_span) = (
            f"{root}/{step}"
            for step in ("layer", "stack", "call", "store", "release"))

    def on_spawn(self, td: TaskDescriptor, ready: bool) -> None:
        self.pending.append(td)

    # -- wavefront layering ---------------------------------------------------
    def _wavefronts(self, tasks: list[TaskDescriptor]) \
            -> list[list[TaskDescriptor]]:
        indeg = {td: td.deps_remaining for td in tasks}
        frontier = [td for td, d in indeg.items() if d == 0]
        waves = []
        seen = 0
        while frontier:
            # canonical intra-wave order: spawn order, not discovery
            # order — the schedule must not depend on which predecessor
            # (or which dependence manager) unlocked a task first
            frontier.sort(key=lambda t: t.spawn_order)
            waves.append(frontier)
            seen += len(frontier)
            nxt: list[TaskDescriptor] = []
            for td in frontier:
                for dep in td.dependents:
                    if dep in indeg:
                        indeg[dep] -= 1
                        if indeg[dep] == 0:
                            nxt.append(dep)
            frontier = nxt
        if seen != len(tasks):
            raise RuntimeError("cycle in task graph (impossible for "
                               "footprint-derived deps)")
        return waves

    def _sig(self, td: TaskDescriptor):
        """The grouping key — shared with the wave-kernel layer and the
        DES's fused-wave predictor, so it lives in ``wavekernel.py``
        (:func:`~repro.core.wavekernel.group_signature`): tasks that
        differ only in region contents or index values share one batched
        dispatch."""
        return wavekernel.group_signature(td)

    def _jitted(self, fn: Callable) -> Callable:
        jfn = self._jit.get(fn)
        if jfn is None:
            jfn = self._jit[fn] = jax.jit(fn)
        return jfn

    @staticmethod
    def _stack_group(group: list[TaskDescriptor]) -> list:
        """Stack each READS arg across the group, then the firstprivate
        values, as eager device arrays: the operands of a fused pallas
        wave kernel, which takes them stacked."""
        ins = [jnp.stack([td.args[pos].region.materialize() for td in group])
               for pos, arg in enumerate(group[0].args) if arg.READS]
        ins.extend(jnp.stack([jnp.asarray(td.values[pos]) for td in group])
                   for pos in range(len(group[0].values)))
        return ins

    @staticmethod
    def _assign_outputs(td: TaskDescriptor, vals: tuple) -> None:
        """Commit one task's output values — the §3.5 store contract
        shared by every batched path (regions first, captured outputs
        after)."""
        for mode, value in zip(td.outputs, vals):
            mode.region.store(value)
        td.output_values = vals

    def _store_group(self, group: list[TaskDescriptor], result) -> None:
        """Commit one batched result to the group's regions and captured
        outputs, in group order: the grouped commit point.  Each output's
        entry is indexable by task — a stacked array (pallas grid), or a
        tuple of per-task values (group program), where ``[i]`` is plain
        Python indexing and no device operation."""
        result = normalize_outputs(result, len(group[0].outputs),
                                   group[0].name or group[0].tid)
        self.grouped_dispatches += 1
        for i, td in enumerate(group):
            self._assign_outputs(
                td, tuple(stacked[i] for stacked in result))

    def _group_program(self, fn: Callable) -> Callable:
        """The one device program that runs a group of ``fn`` tasks:
        ``program(reads, values)`` takes per READS position the group's
        tiles in task order and per firstprivate position the values
        stacked on the host, stacks the tiles, applies ``vmap(fn)`` and
        returns each output as a tuple of per-task values.  It carries
        ``fn``'s name, so the device trace names it ``jit_<fn>``.  The
        values enter strongly typed in their canonical dtype."""
        program = self._vjit.get(fn)
        if program is None:
            vfn = jax.vmap(fn)

            @functools.wraps(fn)
            def run(reads, values):
                out = vfn(*(jnp.stack(tiles) for tiles in reads), *values)
                return jax.tree.map(lambda x: tuple(jnp.unstack(x)), out)

            program = self._vjit[fn] = jax.jit(run)
        return program

    def _group_call(self, group: list[TaskDescriptor],
                    device=None) -> tuple:
        """The whole group as one dispatch of its group program:
        ``(program, (reads, values), store)``.  ``device`` (if given) is
        the execution destination: each tile is assembled *directly on
        it* (``Region.materialize(device=...)``), so tiles resident
        elsewhere move exactly once, and the jit follows its committed
        inputs there; the plain staged path leaves tiles where they are.
        Each firstprivate position is one host-stacked array, one
        transfer per group."""
        for td in group:
            td.state = TaskState.RUNNING
        regions = [[td.args[pos].region for td in group]
                   for pos, arg in enumerate(group[0].args) if arg.READS]
        reads = tuple(tuple(r.materialize(device=device) for r in rs)
                      for rs in regions)
        values = tuple(np.stack([td.values[pos] for td in group])
                       for pos in range(len(group[0].values)))
        if device is not None:
            values = jax.device_put(values, device)
        self.group_program_tasks += len(group)
        self.group_operand_tiles += len(group) * len(regions)
        self.group_distinct_tiles += len({(r.array.array_id, r.ranges)
                                          for rs in regions for r in rs})
        return (self._group_program(group[0].fn), (reads, values),
                functools.partial(self._store_group, group))

    def _task_call(self, td: TaskDescriptor, jfn: Callable,
                   device=None) -> tuple:
        """One task as its own dispatch: ``(jfn, operands, store)``.
        ``device`` (if given) is the execution destination: operands
        assemble directly on it, so jit, following its inputs, executes
        the body on the task's owner device and resident tiles are read
        in place."""
        td.state = TaskState.RUNNING
        if device is None:
            ins = [a.region.materialize() for a in td.args if a.READS]
            ins.extend(td.values)
        else:
            ins = [a.region.materialize(device=device)
                   for a in td.args if a.READS]
            ins.extend(jax.device_put(jnp.asarray(v), device)
                       for v in td.values)
        return jfn, ins, functools.partial(self._store_task, td)

    def _store_task(self, td: TaskDescriptor, result) -> None:
        self._assign_outputs(td, normalize_outputs(
            result, len(td.outputs), td.name or td.tid))

    def _calls(self, group: list[TaskDescriptor]) -> list[tuple]:
        """Assemble the group's operands: one ``(fn, operands, store)``
        per dispatch — the whole group through its group program, or each
        task through ``jit(fn)`` when there is nothing to batch."""
        if len(group) == 1 or not self.group:
            jfn = self._jitted(group[0].fn)
            return [self._task_call(td, jfn) for td in group]
        self._last_mode = "vmap"
        return [self._group_call(group)]

    def _dispatch(self, calls: list[tuple]) -> None:
        """Enqueue every body, then commit every result."""
        with trace_span(self._call_span, self.profile), \
                suspend_runtime_scope():  # tracing runs fn on this thread
            results = [fn(*ins) for fn, ins, _ in calls]
        with trace_span(self._store_span, self.profile):
            for (_, _, store), result in zip(calls, results):
                store(result)

    def _run_group(self, group: list[TaskDescriptor]) -> None:
        if self.kernel_backend == "pallas":
            refusal = self._try_wave_kernel(group)
            if refusal is None:
                return                 # fused pallas grid dispatched
            self._note_kernel_fallback(group, *refusal)
        with trace_span(self._stack_span, self.profile):
            calls = self._calls(group)
        self._dispatch(calls)

    # -- the pallas wave-kernel backend (kernel_backend="pallas") -------------
    def _try_wave_kernel(self, group: list[TaskDescriptor]) \
            -> tuple[str, str] | None:
        """Dispatch the group as one fused pallas grid if it qualifies.
        Returns None on success (results committed), else the fallback
        ``(reason, error)`` — the caller then takes the XLA path, which
        stays the reference oracle for everything the lowering does not
        cover.  Only building the kernel is guarded: the compiled kernel
        is called outside any ``try``, so a device fault propagates."""
        if not self.group:
            return "ungrouped", ""
        reason = wavekernel.eligibility(group)
        if reason is not None:
            return reason, ""
        td = group[0]
        label = td.name or td.fn.__name__
        for t in group:
            t.state = TaskState.RUNNING
        with trace_span(self._stack_span, self.profile):
            ins = self._stack_group(group)
        key = (td.fn, len(group),
               tuple((tuple(x.shape), str(x.dtype)) for x in ins))
        pfn = self._pjit.get(key)
        if pfn is None:
            try:
                with suspend_runtime_scope():   # tracing runs fn here
                    pfn = wavekernel.compile_wave_kernel(
                        td.fn, ins, len(td.outputs),
                        interpret=wavekernel.interpret_mode(), label=label)
            except wavekernel.WaveKernelError as e:
                pfn = e
            self._pjit[key] = pfn
        if isinstance(pfn, wavekernel.WaveKernelError):
            return pfn.reason, pfn.detail
        with trace_span(self._call_span, self.profile):
            result = pfn(*ins)
        self._last_mode = "pallas"
        self.kernel_dispatches += 1
        if self.obs.enabled:
            self.obs.emit("kernel_dispatch", wave=self._wave_id,
                          executor=self.kind, fn=label, tasks=len(group),
                          backend="pallas", reason="")
        with trace_span(self._store_span, self.profile):
            self._store_group(group, result)
        return None

    def _note_kernel_fallback(self, group: list[TaskDescriptor],
                              reason: str, error: str = "") -> None:
        """Account one pallas-requested group that takes the XLA path;
        ``error`` is the compiler's first error line for a refusal."""
        self.kernel_fallbacks += 1
        self.kernel_fallback_reasons[reason] += 1
        if self.obs.enabled:
            td = group[0]
            extra = {"error": error} if error else {}
            self.obs.emit("kernel_dispatch", wave=self._wave_id,
                          executor=self.kind,
                          fn=td.name or td.fn.__name__, tasks=len(group),
                          backend="xla", reason=reason, **extra)

    # -- wave instrumentation -------------------------------------------------
    def _traffic_snapshot(self) -> tuple[int, int, int]:
        t = self.traffic
        if t is None:
            return (0, 0, 0)
        return (t.tile_moves, t.bytes_moved, t.bytes_staged)

    def _enqueue_wave(self, wave: list[TaskDescriptor]) -> None:
        """Account a staged wave as queued work; the staged path has one
        logical dispatch channel (0).  Sharded overrides per owner home."""
        self.obs.queue(0, len(wave))

    def _dequeue_group(self, group: list[TaskDescriptor]) -> None:
        self.obs.queue(0, -len(group))

    def _run_wave_group(self, group: list[TaskDescriptor]) -> None:
        if not self.obs.enabled:
            self._run_group(group)
            return
        # dequeue before dispatch so live depth means "queued, not yet
        # dispatched" — the sharded rebalance reads it as background load
        # and must not count the group it is placing
        self._dequeue_group(group)
        self._last_mode = "jit"
        t0 = time.perf_counter()
        self._run_group(group)
        wall = time.perf_counter() - t0
        self._dispatches += 1
        td = group[0]
        self.obs.emit("dispatch", wave=self._wave_id, executor=self.kind,
                      fn=td.name or td.fn.__name__, tasks=len(group),
                      mode=self._last_mode, wall_s=wall)

    def _run_waves(self, tasks: list[TaskDescriptor]) -> None:
        """Layer ``tasks`` into waves and run them in order.  Two
        independent guards: ``profile`` opens the profiler spans (wave,
        and the layer/stack/call/store/release steps inside it), an
        enabled tracker emits the wave and dispatch events."""
        prof, obs = self.profile, self.obs.enabled
        with trace_span(self._layer_span, prof):
            waves = self._wavefronts(tasks)
        for wave in waves:
            self.waves_run += 1
            self._wave_id += 1
            wid = self._wave_id
            with (trace_span(f"{self._wave_span}{wid}") if prof
                  else _NO_SPAN):
                with trace_span(self._layer_span, prof):
                    groups: dict = defaultdict(list)
                    for td in wave:
                        groups[self._sig(td)].append(td)
                if obs:
                    self.obs.emit("wave_open", wave=wid, executor=self.kind,
                                  tasks=len(wave), groups=len(groups))
                    self._enqueue_wave(wave)
                    moves0, moved0, staged0 = self._traffic_snapshot()
                    disp0 = self._dispatches
                    t0 = time.perf_counter()
                for group in groups.values():
                    self._run_wave_group(group)
                if obs:
                    wall = time.perf_counter() - t0
                    moves1, moved1, staged1 = self._traffic_snapshot()
                    self.obs.emit("wave_close", wave=wid, executor=self.kind,
                                  tasks=len(wave), wall_s=wall,
                                  dispatches=self._dispatches - disp0,
                                  tile_moves=moves1 - moves0,
                                  bytes_moved=moved1 - moved0,
                                  bytes_staged=staged1 - staged0)
                with trace_span(self._release_span, prof):
                    for td in wave:
                        self.scheduler._collect(td)
        with trace_span(self._release_span, prof):
            self.scheduler.release_all()

    def barrier(self) -> None:
        self._run_waves(self.pending)
        self.pending.clear()

    def wait_for(self, tds) -> None:
        """Stage and dispatch *only* the dependence cone of ``tds``; every
        pending task outside the cone stays pending for a later wave."""
        cone = dependence_cone(tds)
        if not cone:
            return
        self._run_waves([td for td in self.pending if td in cone])
        self.pending = [td for td in self.pending if td not in cone]

    def reclaim(self) -> None:
        self.barrier()
