"""Core runtime behaviour: dependence analysis, MPB protocol, executors.

The central property is *serial elision*: for any task program, executing
through the dynamic host runtime or the staged wavefront runtime produces
bit-identical results to running the tasks sequentially in program order.
Task programs are built on the declarative ``@task`` front-end
(footprint-declared functions spawned inside a runtime scope); the old
imperative ``rt.spawn(fn, In(...), ...)`` shim is gone — one test below
pins the removal.
"""
import functools
import json
import pathlib
import subprocess
import sys

import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import TaskRuntime, task
from repro.core.blocks import BlockArray
from repro.core.graph import DescriptorPool, TaskState
from repro.core.mpb import MPBQueue, SlotState


# ---------------------------------------------------------------------------
# deterministic, order-sensitive task functions (footprint-declared)
@task(inout="prev", in_="x")
def _acc(prev, x):
    return prev * jnp.float32(0.5) + x


@task(in_=("a", "b"), out="o")
def _combine(a, b, o=None):
    return a - jnp.float32(2.0) * b


@task(in_="a", out="o")
def _scale(a, o=None):
    return a * jnp.float32(1.25) + jnp.float32(1.0)


@task(inout="x")
def _fill7(x):
    return jnp.full_like(x, 7.0)


# ---------------------------------------------------------------------------
# unit: blocks / regions
class TestBlocks:
    def test_roundtrip(self):
        a = np.arange(64, dtype=np.float32).reshape(8, 8)
        ba = BlockArray.from_array(a, (4, 4))
        assert ba.grid == (2, 2)
        np.testing.assert_array_equal(np.asarray(ba.gather()), a)

    def test_region_materialize_store(self):
        a = np.arange(64, dtype=np.float32).reshape(8, 8)
        ba = BlockArray.from_array(a, (4, 4))
        reg = ba[0:2, 1]                      # a 2x1 block column
        assert reg.shape == (8, 4)
        np.testing.assert_array_equal(np.asarray(reg.materialize()),
                                      a[:, 4:8])
        reg.store(jnp.zeros((8, 4), jnp.float32))
        assert np.asarray(ba.gather())[:, 4:8].sum() == 0

    def test_bad_block_shape(self):
        with pytest.raises(ValueError):
            BlockArray((10, 10), (4, 4))

    def test_footprint_ids_unique_per_array(self):
        x = BlockArray((8, 8), (4, 4))
        y = BlockArray((8, 8), (4, 4))
        assert set(x.whole.block_ids).isdisjoint(set(y.whole.block_ids))


# ---------------------------------------------------------------------------
# unit: the MPB SPSC protocol (§3.4-3.5)
class TestMPB:
    def _td(self, pool, i=0):
        return pool.acquire(_scale.fn, (), name=f"t{i}")

    def test_fill_reject_complete_reuse(self):
        pool = DescriptorPool(64)
        q = MPBQueue(0, n_slots=2)
        t0, t1, t2 = (self._td(pool, i) for i in range(3))
        assert q.try_put(t0) == (True, None)
        assert q.try_put(t1) == (True, None)
        ok, col = q.try_put(t2)              # ring full -> reject
        assert not ok and col is None
        assert q.full_rejections == 1
        # worker consumes t0, marks completed; master's next put reclaims it
        w = q.next_ready(timeout=0)
        assert w is t0
        q.mark_completed(t0)
        ok, col = q.try_put(t2)
        assert ok and col is t0

    def test_collect_completed(self):
        pool = DescriptorPool(64)
        q = MPBQueue(0, n_slots=4)
        tds = [self._td(pool, i) for i in range(3)]
        for td in tds:
            q.try_put(td)
        for td in tds:
            assert q.next_ready(timeout=0) is td
            q.mark_completed(td)
        assert q.collect_completed() == tds
        assert q.occupancy() == 0


# ---------------------------------------------------------------------------
# unit: dependence orderings
class TestDependences:
    def _rt(self):
        return TaskRuntime(executor="staged")

    def _edges(self, rt):
        edges = []
        orig = rt.analyzer.analyze

        def wrapped(td):
            deps = orig(td)
            edges.extend((d.tid, td.tid) for d in deps)
            return deps

        rt.analyzer.analyze = wrapped
        return edges

    def test_raw(self):
        rt = self._rt()
        edges = self._edges(rt)
        with rt.scope():
            A = rt.zeros((4, 4), (4, 4))
            t0 = _fill7(A[0, 0])
            t1 = _scale(A[0, 0], A[0, 0])
            assert (t0.tid, t1.tid) in edges
            rt.barrier()
        np.testing.assert_allclose(np.asarray(A.gather()), 7 * 1.25 + 1)

    def test_war_and_waw(self):
        rt = self._rt()
        edges = self._edges(rt)
        with rt.scope():
            A = rt.zeros((4, 4), (4, 4))
            B = rt.zeros((4, 4), (4, 4))
            r = _scale(A[0, 0], B[0, 0])       # reader of A
            w1 = _fill7(A[0, 0])               # WAR on r, WAW later
            w2 = _fill7(A[0, 0])
            assert (r.tid, w1.tid) in edges                # WAR
            assert (w1.tid, w2.tid) in edges               # WAW
            rt.barrier()

    def test_disjoint_footprints_no_deps(self):
        rt = self._rt()
        edges = self._edges(rt)
        with rt.scope():
            A = rt.zeros((8, 8), (4, 4))
            _fill7(A[0, 0])
            _fill7(A[1, 1])
            assert edges == []
            rt.barrier()

    def test_multiblock_region_overlap(self):
        rt = self._rt()
        edges = self._edges(rt)
        with rt.scope():
            A = rt.zeros((8, 8), (4, 4))
            t0 = _fill7(A[0, 0:2])   # row of blocks
            t1 = _fill7(A[0:2, 1])   # column of blocks, overlaps
            assert (t0.tid, t1.tid) in edges
            rt.barrier()


# ---------------------------------------------------------------------------
# descriptor pool exhaustion (§3.3): master blocks until recycling
@pytest.mark.parametrize("kind", ["host", "staged"])
def test_pool_exhaustion_recycles(kind):
    rt = TaskRuntime(executor=kind, n_workers=2, pool_capacity=4,
                     mpb_slots=2)
    with rt.scope():
        A = rt.zeros((4, 4), (4, 4))
        for _ in range(20):
            _scale(A[0, 0], A[0, 0])
        rt.barrier()
    got = np.asarray(A.gather())
    expect = np.zeros((4, 4), np.float32)
    for _ in range(20):
        expect = expect * 0.5 * 0 + expect * 1.25 + 1  # _scale repeatedly
    np.testing.assert_allclose(got, expect, rtol=1e-6)
    rt.shutdown()


# ---------------------------------------------------------------------------
# release (§3.6) keeps metadata O(live tasks): back-to-back solves on one
# runtime leave no executed descriptor (and its output tiles) behind
@pytest.mark.parametrize("kind", ["staged", "sharded"])
def test_executed_descriptors_are_freed(kind):
    import gc

    from benchmarks.apps import cholesky_app
    from repro.core.graph import TaskDescriptor

    rt = TaskRuntime(executor=kind)
    live = []
    for _ in range(4):
        cholesky_app(rt, n=256, tile=64, verify=False)   # 20 tasks
        gc.collect()
        live.append(sum(isinstance(o, TaskDescriptor)
                        for o in gc.get_objects()))
    rt.shutdown()
    assert live == live[:1] * 4, live


# ---------------------------------------------------------------------------
# the deprecated imperative shim is gone (window closed after one PR of
# DeprecationWarning); @task is the only spawn surface
def test_spawn_shim_removed():
    with TaskRuntime(executor="staged") as rt:
        assert not hasattr(rt, "spawn")


# ---------------------------------------------------------------------------
# property: serial elision equivalence on random task programs
def _random_program(rt, ops):
    """Replay a generated op list onto a runtime; return its arrays."""
    with rt.scope():
        A = rt.zeros((12, 12), (4, 4), name="A")
        B = rt.full((12, 12), (4, 4), 1.0, name="B")
        arrays = [A, B]
        for op in ops:
            kind, src_a, si, sj, dst_a, di, dj = op
            src, dst = arrays[src_a], arrays[dst_a]
            if kind == 0:
                _acc(dst[di, dj], src[si, sj])
            elif kind == 1:
                _combine(src[si, sj], dst[di, dj], dst[di, dj])
            elif kind == 2:
                _scale(src[si, sj], dst[di, dj])
            else:
                _fill7(dst[di, dj])
        rt.barrier()
    return [np.asarray(a.gather()) for a in arrays]


_op = st.tuples(st.integers(0, 3), st.integers(0, 1), st.integers(0, 2),
                st.integers(0, 2), st.integers(0, 1), st.integers(0, 2),
                st.integers(0, 2))


@settings(max_examples=30, deadline=None)
@given(ops=st.lists(_op, min_size=1, max_size=40))
def test_serial_elision_staged(ops):
    ref = _random_program(TaskRuntime(executor="sequential"), ops)
    got = _random_program(TaskRuntime(executor="staged"), ops)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(r, g)


@settings(max_examples=12, deadline=None)
@given(ops=st.lists(_op, min_size=1, max_size=25))
def test_serial_elision_host(ops):
    ref = _random_program(TaskRuntime(executor="sequential"), ops)
    rt = TaskRuntime(executor="host", n_workers=3, mpb_slots=2)
    try:
        got = _random_program(rt, ops)
    finally:
        rt.shutdown()
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(r, g)


# ---------------------------------------------------------------------------
# property: execution order respects every discovered dependence edge
@settings(max_examples=15, deadline=None)
@given(ops=st.lists(_op, min_size=2, max_size=40))
def test_execution_respects_dependences(ops):
    rt = TaskRuntime(executor="staged")
    edges = []
    orig = rt.analyzer.analyze
    def wrapped(td):
        deps = orig(td)
        edges.extend((d, td) for d in deps)
        return deps
    rt.analyzer.analyze = wrapped
    _random_program(rt, ops)
    for d, t in edges:
        assert d.exec_order is not None and t.exec_order is not None
        assert d.exec_order < t.exec_order, (d, t)


# ---------------------------------------------------------------------------
# scheduling policies all produce correct results (new @task front-end)
@task(inout="c", in_=("x", "y"))
def _gemm_task(c, x, y):
    return c + x @ y


@pytest.mark.parametrize("policy", ["round_robin", "locality", "random"])
def test_policies(policy):
    rng = np.random.default_rng(1)
    a = rng.standard_normal((64, 64), dtype=np.float32)
    b = rng.standard_normal((64, 64), dtype=np.float32)

    with TaskRuntime(executor="host", n_workers=3, mpb_slots=2,
                     policy=policy) as rt:
        A = rt.from_array(a, (16, 16))
        B = rt.from_array(b, (16, 16))
        C = rt.zeros((64, 64), (16, 16))
        g = 4
        for i in range(g):
            for j in range(g):
                for k in range(g):
                    _gemm_task(C[i, j], A[i, k], B[k, j])
        rt.barrier()
    np.testing.assert_allclose(np.asarray(C.gather()), a @ b,
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# placement
def test_placement_striped_balanced():
    from repro.core.placement import home_histogram
    rt = TaskRuntime(executor="sequential", placement="striped",
                     n_controllers=4)
    A = rt.zeros((32, 32), (4, 4))     # 64 blocks
    hist = home_histogram(A, 4)
    assert hist == [16, 16, 16, 16]


def test_placement_single_contended():
    from repro.core.placement import home_histogram
    rt = TaskRuntime(executor="sequential", placement="single")
    A = rt.zeros((32, 32), (4, 4))
    assert home_histogram(A, 4) == [64, 0, 0, 0]


# ---------------------------------------------------------------------------
# one device program per wave group: the stack, the vmapped body and the
# per-task unstack run inside one jit, so the barrier dispatches no eager
# stack and no per-task slice on the XLA path
def _group_program_probe(executor: str) -> dict:
    """Run ``cholesky_app`` (n=64, tile=16: 20 tasks) once to trace every
    group shape, then again with eager ``jnp.stack`` and
    ``jax.Array.__getitem__`` patched to raise inside the barrier.  On a
    mesh every group runs as per-owner group programs, with no
    exemption."""
    from unittest import mock

    import jax
    from benchmarks.apps import cholesky_app

    want = np.asarray(cholesky_app(TaskRuntime(executor="sequential"),
                                   n=64, tile=16, verify=False).gather())
    rt = TaskRuntime(executor=executor, n_controllers=2)
    cholesky_app(rt, n=64, tile=16, verify=False)
    ex = rt._exec
    widths = []

    def counted(program):
        def call(reads, values):
            widths.append(len(reads[0]))
            return program(reads, values)
        return call

    for fn in ex._vjit:
        ex._vjit[fn] = counted(ex._vjit[fn])

    real_stack = jnp.stack
    array_type = type(jnp.zeros(1))

    def stack(arrays, *args, **kwargs):
        arrays = list(arrays)
        if not all(isinstance(a, jax.core.Tracer) for a in arrays):
            raise AssertionError("eager jnp.stack in the barrier")
        return real_stack(arrays, *args, **kwargs)

    def getitem(self, idx):
        raise AssertionError("eager per-task slice in the barrier")

    barrier = ex.barrier

    def patched_barrier():
        with mock.patch.object(jnp, "stack", stack), \
                mock.patch.object(array_type, "__getitem__", getitem):
            barrier()

    ex.barrier = patched_barrier
    before = rt.stats()
    got = np.asarray(cholesky_app(rt, n=64, tile=16, verify=False).gather())
    after = rt.stats()
    rt.shutdown()
    return {"bit_identical": bool(np.array_equal(got, want)),
            "group_program_tasks": (after.group_program_tasks
                                    - before.group_program_tasks),
            "program_widths": widths,
            "sharded_dispatches": ((after.sharded_dispatches or 0)
                                   - (before.sharded_dispatches or 0))}


@functools.lru_cache(maxsize=None)
def _group_program_run(executor: str) -> dict:
    """The probe: staged in this process, sharded on a forced 2-device
    mesh in a subprocess (the device count is fixed before JAX starts)."""
    if executor == "staged":
        return _group_program_probe(executor)
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json, sys
sys.path[:0] = ["src", ".", "tests"]
import jax, numpy as np
import conftest, test_core_runtime
from repro import dist
assert jax.device_count() == 2
mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("data",))
with dist.use_mesh(mesh):
    print(json.dumps(test_core_runtime._group_program_probe("sharded")))
"""
    out = subprocess.run([sys.executable, "-c", code],
                         cwd=pathlib.Path(__file__).resolve().parent.parent,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("executor", ["staged", "sharded"])
def test_group_program_runs_no_eager_stack_or_slice(executor):
    # the probe's patches raise on an eager stack or per-task slice
    run = _group_program_run(executor)
    assert run["program_widths"]
    if executor == "sharded":
        # every group program ran pinned to an owner device
        assert run["sharded_dispatches"] == len(run["program_widths"]) > 0


@pytest.mark.parametrize("executor", ["staged", "sharded"])
def test_group_program_tasks_counts_grouped_tasks(executor):
    run = _group_program_run(executor)
    assert min(run["program_widths"]) >= 2
    assert run["group_program_tasks"] == sum(run["program_widths"])
    if executor == "staged":
        # 20 tasks: the 4 potrf and the width-1 trsm and update of the
        # last step run alone
        assert run["group_program_tasks"] == 20 - 4 - 2


@pytest.mark.parametrize("executor", ["staged", "sharded"])
def test_group_program_bit_identical_to_sequential(executor):
    assert _group_program_run(executor)["bit_identical"]


def test_store_group_commits_stacked_or_per_task_values():
    """The grouped commit point takes each output as a stacked array
    (pallas grid) or a tuple of per-task values (group program)."""
    rt = TaskRuntime(executor="staged")
    with rt.scope():
        src = rt.full((4, 12), (4, 4), 1.0)
        dst = rt.zeros((4, 12), (4, 4))
        for j in range(3):
            _scale(src[0, j], dst[0, j])
    group = list(rt._exec.pending)
    vals = [jnp.full((4, 4), float(j + 1)) for j in range(3)]
    committed = []
    for result in (jnp.stack(vals), tuple(vals)):
        rt._exec._store_group(group, result)
        committed.append([np.asarray(dst.get_tile((0, j)))
                          for j in range(3)])
        assert [np.asarray(td.output_values[0]).tolist()
                for td in group] == [v.tolist() for v in committed[-1]]
    for j in range(3):
        np.testing.assert_array_equal(committed[0][j], vals[j])
        np.testing.assert_array_equal(committed[1][j], vals[j])
    rt.shutdown()
