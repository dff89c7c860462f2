"""Placement -> sharding: the home-aware mesh execution layer.

Covers the properties ISSUE 3 pins down: ``device_assignment`` round-trips
(every home maps to a device; the block-cyclic layout matches
``home_histogram``), owner-computes traffic accounting, the per-owner
group programs under a mesh, the single-device fallback
(no mesh installed at all), and sharded-vs-sequential numerics on the
cholesky and jacobi benchmark apps.
"""
import functools
import json
import pathlib
import subprocess
import sys

import numpy as np
import pytest
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import dist
from repro.core import RuntimeConfig, TaskRuntime, task
from repro.core.blocks import BlockArray
from repro.core.placement import (assign_homes, device_assignment,
                                  home_histogram, home_sharding)
from repro.core.sharded import ShardedExecutor, owner_home


@task(inout="c", in_=("a", "b"))
def _gemm(c, a, b):
    return c + a @ b


@task(inout="x")
def _bump(x):
    return x + 1.0


def _gemm_program(rt, a, b, tile=32):
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
        return np.asarray(C.gather())


# ---------------------------------------------------------------------------
class TestDeviceAssignment:
    def test_no_mesh_every_home_maps_to_default_device(self):
        assert dist.current() is None
        devs = device_assignment(4)
        assert len(devs) == 4
        assert all(d is jax.devices()[0] for d in devs)

    def test_block_cyclic_over_mesh_devices(self):
        with dist.use_mesh(dist.single_device_mesh()) as ctx:
            devs = device_assignment(4, ctx)
            mesh_devs = list(np.asarray(ctx.mesh.devices).flat)
            for h, d in enumerate(devs):
                assert d is mesh_devs[h % len(mesh_devs)]

    def test_roundtrip_matches_home_histogram(self):
        """Pushing every home's block count through the assignment must
        conserve blocks: per-device totals sum to the histogram's total,
        and striped homes spread as evenly over devices as over homes."""
        ba = BlockArray((32, 32), (4, 4))          # 64 blocks
        assign_homes(ba, "striped", n_homes=4)
        hist = home_histogram(ba, 4)
        assert hist == [16, 16, 16, 16]
        with dist.use_mesh(dist.single_device_mesh()) as ctx:
            devs = device_assignment(4, ctx)
            per_dev: dict = {}
            for h, d in enumerate(devs):
                per_dev[d] = per_dev.get(d, 0) + hist[h]
        assert sum(per_dev.values()) == sum(hist) == 64
        # block-cyclic: with ndev dividing n_homes, every device carries
        # the same number of blocks
        counts = list(per_dev.values())
        assert max(counts) == min(counts)

    def test_every_block_home_is_assigned(self):
        """Round-trip property: any home assign_homes produced indexes
        into the device map (no orphan homes)."""
        for policy in ("single", "striped", "striped_diag"):
            ba = BlockArray((24, 24), (4, 4))
            assign_homes(ba, policy, n_homes=4)
            devs = device_assignment(4)
            for idx, h in ba.home.items():
                assert devs[h % len(devs)] is not None

    def test_home_sharding_divisibility_guard(self):
        ba = BlockArray((32, 32), (4, 4))          # 64 blocks: divisible
        assert home_sharding(ba) is None           # no mesh -> fallback
        with dist.use_mesh(dist.single_device_mesh()) as ctx:
            s = home_sharding(ba, ctx)
            assert s.mesh is ctx.mesh
            # the block axis of the (n_blocks, 4, 4) view is sharded over
            # the mesh's one axis, whatever normal form the spec takes
            want = NamedSharding(ctx.mesh, P("data"))
            assert s.is_equivalent_to(want, 3)
            assert s.spec[0] in ("data", ("data",))


# ---------------------------------------------------------------------------
class TestOwnerComputes:
    def test_owner_is_home_of_first_output_block(self):
        with TaskRuntime(executor="sharded", placement="striped",
                         n_controllers=4) as rt:
            A = rt.zeros((16, 16), (4, 4))         # homes 0..3 striped
            B = rt.zeros((16, 16), (4, 4))
            f = _gemm(A[1, 2], B[0, 0], B[0, 1])   # output block (1, 2)
            assert owner_home(f.descriptor) == A.home[(1, 2)]
            rt.barrier()

    def test_cross_home_bytes_single_placement_is_zero(self):
        """With everything homed on controller 0 (the paper's contended
        baseline) owner-computes never crosses homes."""
        rng = np.random.default_rng(0)
        a = rng.standard_normal((64, 64), dtype=np.float32)
        rt = TaskRuntime(executor="sharded", placement="single")
        _gemm_program(rt, a, a)
        s = rt.stats()
        assert s.cross_home_bytes == 0
        assert s.local_home_bytes > 0

    def test_cross_home_bytes_striped_gemm_exact(self):
        """Striped homes on the gemm task grid: C[i,j] and B[k,j] share
        the owner's home column, A[i,k] crosses whenever k != j — the
        count is exact, like sim.py's per-home contention charge."""
        rng = np.random.default_rng(1)
        a = rng.standard_normal((128, 128), dtype=np.float32)
        rt = TaskRuntime(executor="sharded", placement="striped",
                        n_controllers=4)
        _gemm_program(rt, a, a, tile=32)
        s = rt.stats()
        g, block_bytes = 4, 32 * 32 * 4
        # g^3 tasks; A-read crosses for the g^2 * (g-1) tasks with k != j
        assert s.cross_home_bytes == g * g * (g - 1) * block_bytes
        assert s.local_home_bytes == (3 * g ** 3 - g * g * (g - 1)) \
            * block_bytes

    def test_accounting_identical_with_and_without_mesh(self):
        """Home traffic is a placement-policy quantity: the single-device
        fallback must report the same bytes a mesh run does."""
        rng = np.random.default_rng(2)
        a = rng.standard_normal((64, 64), dtype=np.float32)
        rt1 = TaskRuntime(executor="sharded", placement="striped_diag")
        _gemm_program(rt1, a, a)
        with dist.use_mesh(dist.single_device_mesh()):
            rt2 = TaskRuntime(executor="sharded", placement="striped_diag")
            _gemm_program(rt2, a, a)
        s1, s2 = rt1.stats(), rt2.stats()
        assert s1.cross_home_bytes == s2.cross_home_bytes
        assert s1.local_home_bytes == s2.local_home_bytes


# ---------------------------------------------------------------------------
class TestShardedExecutor:
    def test_registered_in_config(self):
        cfg = RuntimeConfig(executor="sharded").validate()
        rt = TaskRuntime(cfg)
        assert isinstance(rt._exec, ShardedExecutor)
        assert rt._exec.n_homes == cfg.n_controllers

    def test_single_device_fallback_no_mesh(self):
        """No mesh installed: dispatch degrades to the staged path (no
        per-owner programs), numerics match sequential bit-for-bit, and the stats
        carry the sharded section."""
        rng = np.random.default_rng(3)
        a = rng.standard_normal((128, 128), dtype=np.float32)
        b = rng.standard_normal((128, 128), dtype=np.float32)
        ref = _gemm_program(TaskRuntime(executor="sequential"), a, b)
        rt = TaskRuntime(executor="sharded")
        got = _gemm_program(rt, a, b)
        np.testing.assert_array_equal(ref, got)
        s = rt.stats()
        assert s.sharded_dispatches == 0           # fallback: plain staged
        assert s.grouped_dispatches and s.grouped_dispatches > 0
        assert s.cross_home_bytes is not None

    def test_shard_map_hybrid_under_mesh(self):
        """With a mesh context active every grouped wavefront dispatch
        runs as a group program pinned to its owner device (one device
        here, so one program per group), and results still match
        sequential bit-for-bit."""
        rng = np.random.default_rng(4)
        a = rng.standard_normal((128, 128), dtype=np.float32)
        b = rng.standard_normal((128, 128), dtype=np.float32)
        ref = _gemm_program(TaskRuntime(executor="sequential"), a, b)
        with dist.use_mesh(dist.single_device_mesh()):
            rt = TaskRuntime(executor="sharded")
            got = _gemm_program(rt, a, b)
        np.testing.assert_array_equal(ref, got)
        s = rt.stats()
        assert s.sharded_dispatches == s.grouped_dispatches > 0

    def test_firstprivate_values_ride_the_sharded_dispatch(self):
        """Index-parameterized tasks batch through the per-owner group
        program with their values stacked on the owner device (the staged
        grouping reused)."""
        @task(in_="x", out="y", firstprivate="k")
        def affine(x, k, y=None):
            return x * k

        def run(executor, mesh):
            import contextlib
            ctx = dist.use_mesh(dist.single_device_mesh()) if mesh \
                else contextlib.nullcontext()
            with ctx:
                with TaskRuntime(executor=executor) as rt:
                    X = rt.full((16, 16), (4, 4), 1.0)
                    Y = rt.zeros((16, 16), (4, 4))
                    for n, (i, j) in enumerate(
                            (i, j) for i in range(4) for j in range(4)):
                        affine(X[i, j], float(n), Y[i, j])
                    rt.barrier()
                    return np.asarray(Y.gather()), rt.stats()

        ref, _ = run("sequential", mesh=False)
        got, s = run("sharded", mesh=True)
        np.testing.assert_array_equal(ref, got)
        assert s.sharded_dispatches == 1           # one wave, one device

    def test_wait_on_and_futures_still_region_scoped(self):
        """The sharded executor inherits cone-scoped synchronization."""
        with dist.use_mesh(dist.single_device_mesh()):
            with TaskRuntime(executor="sharded") as rt:
                A = rt.zeros((4, 4), (4, 4))
                B = rt.zeros((4, 4), (4, 4))
                f = _bump(A[0, 0])
                g = _bump(B[0, 0])
                assert not (f.done() or g.done())
                np.testing.assert_allclose(np.asarray(f.result()), 1.0)
                assert not g.done(), "unrelated task was forced"


# ---------------------------------------------------------------------------
class TestShardedApps:
    """Sharded-vs-sequential numerics on the paper apps the issue names.
    Each app also self-verifies against its reference kernel inside
    run_app, so these runs assert correctness twice over."""

    @pytest.mark.parametrize("mesh", [False, True])
    def test_cholesky(self, mesh):
        from benchmarks.apps import run_app
        import contextlib
        ctx = dist.use_mesh(dist.single_device_mesh()) if mesh \
            else contextlib.nullcontext()
        with ctx:
            s = run_app("cholesky", "sharded",
                        placement="striped_diag")
        assert s.cross_home_bytes is not None and s.cross_home_bytes > 0
        if mesh:
            assert s.sharded_dispatches and s.sharded_dispatches > 0

    @pytest.mark.parametrize("mesh", [False, True])
    def test_jacobi(self, mesh):
        from benchmarks.apps import run_app
        import contextlib
        ctx = dist.use_mesh(dist.single_device_mesh()) if mesh \
            else contextlib.nullcontext()
        with ctx:
            s = run_app("jacobi", "sharded")
        assert s.cross_home_bytes is not None and s.cross_home_bytes > 0

    def test_cholesky_matches_sequential_gather(self):
        """Beyond the apps' reference checks: the factor the sharded
        executor leaves in memory equals the sequential executor's."""
        from repro.kernels.cholesky import ops as chol_ops

        @task(inout="a")
        def potrf(a):
            return chol_ops.potrf(a)

        @task(in_="l", inout="a")
        def trsm(l, a):
            return chol_ops.trsm(l, a)

        @task(inout="c", in_=("x", "y"))
        def update(c, x, y):
            return chol_ops.update(c, x, y)

        n, tile = 128, 32
        g = n // tile
        rng = np.random.default_rng(5)
        m = rng.standard_normal((n, n)).astype(np.float32)
        spd = m @ m.T + n * np.eye(n, dtype=np.float32)

        def run(executor, mesh=False):
            import contextlib
            ctx = dist.use_mesh(dist.single_device_mesh()) if mesh \
                else contextlib.nullcontext()
            with ctx:
                with TaskRuntime(executor=executor,
                                 placement="striped_diag") as rt:
                    A = rt.from_array(spd, (tile, tile))
                    for k in range(g):
                        potrf(A[k, k])
                        for i in range(k + 1, g):
                            trsm(A[k, k], A[i, k])
                        for i in range(k + 1, g):
                            for j in range(k + 1, i + 1):
                                update(A[i, j], A[i, k], A[j, k])
                    rt.barrier()
                    return np.asarray(A.gather())

        ref = run("sequential")
        np.testing.assert_allclose(run("sharded"), ref,
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(run("sharded", mesh=True), ref,
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
_FOUR_DEVICES = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
sys.path[:0] = ["src", "."]
import jax, numpy as np
from repro import dist
from repro.core import TaskRuntime, task
from chipbench.programs import potrf

assert jax.device_count() == 4
mesh = jax.sharding.Mesh(np.asarray(jax.devices()).reshape(4), ("data",))
out = {}

def sharded():
    return TaskRuntime(executor="sharded", placement="striped",
                       n_controllers=4)

@task(inout="c", in_=("a", "b"))
def gemm(c, a, b):
    return c + a @ b

@task(in_="halo", out="dest")
def avg(halo, dest=None):
    return halo[:4] * 0.5 + halo[4:] * 0.5

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
        return np.asarray(C.gather())

ref = prog(TaskRuntime(executor="sequential"))
with dist.use_mesh(mesh):
    rt = sharded()
    got = prog(rt)
s = rt.stats()
out["gemm"] = {"bit_identical": bool(np.array_equal(ref, got)),
               "sharded_dispatches": s.sharded_dispatches,
               "grouped_dispatches": s.grouped_dispatches,
               "cross_home_bytes": s.cross_home_bytes}

# uneven wave (5 tasks on 4 devices) + multi-block reads spanning owners
with dist.use_mesh(mesh):
    with sharded() as rt:
        X = rt.full((24, 4), (4, 4), 1.0)    # 6 blocks on 4 devices
        Y = rt.zeros((20, 4), (4, 4))
        for i in range(5):
            avg(X[i:i + 2, 0], Y[i, 0])
        rt.barrier()
        out["uneven_ok"] = bool(np.allclose(np.asarray(Y.gather()), 1.0))

# the benchmark's Cholesky DAG at grid 16: 816 tasks in 46 waves
grid, tile = 16, 8
n = grid * tile
m = rng.standard_normal((n, n)).astype(np.float32)
spd = m @ m.T + n * np.eye(n, dtype=np.float32)

def factor(rt):
    with rt.scope():
        arrays = {"A": rt.from_array(spd, (tile, tile), name="A")}
        before = rt.traffic.bytes_moved
        potrf.spawn(arrays, grid)
        rt.barrier()
        moved = rt.traffic.bytes_moved - before
        return np.asarray(arrays["A"].gather()), moved

want, _ = factor(TaskRuntime(executor="sequential"))
with dist.use_mesh(mesh):
    rt = sharded()
    got, moved = factor(rt)
    s = rt.stats()
out["potrf"] = {"bit_identical": bool(np.array_equal(want, got)),
                "bytes_moved": moved,
                "cross_home_bytes": s.cross_home_bytes,
                "bytes_staged": s.bytes_staged,
                "tile_bytes": tile * tile * 4,
                "group_programs": sum(p._cache_size()
                                      for p in rt._exec._vjit.values())}
print(json.dumps(out))
"""


@functools.lru_cache(maxsize=None)
def _four_device_run() -> dict:
    """4 host devices in a subprocess (the device count is fixed before
    JAX starts), blocks striped over 4 homes -> 4 devices; one run feeds
    every four-device test."""
    out = subprocess.run([sys.executable, "-c", _FOUR_DEVICES],
                         cwd=pathlib.Path(__file__).resolve().parent.parent,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_sharded_on_four_devices_matches_sequential():
    """The real thing: per-owner group programs on 4 devices, an uneven
    wave, cross-device multi-block materialize, and a mixed-device gather
    — all bit-identical to sequential."""
    run = _four_device_run()
    gemm = run["gemm"]
    assert gemm["bit_identical"]
    # striped homes put C[i, j] at home j % 4: each of the 4 waves splits
    # into one group program per owner device
    assert gemm["sharded_dispatches"] == gemm["grouped_dispatches"] == 16
    assert gemm["cross_home_bytes"] > 0
    assert run["uneven_ok"]


def test_four_device_cholesky_moves_only_cross_home_tiles():
    """Owner-computes on the benchmark's Cholesky DAG (``chipbench``'s
    potrf program, grid 16, striped homes over 4 devices): every task
    runs on its owner device and each group program receives a distinct
    tile once, so a cross-home tile moves once per owner device and
    group: 319 moves, against the 1112 cross-home tile reads the
    footprints count.  The factor is bit-identical to sequential."""
    run = _four_device_run()["potrf"]
    assert run["bit_identical"]
    assert run["cross_home_bytes"] == 1112 * run["tile_bytes"]
    assert run["bytes_moved"] == 319 * run["tile_bytes"]
    assert run["bytes_moved"] < run["cross_home_bytes"]
    assert run["bytes_staged"] == 0


def test_four_device_cholesky_group_programs_per_shape():
    """The static read index of the per-owner group programs adds no
    program: the grid-16 Cholesky on 4 devices holds one per grouped
    shape, 28, as the staged executor does."""
    assert _four_device_run()["potrf"]["group_programs"] == 28
