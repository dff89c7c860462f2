"""The pallas wave-kernel backend: eligibility edges, fallback
accounting, bit-identity against the staged reference, sim charging.

Every ineligible shape must take the XLA fallback — *named*, counted in
``RuntimeStats.kernel_fallbacks``, tagged on the ``kernel_dispatch``
event — and produce numerics identical to the staged path, because the
fallback *is* the staged path.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from repro import dist
from repro.core import RuntimeConfig, TaskRuntime, task
from repro.core import wavekernel
from repro.core.blocks import FootprintSpec
from repro.obs import InMemoryTracker


@task(inout="c", in_=("x", "y"))
def _gemm(c, x, y):
    return c + jnp.dot(x, y, preferred_element_type=jnp.float32)


@task(inout="c", in_="a")
def _add(c, a):
    return c + a


@task(inout="c", in_="m")
def _add_int(c, m):
    return c + m.astype(jnp.float32)


@task(inout="v", in_="w")
def _add1d(v, w):
    return v + w


def _gemm_run(backend, n=64, tile=16, tracker=None, executor="staged"):
    rng = np.random.default_rng(7)
    a = rng.standard_normal((n, n), dtype=np.float32)
    b = rng.standard_normal((n, n), dtype=np.float32)
    rt = TaskRuntime(RuntimeConfig(executor=executor,
                                   kernel_backend=backend,
                                   tracker=tracker))
    g = n // tile
    with rt.scope():
        A = rt.from_array(a, (tile, tile))
        B = rt.from_array(b, (tile, tile))
        C = rt.zeros((n, n), (tile, tile))
        for k in range(g):
            for i in range(g):
                for j in range(g):
                    _gemm(C[i, j], A[i, k], B[k, j])
        rt.barrier()
        out = np.asarray(C.gather())
    stats = rt.stats()
    rt.shutdown()
    return out, stats


# ---------------------------------------------------------------------------
class TestAcceptance:
    def test_striped_gemm_bit_identical_one_dispatch_per_wave(self):
        """The issue's acceptance bar: on the striped gemm program the
        pallas backend is bit-identical to staged and every eligible wave
        dispatches exactly once (one fused grid per wave)."""
        ref, ref_stats = _gemm_run("xla")
        out, stats = _gemm_run("pallas")
        np.testing.assert_array_equal(out, ref)
        # every wave is one homogeneous group -> one fused dispatch each
        assert stats.kernel_dispatches == stats.waves == ref_stats.waves
        assert stats.kernel_fallbacks == 0
        assert stats.grouped_dispatches == stats.waves

    def test_xla_backend_leaves_kernel_counters_inert(self):
        _, stats = _gemm_run("xla")
        assert stats.kernel_dispatches is None
        assert stats.kernel_fallbacks is None

    def test_jacobi_app_fuses_every_group(self):
        from benchmarks.apps import run_app
        stats = run_app("jacobi", "staged", kernel_backend="pallas")
        assert stats.kernel_fallbacks == 0
        assert stats.kernel_dispatches > 0

    def test_kernel_dispatch_events(self):
        trk = InMemoryTracker()
        _, stats = _gemm_run("pallas", tracker=trk)
        evs = trk.events_of("kernel_dispatch")
        assert len(evs) == stats.kernel_dispatches
        assert all(e.data["backend"] == "pallas" and e.data["reason"] == ""
                   for e in evs)
        assert all(e.data["executor"] == "staged" for e in evs)


# ---------------------------------------------------------------------------
def _edge_run(spawn, backend, tracker=None, **cfg):
    rt = TaskRuntime(RuntimeConfig(executor="staged",
                                   kernel_backend=backend,
                                   tracker=tracker, **cfg))
    with rt.scope():
        arrays = spawn(rt)
        rt.barrier()
        outs = [np.asarray(a.gather()) for a in arrays]
    stats = rt.stats()
    rt.shutdown()
    return outs, stats


def _fallback_reasons(tracker):
    return [e.data["reason"] for e in tracker.events_of("kernel_dispatch")
            if e.data["backend"] == "xla"]


class TestEligibilityEdges:
    """Each ineligible shape: fallback taken (counted + named), numerics
    still match the staged run of the identical program."""

    def _both(self, spawn):
        trk = InMemoryTracker()
        ref, _ = _edge_run(spawn, "xla")
        out, stats = _edge_run(spawn, "pallas", tracker=trk)
        for got, want in zip(out, ref):
            np.testing.assert_array_equal(got, want)
        assert stats.kernel_fallbacks > 0
        return stats, _fallback_reasons(trk)

    def test_single_task_group(self):
        def spawn(rt):
            C = rt.zeros((8, 8), (8, 8))
            A = rt.full((8, 8), (8, 8), 2.0)
            _add(C[0, 0], A[0, 0])
            return [C]

        stats, reasons = self._both(spawn)
        assert reasons == ["single_task"]
        assert stats.kernel_dispatches == 0

    def test_non_rectangular_footprint(self):
        def spawn(rt):
            V = rt.zeros((32,), (8,))
            W = rt.full((32,), (8,), 1.5)
            for i in range(4):
                _add1d(V[i], W[i])
            return [V]

        _, reasons = self._both(spawn)
        assert "non_rectangular" in reasons

    def test_mixed_dtype_wave(self):
        def spawn(rt):
            C = rt.zeros((32, 8), (8, 8))
            M = rt.from_array(np.arange(256, dtype=np.int32).reshape(32, 8),
                              (8, 8))
            for i in range(4):
                _add_int(C[i, 0], M[i, 0])
            return [C]

        _, reasons = self._both(spawn)
        assert "mixed_dtype" in reasons

    def test_grid_dim_overflow(self, monkeypatch):
        monkeypatch.setattr(wavekernel, "MAX_GRID_TASKS", 2)

        def spawn(rt):
            C = rt.zeros((32, 8), (8, 8))
            A = rt.full((32, 8), (8, 8), 3.0)
            for i in range(4):
                _add(C[i, 0], A[i, 0])
            return [C]

        _, reasons = self._both(spawn)
        assert "grid_overflow" in reasons

    def test_ungrouped_waves_fall_back(self):
        def spawn(rt):
            C = rt.zeros((32, 8), (8, 8))
            A = rt.full((32, 8), (8, 8), 1.0)
            for i in range(4):
                _add(C[i, 0], A[i, 0])
            return [C]

        trk = InMemoryTracker()
        ref, _ = _edge_run(spawn, "xla", group_waves=False)
        out, stats = _edge_run(spawn, "pallas", tracker=trk,
                               group_waves=False)
        np.testing.assert_array_equal(out[0], ref[0])
        assert stats.kernel_fallbacks > 0
        assert set(_fallback_reasons(trk)) == {"ungrouped"}

    def test_sharded_under_mesh_names_its_fallback(self):
        """With a live mesh the sharded executor keeps the shard_map
        hybrid (owner-computes would break under a one-device fused
        grid) and names the fallback."""
        rng = np.random.default_rng(9)
        a = rng.standard_normal((64, 64), dtype=np.float32)
        b = rng.standard_normal((64, 64), dtype=np.float32)

        def run(backend, mesh, tracker=None):
            import contextlib
            cm = (dist.use_mesh(dist.single_device_mesh()) if mesh
                  else contextlib.nullcontext())
            with cm:
                rt = TaskRuntime(RuntimeConfig(
                    executor="sharded", kernel_backend=backend,
                    tracker=tracker))
                with rt.scope():
                    A = rt.from_array(a, (16, 16))
                    B = rt.from_array(b, (16, 16))
                    C = rt.zeros((64, 64), (16, 16))
                    for k in range(4):
                        for i in range(4):
                            for j in range(4):
                                _gemm(C[i, j], A[i, k], B[k, j])
                    rt.barrier()
                    out = np.asarray(C.gather())
                stats = rt.stats()
                rt.shutdown()
                return out, stats

        trk = InMemoryTracker()
        ref, _ = run("xla", mesh=True)
        out, stats = run("pallas", mesh=True, tracker=trk)
        np.testing.assert_array_equal(out, ref)
        assert stats.kernel_dispatches == 0
        assert stats.kernel_fallbacks > 0
        assert set(_fallback_reasons(trk)) == {"sharded_mesh"}
        # without a mesh the same program fuses via the staged fallback
        out2, stats2 = run("pallas", mesh=False)
        np.testing.assert_array_equal(out2, ref)
        assert stats2.kernel_dispatches == stats2.waves


# ---------------------------------------------------------------------------
class TestFallbackSplit:
    """Only building a kernel may fall back: a group over the VMEM budget
    or refused by the compiler takes the XLA path under its own named
    reason, while a fault in the call of a compiled kernel propagates."""

    def test_vmem_budget_reason_named(self, monkeypatch):
        monkeypatch.setattr(wavekernel, "VMEM_BUDGET_BYTES", 1024)
        trk = InMemoryTracker()
        ref, _ = _gemm_run("xla")
        out, stats = _gemm_run("pallas", tracker=trk)
        np.testing.assert_array_equal(out, ref)
        assert stats.kernel_dispatches == 0
        assert stats.kernel_fallback_reasons == {"vmem_budget": stats.waves}
        assert set(_fallback_reasons(trk)) == {"vmem_budget"}

    def test_compile_refused_named_with_first_error_line(self, monkeypatch):
        attempts = []

        class Refused:
            def lower(self, *args):
                attempts.append(len(args))
                raise RuntimeError("RESOURCE_EXHAUSTED: Ran out of memory "
                                   "in memory space vmem\nsecond line")

        monkeypatch.setattr(wavekernel, "build_wave_kernel",
                            lambda *a, **k: Refused())
        trk = InMemoryTracker()
        ref, _ = _gemm_run("xla")
        out, stats = _gemm_run("pallas", tracker=trk)
        np.testing.assert_array_equal(out, ref)
        assert stats.kernel_dispatches == 0
        assert stats.kernel_fallback_reasons == \
            {"compile_refused": stats.waves}
        evs = [e.data for e in trk.events_of("kernel_dispatch")]
        assert {e["reason"] for e in evs} == {"compile_refused"}
        assert {e["error"] for e in evs} == {
            "_gemm: RESOURCE_EXHAUSTED: Ran out of memory in memory "
            "space vmem"}
        # every wave has the same group shape: one refusal, then cached
        assert attempts == [3]

    def test_device_fault_in_fused_call_propagates(self, monkeypatch):
        def compiled_but_faulting(*args, **kwargs):
            def call(*stacked):
                raise RuntimeError("device fault in fused call")
            return call

        monkeypatch.setattr(wavekernel, "compile_wave_kernel",
                            compiled_but_faulting)
        rt = TaskRuntime(RuntimeConfig(executor="staged",
                                       kernel_backend="pallas"))
        with rt.scope():
            A = rt.full((32, 32), (16, 16), 1.0)
            C = rt.zeros((32, 32), (16, 16))
            for i in range(2):
                _add(C[i, 0], A[i, 0])
            with pytest.raises(RuntimeError, match="device fault"):
                rt.barrier()
        stats = rt.stats()
        rt.shutdown()
        assert stats.kernel_fallbacks == 0
        assert stats.kernel_dispatches == 0


# ---------------------------------------------------------------------------
class TestEligibilityUnit:
    def test_footprint_spec(self):
        rt = TaskRuntime(RuntimeConfig(executor="sequential"))
        with rt.scope():
            A = rt.zeros((32, 16), (8, 8))
            spec = A[1:3, 0:2].footprint_spec()
        rt.shutdown()
        assert spec == FootprintSpec((16, 16), "float32", (2, 2))
        assert spec.rank == 2 and spec.n_tiles == 4

    def test_interpret_mode_env_flag(self, monkeypatch):
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
        assert wavekernel.interpret_mode() is True

    def test_config_validation(self):
        with pytest.raises(ValueError, match="kernel_backend"):
            RuntimeConfig(kernel_backend="vulkan").validate()
        assert RuntimeConfig(kernel_backend="pallas").validate()

    def test_infer_out_structs_rejects_untraceable_bodies(self):
        import jax

        def bad(x):
            return float(np.asarray(x).sum())    # concretizes the tracer

        with pytest.raises(wavekernel.WaveKernelError):
            wavekernel.infer_out_structs(
                bad, [jax.ShapeDtypeStruct((4, 4), np.float32)], 1, "bad")

    def test_build_wave_kernel_matches_vmap(self):
        import jax

        def body(c, x, s):
            return c + s * x

        n, h = 5, 8
        rng = np.random.default_rng(3)
        C = jnp.asarray(rng.standard_normal((n, h, h)).astype(np.float32))
        X = jnp.asarray(rng.standard_normal((n, h, h)).astype(np.float32))
        S = jnp.asarray(rng.standard_normal(n).astype(np.float32))
        structs = [jax.ShapeDtypeStruct((h, h), np.float32),
                   jax.ShapeDtypeStruct((h, h), np.float32),
                   jax.ShapeDtypeStruct((), np.float32)]
        outs = wavekernel.infer_out_structs(body, structs, 1, "body")
        run = wavekernel.build_wave_kernel(body, n, structs, outs,
                                           interpret=True, label="body")
        want = jax.jit(jax.vmap(body))(C, X, S)
        np.testing.assert_array_equal(np.asarray(run(C, X, S)),
                                      np.asarray(want))


# ---------------------------------------------------------------------------
class TestSimCharging:
    def test_fused_waves_predicted_cheaper(self):
        """The DES charges fused waves on-chip: no per-task L2 flush and
        write-backs at MPB cost, so the pallas prediction undercuts the
        XLA prediction for the same program."""
        from benchmarks.apps import run_app

        xla = run_app("matmul", "sim", kernel_backend="xla")
        pal = run_app("matmul", "sim", kernel_backend="pallas")
        assert xla.kernel_dispatches is None
        assert pal.kernel_dispatches > 0
        assert pal.kernel_fallbacks == 0
        assert pal.predicted_total_s < xla.predicted_total_s

    def test_sim_fallback_prediction_matches_real_split(self):
        """The DES's predicted fuse/fallback split uses the same shared
        eligibility as the real dispatch, so on the same app the counts
        agree (cholesky mixes fused waves with single-task fallbacks)."""
        from benchmarks.apps import run_app

        real = run_app("cholesky", "staged", kernel_backend="pallas")
        sim = run_app("cholesky", "sim", kernel_backend="pallas")
        assert sim.kernel_dispatches == real.kernel_dispatches
        assert sim.kernel_fallbacks == real.kernel_fallbacks
        assert sim.kernel_fallback_reasons == real.kernel_fallback_reasons
