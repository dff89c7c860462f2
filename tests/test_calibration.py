"""The calibrated sim-executor cost path (ISSUE 4): flopcount-derived
default task costs, DES monotonicity in contention and hop distance, the
paper trends on real ``@task`` programs under ``executor="sim"``, and the
``SCCParams`` fit against the paper's microbenchmark anchors."""
import dataclasses

import numpy as np
import pytest

from repro.core import RuntimeConfig, TaskRuntime, task
from repro.core.calibrate import (CalibrationError, FIG3_LATENCY_CYCLES,
                                  FIG4_SLOWDOWN, calibrate, fit_params,
                                  granularity_sweep, validate_trends)
from repro.core.costmodel import (SCCParams, core_mc_hops,
                                  master_core_choice, tpu_params,
                                  worker_order)
from repro.core.sim import FlopcountCost, SimExecutor, SimTask, simulate

import sys
sys.path.insert(0, ".")
from benchmarks.apps import run_app  # noqa: E402


@task(out="c", in_=("a", "b"))
def _pure_gemm(a, b, c=None):
    return a @ b


@task(inout="x", firstprivate="r0")
def _sliced(x, r0):
    import jax
    return jax.lax.dynamic_update_slice(
        x, jax.lax.dynamic_slice(x, (r0, 0), (1, x.shape[1])) * 2.0,
        (r0, 0))


@task(inout="x")
def _untraceable(x):
    # concrete-value branch: jax.make_jaxpr cannot trace this body
    if float(np.asarray(x).sum()) > 0:
        return x + 1.0
    return x - 1.0


def _first_descriptor(spawn):
    """Spawn inside a sim runtime; return (descriptor, executor)."""
    rt = TaskRuntime(RuntimeConfig(executor="sim"))
    try:
        with rt.scope():
            spawn(rt)
            return rt._exec.pending[0], rt._exec
    finally:
        rt.shutdown()


class TestFlopcountCost:
    def test_gemm_tile_cost_is_2mnk(self):
        """The satellite check: flopcount-derived gemm cost is exactly
        the analytic 2*M*N*K (non-square to catch dimension mixups)."""
        M, K, N = 32, 16, 24

        def spawn(rt):
            A = rt.zeros((M, K), (M, K))
            B = rt.zeros((K, N), (K, N))
            C = rt.zeros((M, N), (M, N))
            _pure_gemm(A[0, 0], B[0, 0], C[0, 0])

        td, _ = _first_descriptor(spawn)
        flops, nbytes = FlopcountCost()(td)
        assert flops == 2.0 * M * N * K
        # DRAM traffic covers at least the footprint: two reads + a write
        assert nbytes >= 4 * (M * K + K * N + M * N)

    def test_default_cost_is_flopcount(self):
        """executor="sim" without sim_cost_fn uses FlopcountCost."""
        rt = TaskRuntime(RuntimeConfig(executor="sim"))
        try:
            assert isinstance(rt._exec.cost_fn, FlopcountCost)
        finally:
            rt.shutdown()

    def test_cost_traced_once_per_structure(self):
        fc = FlopcountCost()

        def spawn(rt):
            A = rt.zeros((8, 8), (4, 4))
            B = rt.zeros((8, 8), (4, 4))
            C = rt.zeros((8, 8), (4, 4))
            for i in range(2):
                for j in range(2):
                    _pure_gemm(A[i, 0], B[0, j], C[i, j])

        rt = TaskRuntime(RuntimeConfig(executor="sim"))
        try:
            with rt.scope():
                spawn(rt)
                costs = {fc(td) for td in rt._exec.pending}
                assert len(rt._exec.pending) == 4
                assert len(costs) == 1          # same structure, same cost
                assert len(fc._cache) == 1      # one trace covered all
        finally:
            rt.shutdown()

    def test_firstprivate_values_enter_the_trace(self):
        def spawn(rt):
            X = rt.zeros((8, 8), (8, 8))
            _sliced(X[0, 0], 3)

        td, _ = _first_descriptor(spawn)
        flops, nbytes = FlopcountCost()(td)
        assert flops > 0 and nbytes >= 8 * 8 * 4

    def test_untraceable_body_falls_back_to_footprint(self):
        def spawn(rt):
            X = rt.zeros((8, 8), (8, 8))
            _untraceable(X[0, 0])

        td, _ = _first_descriptor(spawn)
        fc = FlopcountCost()
        assert fc(td) == SimExecutor._footprint_cost(td)
        assert fc._cache[fc._key(td)] is None   # remembered as untraceable


class TestSimMonotone:
    """DES predictions move the right way with contention and distance."""

    def _stream(self, home=0, n=64):
        return [SimTask(tid=i, flops=1e3, mem_bytes=1e6, homes=(home,))
                for i in range(n)]

    def test_sim_time_monotone_in_contention(self):
        alphas = (0.1, 0.3, 0.55, 0.9)
        times = [simulate(self._stream(), 8,
                          dataclasses.replace(SCCParams(),
                                              contention_alpha=a)).total_s
                 for a in alphas]
        assert all(b > a for a, b in zip(times, times[1:]))

    def test_sim_time_monotone_in_hop_distance(self):
        w0 = worker_order(master_core_choice())[0]
        hops = [core_mc_hops(w0, m) for m in range(4)]
        near, far = int(np.argmin(hops)), int(np.argmax(hops))
        assert hops[near] < hops[far]
        p = SCCParams()
        t_near = simulate(self._stream(home=near, n=4), 1, p).total_s
        t_far = simulate(self._stream(home=far, n=4), 1, p).total_s
        assert t_far > t_near

    def test_sim_params_reach_the_executor(self):
        """RuntimeConfig.sim_params swaps the cost model under the DES."""
        slow = dataclasses.replace(SCCParams(), freq_hz=533e6 / 4)
        s_fast = run_app("matmul", "sim", n_workers=8,
                         app_kwargs={"n": 128, "tile": 32})
        s_slow = run_app("matmul", "sim", n_workers=8, sim_params=slow,
                         app_kwargs={"n": 128, "tile": 32})
        assert s_slow.predicted_total_s > 2.0 * s_fast.predicted_total_s


class TestSimAppTrends:
    """The acceptance criterion: executor="sim" with the default
    flopcount cost reproduces the paper's two trends on real programs."""

    def test_gemm_app_striped_beats_single(self):
        kw = {"app_kwargs": {"n": 256, "tile": 64}, "n_workers": 16}
        striped = run_app("matmul", "sim", placement="striped", **kw)
        single = run_app("matmul", "sim", placement="single", **kw)
        assert striped.predicted_total_s < single.predicted_total_s

    def test_granularity_sweep_has_interior_optimum(self):
        rows = granularity_sweep(fit_params().params)
        best = max(range(len(rows)), key=lambda i: rows[i]["speedup"])
        assert 0 < best < len(rows) - 1


class TestCalibrate:
    def test_fit_recovers_anchor_shape(self):
        r = fit_params()
        assert 10 < r.params.dram_hop_cycles < 25
        assert 200 < r.params.dram_base_cycles < 300
        assert 0.4 < r.params.contention_alpha < 0.7
        assert r.fig3_max_rel_err < 0.05
        assert r.fig4_max_rel_err < 0.05

    def test_fit_is_exact_on_synthetic_anchors(self):
        fig3 = {h: 300.0 + 20.0 * h for h in range(0, 9, 2)}
        fig4 = {c: 1.0 + 0.4 * (c - 1) for c in (1, 2, 4, 8, 16, 32)}
        r = fit_params(fig3=fig3, fig4=fig4)
        assert r.params.dram_base_cycles == pytest.approx(300.0)
        assert r.params.dram_hop_cycles == pytest.approx(20.0)
        assert r.params.contention_alpha == pytest.approx(0.4)
        assert r.fig3_max_rel_err < 1e-9
        assert r.fig4_max_rel_err < 1e-9

    def test_fit_preserves_unfitted_constants(self):
        base = dataclasses.replace(SCCParams(), flush_cycles=1234.0)
        assert fit_params(base).params.flush_cycles == 1234.0

    def test_calibrate_validates_trends(self):
        r = calibrate()
        assert r.ok
        assert set(r.checks) == {
            "fig3_latency_monotone_in_hops",
            "fig4_time_monotone_in_contention",
            "striped_beats_single",
            "granularity_interior_optimum",
        }
        d = r.as_dict()
        assert all(d["checks"].values())

    def test_calibrate_raises_when_a_finding_breaks(self):
        """A master-dominated model loses both placement sensitivity and
        the interior granularity optimum — calibrate must refuse it."""
        broken = dataclasses.replace(SCCParams(), spawn_base_cycles=5e6,
                                     schedule_cycles=5e5)
        with pytest.raises(CalibrationError, match="no longer reproduce"):
            calibrate(base=broken)

    def test_validate_trends_flags_disabled_contention(self):
        flat = dataclasses.replace(SCCParams(), contention_alpha=0.0)
        checks = validate_trends(flat)
        assert not checks["striped_beats_single"]
        assert not checks["fig4_time_monotone_in_contention"]

    def test_anchor_tables_are_well_formed(self):
        assert sorted(FIG3_LATENCY_CYCLES) == [0, 2, 4, 6, 8]
        assert FIG4_SLOWDOWN[1] == 1.0
        assert all(FIG4_SLOWDOWN[a] < FIG4_SLOWDOWN[b]
                   for a, b in zip(sorted(FIG4_SLOWDOWN),
                                   sorted(FIG4_SLOWDOWN)[1:]))


class TestTPUPeaks:
    def test_v5e_peaks_carry_their_source(self):
        hw = tpu_params("TPU v5 lite")
        assert (hw.peak_flops_bf16, hw.hbm_bw) == (197e12, 819e9)
        assert "TPU v5e" in hw.source
        terms = hw.roofline_terms(197e12, 819e9, 0.0)
        assert terms == {"compute_s": 1.0, "memory_s": 1.0,
                         "collective_s": 0.0}

    @pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
    def test_unknown_device_kind_raises(self, kind):
        with pytest.raises(ValueError, match="no published peaks"):
            tpu_params(kind)
