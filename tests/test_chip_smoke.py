"""``chip_smoke.py`` rehearsed on the CPU at tiny sizes.

The script itself refuses anything but a TPU; these tests drive its phase
functions directly (Pallas in interpret mode), run the four-device
sharded comparison on forced host devices in a subprocess, and check the
refusals: no ``ok`` line and a non-zero exit without a chip, or without
the rest of the checkout.
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

import jax

import chip_smoke

ROOT = pathlib.Path(__file__).resolve().parent.parent

TINY = {
    "cholesky": {"n": 64, "tile": 16},
    "matmul": {"n": 64, "tile": 16},
    "jacobi": {"n": 64, "tile": 16, "iters": 2},
    "fft": {"n": 64, "row_block": 16, "tile": 16},
    "black_scholes": {"n_options": 4096, "task_options": 512},
}


@pytest.mark.parametrize("phase", chip_smoke.ONE_CHIP,
                         ids=[p.name for p in chip_smoke.ONE_CHIP])
def test_phase_runs_tiny_on_cpu(phase):
    rep = chip_smoke.run_phase(
        dataclasses.replace(phase, sizes=TINY[phase.app]), 3, jax.devices())
    assert rep["numerics"] == "checked"
    assert rep["platform"] == "cpu"
    # a CPU wall time is never labelled as a chip number
    assert "cpu_wall_s_cold" in rep and "tpu_wall_s_cold" not in rep
    assert rep["tasks"] > 0 and rep["waves"] > 0
    if phase.kernel_backend == "pallas":
        assert rep["kernel_dispatches"] > 0
        assert rep["kernel_fallbacks"] == \
            sum(rep["kernel_fallback_reasons"].values())


def test_sharded_compare_on_four_host_devices():
    code = (
        "import json, chip_smoke, jax\n"
        f"rep = chip_smoke.sharded_compare({TINY['cholesky']!r}, 1, "
        "jax.devices())\n"
        "print(json.dumps(rep))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    assert sorted(rep["tiles_per_device"].values()) == [4, 4, 4, 4]
    assert rep["traffic_incl_readback"]["bytes_staged"] == 0
    assert rep["waves_traffic"]["tile_moves"] > 0
    assert rep["max_abs_diff_vs_staged"] <= rep["diff_tol"]


def _run_script(cwd) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_without_a_tpu():
    out = _run_script(ROOT)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "not 'tpu'" in out.stderr


def test_refuses_without_the_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out = _run_script(tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


class _FakeTPU:
    platform = "tpu"
    device_kind = "TPU v5 lite"


def test_refuses_the_interpret_flag(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert "REPRO_PALLAS_INTERPRET" in chip_smoke._refuse([_FakeTPU()])


def test_refuses_interpret_mode(monkeypatch):
    # a TPU device reported while JAX's default backend is the CPU:
    # the wave kernels would run under the Pallas interpreter
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert "interpret mode" in chip_smoke._refuse([_FakeTPU()])


class _Trk:
    def __init__(self, events):
        self._events = events

    def events_of(self, kind):
        return [type("E", (), {"data": d}) for d in self._events]


class _Stats:
    kernel_dispatches = 3
    kernel_fallbacks = 2
    kernel_fallback_reasons = {"single_task": 1, "compile_refused": 1}


def _fallback(fn, reason, error=""):
    d = {"backend": "xla", "fn": fn, "tasks": 8, "reason": reason}
    if error:
        d["error"] = error
    return d


def test_pallas_rule_allows_named_refusals_of_other_bodies():
    phase = chip_smoke.Phase("cholesky/pallas", "cholesky", TINY["cholesky"],
                             kernel_backend="pallas")
    rep = chip_smoke._pallas_report(phase, _Stats(), _Trk([
        _fallback("_update", "single_task"),
        _fallback("_trsm", "compile_refused", "_trsm: no triangular_solve")]))
    assert rep["compile_refused"] == {"_trsm": "_trsm: no triangular_solve"}


@pytest.mark.parametrize("reason", ["compile_refused", "vmem_budget"])
def test_pallas_rule_rejects_fused_body_fallback(reason):
    phase = chip_smoke.Phase("matmul/pallas", "matmul", TINY["matmul"],
                             kernel_backend="pallas")
    with pytest.raises(AssertionError, match="_gemm"):
        chip_smoke._pallas_report(phase, _Stats(),
                                  _Trk([_fallback("_gemm", reason)]))
