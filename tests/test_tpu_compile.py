"""Kernels of the main path, compiled for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler, which ships with libtpu,
compiles for a topology that is described and not attached, and refuses
what the chip's compiler would refuse (an op without a Mosaic lowering,
more VMEM than a kernel may use).  Interpret-mode tests cannot see
either.  The topology is described inside a module-scoped fixture — never
while a module is imported — and the tests skip from there where libtpu
cannot describe it.
"""
from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import RuntimeConfig, TaskRuntime, task, wavekernel
from repro.kernels.matmul import kernel as mm_kernel
from repro.kernels.matmul import ref as mm_ref


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _struct(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _gemm_body(c, x, y):
    return mm_ref.matmul(x, y, c)


def _compiles_to_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@task(inout="c", in_=("x", "y"))
def _gemm(c, x, y):
    return _gemm_body(c, x, y)


def test_describes_a_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"


def test_gemm_wave_kernel_8x512(one_chip):
    """The matmul app's gemm body, fused over a wave of 8 tasks on 512^2
    f32 tiles (8 MiB of double-buffered blocks per grid step)."""
    ins = [_struct(one_chip, (8, 512, 512)) for _ in range(3)]
    compiled = wavekernel.compile_wave_kernel(_gemm_body, ins, 1,
                                              interpret=False, label="gemm")
    assert _compiles_to_kernel(compiled)


def test_firstprivate_scalars_ride_in_smem(one_chip):
    """Firstprivate scalars stack to an (n,) vector held whole in SMEM;
    as (1,) VMEM blocks the compiler refused them."""
    def body(c, x, s, k):
        return c + (s * k.astype(jnp.float32)) * x

    ins = [_struct(one_chip, (8, 512, 512)), _struct(one_chip, (8, 512, 512)),
           _struct(one_chip, (8,)), _struct(one_chip, (8,), jnp.int32)]
    compiled = wavekernel.compile_wave_kernel(body, ins, 1, interpret=False,
                                              label="scaled")
    assert _compiles_to_kernel(compiled)


def test_tile_update_pallas_512(one_chip):
    c, a, b = (_struct(one_chip, (512, 512)) for _ in range(3))
    compiled = mm_kernel.tile_update_pallas.lower(c, a, b).compile()
    assert _compiles_to_kernel(compiled)


def test_matmul_pallas_4096(one_chip):
    a, b, c = (_struct(one_chip, (4096, 4096)) for _ in range(3))
    compiled = mm_kernel.matmul_pallas.lower(a, b, c).compile()
    assert _compiles_to_kernel(compiled)


def test_vmem_budget_names_the_group_the_compiler_refuses(one_chip):
    """8 gemm tasks on 1024^2 f32 tiles need 2 x 16 MiB of blocks per
    grid step: eligibility names the group "vmem_budget", and the
    compiler, asked anyway, refuses it for VMEM."""
    rt = TaskRuntime(RuntimeConfig(executor="staged",
                                   kernel_backend="pallas"))
    with rt.scope():
        C = rt.zeros((8 * 1024, 1024), (1024, 1024))
        A = rt.zeros((8 * 1024, 1024), (1024, 1024))
        B = rt.zeros((1024, 1024), (1024, 1024))
        for i in range(8):
            _gemm(C[i, 0], A[i, 0], B[0, 0])
        group = list(rt._exec.pending)
        assert wavekernel.eligibility(group) == "vmem_budget"
        rt._exec.pending.clear()
    rt.shutdown()

    ins = [_struct(one_chip, (8, 1024, 1024)) for _ in range(3)]
    with pytest.raises(wavekernel.WaveKernelError) as err:
        wavekernel.compile_wave_kernel(_gemm_body, ins, 1, interpret=False,
                                       label="gemm")
    assert err.value.reason == "compile_refused"
    assert "vmem" in err.value.detail.lower()


@pytest.mark.parametrize("body, n_in, op", [
    (lambda l, a: jax.scipy.linalg.solve_triangular(l, a.T, lower=True).T,
     2, "triangular_solve"),
    (lambda x: x.at[1:-1, 1:-1].set(0.5 * x[1:-1, 1:-1]), 1, "scatter"),
], ids=["trsm", "jacobi_scatter"])
def test_bodies_without_a_mosaic_lowering_are_named(one_chip, body, n_in,
                                                    op):
    ins = [_struct(one_chip, (8, 512, 512)) for _ in range(n_in)]
    with pytest.raises(wavekernel.WaveKernelError) as err:
        wavekernel.compile_wave_kernel(body, ins, 1, interpret=False,
                                       label=op)
    assert err.value.reason == "compile_refused"
    assert op in err.value.detail
