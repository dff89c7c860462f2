"""Tiled GEMM (``C += A @ B``, PLASMA ``dgemm``'s task graph) through the
``@task`` runtime, as the chip benchmark's ``dgemm_f32_n8192``
configuration runs it, and the group programs' operand counters.

The product is checked against one plain ``C0 + A @ B`` at HIGHEST,
within the configuration's own limits, on the staged executor and on the
sharded one over a forced 2-device mesh.  ``group_operand_tiles`` and
``group_distinct_tiles`` are pinned exactly: a GEMM wave ``k`` is one
group of ``g^2`` tasks reading ``C[i, j]``, ``A[i, k]`` and ``B[k, j]``,
so ``3 g^2`` operands of which ``g^2 + 2 g`` are distinct, over ``g``
waves.
"""
import functools
import json
import pathlib
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.programs import gemm, potrf
from repro.core import TaskRuntime

ROOT = pathlib.Path(__file__).resolve().parent.parent
LIMITS = json.loads(
    (ROOT / "chipbench/configs/dgemm_f32_n8192.json").read_text())["limits"]
N = 256


def gemm_probe(executor: str, tile: int, seed: int = 7) -> dict:
    """One product of ``n = 256`` through the runtime: its gaps to the
    plain reference and the runtime's counters."""
    rng = np.random.default_rng(seed)
    a, b, c0 = (rng.standard_normal((N, N), dtype=np.float32)
                for _ in range(3))
    want = np.asarray(c0 + jnp.matmul(a, b, precision="highest"),
                      np.float64)
    rt = TaskRuntime(executor=executor)
    arrays = {k: rt.from_array(v, (tile, tile), name=k)
              for k, v in zip("ABC", (a, b, c0))}
    with rt.scope():
        gemm.spawn(arrays, N // tile)
    rt.barrier()
    got = np.asarray(arrays["C"].gather(), np.float64)
    s = rt.stats()
    rt.shutdown()
    d = got - want
    return {"product_gap": float(abs(d).max() / abs(want).max()),
            "product_fro_gap": float(np.linalg.norm(d)
                                     / np.linalg.norm(want)),
            "group_program_tasks": s.group_program_tasks,
            "group_operand_tiles": s.group_operand_tiles,
            "group_distinct_tiles": s.group_distinct_tiles}


@functools.lru_cache(maxsize=None)
def _gemm_run(executor: str, tile: int) -> dict:
    """Staged in this process; sharded on a forced 2-device mesh in a
    subprocess (the device count is fixed before JAX starts)."""
    if executor == "staged":
        return gemm_probe(executor, tile)
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json, sys
sys.path[:0] = ["src", ".", "tests"]
import jax, numpy as np
import conftest, test_tiled_gemm
from repro import dist
assert jax.device_count() == 2
mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("data",))
with dist.use_mesh(mesh):
    print(json.dumps(test_tiled_gemm.gemm_probe("sharded", {tile})))
"""
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("tile", [64, 128])
@pytest.mark.parametrize("executor", ["staged", "sharded"])
def test_gemm_matches_the_plain_product(executor, tile):
    run = _gemm_run(executor, tile)
    for k, limit in LIMITS.items():
        assert run[k] <= limit, (k, run[k], limit)


@pytest.mark.parametrize("tile", [64, 128])
def test_gemm_group_operand_counters(tile):
    g = N // tile
    run = _gemm_run("staged", tile)
    assert run["group_program_tasks"] == g ** 3
    assert run["group_operand_tiles"] == 3 * g ** 3
    assert run["group_distinct_tiles"] == g ** 2 * (g + 2)


def test_cholesky_group_operand_counters():
    """Cholesky at g = 3 runs two group programs: the trsm wave of 2
    tasks (A00 shared, A10, A20: 4 operands, 3 distinct) and the first
    update wave of 3 (A11; A10 A10, A21; A20 A10, A22; A20 A20: 9
    operands, 5 distinct).  The potrfs and the later trsm and update run
    alone, outside any group program."""
    rng = np.random.default_rng(3)
    m = rng.standard_normal((48, 48)).astype(np.float32)
    rt = TaskRuntime(executor="staged")
    arrays = {"A": rt.from_array(m @ m.T / 48 + np.eye(48, dtype=np.float32),
                                 (16, 16))}
    with rt.scope():
        potrf.spawn(arrays, 3)
    rt.barrier()
    s = rt.stats()
    rt.shutdown()
    assert (s.group_program_tasks, s.group_operand_tiles,
            s.group_distinct_tiles) == (5, 13, 8)


def test_counters_start_at_zero_and_stay_off_other_executors():
    rt = TaskRuntime(executor="staged")
    s = rt.stats()
    assert (s.group_operand_tiles, s.group_distinct_tiles) == (0, 0)
    rt.shutdown()
    rt = TaskRuntime(executor="sequential")
    s = rt.stats()
    assert s.group_operand_tiles is None and s.group_distinct_tiles is None
    rt.shutdown()
