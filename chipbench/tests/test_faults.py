"""A whole run with the timed path broken underneath reads ``correct``
false: for each fault a cell can have.  The single-chip cells run in this
process; the mesh cell in a subprocess with four forced host devices."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import harness
from chipbench.tests.conftest import TINY
from chipbench.tests.drive import CELLS, drive

ONE_CHIP = [w for w in CELLS if w["chips"] == 1]
MESH = [w for w in CELLS if w["chips"] == 4]
SINGLE_FAULTS = ("unchanged", "half_batch", "altered")


@pytest.mark.parametrize("fault", SINGLE_FAULTS)
@pytest.mark.parametrize("w", ONE_CHIP, ids=lambda w: w["name"])
def test_fault_reads_incorrect(w, fault):
    n, tile = TINY[w["traffic"]]
    out = drive(w["name"], n, tile, fault)
    assert out["correct"] is False
    assert out["failed"] >= 1
    assert any(not c["value"] <= c["limit"] for c in out["checks"].values())


def _mesh_run(name, fault=None):
    n, tile = TINY["sharded4_t512"]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(harness.ROOT / "src"), str(harness.ROOT)]))
    args = [sys.executable, "-m", "chipbench.tests.drive", name, str(n),
            str(tile)] + ([fault] if fault else [])
    p = subprocess.run(args, env=env, cwd=harness.ROOT, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", (None, "no_exchange") + SINGLE_FAULTS)
@pytest.mark.parametrize("w", MESH, ids=lambda w: w["name"])
def test_mesh_cell(w, fault):
    out = _mesh_run(w["name"], fault)
    assert out["device"]["count"] == 4
    assert out["correct"] is (fault is None)
