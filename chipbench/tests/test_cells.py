"""Each cell, cut to a tiny n, runs through the harness and passes its
comparison with the plain reference."""

import pytest

from chipbench.tests.conftest import TINY
from chipbench.tests.drive import CELLS, drive

ONE_CHIP = [w for w in CELLS if w["chips"] == 1]


@pytest.mark.parametrize("w", ONE_CHIP, ids=lambda w: w["name"])
def test_cell_solves_and_checks(w):
    n, tile = TINY[w["traffic"]]
    out = drive(w["name"], n, tile)
    assert out["correct"] is True
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"solve_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("w", ONE_CHIP[:1], ids=lambda w: w["name"])
def test_traced_run_reports_host_metrics(w):
    n, tile = TINY[w["traffic"]]
    out = drive(w["name"], n, tile, trace=True)
    assert out["correct"] is True
    m = out["metrics"]
    assert m["compiles_in_window"]["value"] == 0
    assert m["master_us_per_task"]["value"] > 0
    assert m["dispatch_us_per_task"]["value"] > 0
    # no device planes in a CPU trace: the device readers find nothing
    assert "device_idle_share" not in m and "update_roofline" not in m
    assert "breakdown" in out and "window_s" in out["device"]
