"""Pytest set-up for the benchmark's tests.

``chipbench/tests/drive.py`` adds to the benchmark's cells the
``HELD_BACK`` entries of the gemm cells from before those cells joined
``BENCHMARK.json``, and finds a cell by its name.  A cell the benchmark
now holds is driven from its own entry, so its held-back twin is dropped
from ``drive.CELLS``, the list every test module shares, before the
tests are collected.  This file goes with ``HELD_BACK``.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def pytest_configure(config):
    from chipbench.tests import drive
    benched = {w["name"] for w in drive.BENCH["workloads"]}
    drive.CELLS[:] = drive.BENCH["workloads"] + [
        w for w in drive.HELD_BACK if w["name"] not in benched]
