"""The benchmark's own tests run on the CPU, at tiny sizes:

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q chipbench/tests
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

#: (n, tile) standing in for each cell's traffic at a size a test holds
TINY = {"staged_t512": (256, 64), "staged_t2048": (256, 128),
        "sharded4_t512": (256, 64)}
