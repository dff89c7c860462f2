"""Drive a whole benchmark run at a tiny size on the CPU, past the run's
refusal of a non-TPU platform, optionally with the timed path broken.

    python -m chipbench.tests.drive <cell> <n> <tile> [fault]

prints the result line (used by the tests in a subprocess where the run
needs four forced host devices).
"""
from __future__ import annotations

import contextlib
import json
import sys
import time
from unittest import mock

from chipbench import harness

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
#: cells whose program and reference are kept here though the benchmark
#: leaves them out: each of their windows runs the chip out of memory
#: (the staged executor keeps every solve's task outputs alive)
HELD_BACK = [
    {"name": "gemm.n8192.t512", "config": "gemm_f32_n8192",
     "traffic": "staged_t512", "chips": 1},
    {"name": "gemm.n8192.t2048", "config": "gemm_f32_n8192",
     "traffic": "staged_t2048", "chips": 1},
]
CELLS = BENCH["workloads"] + HELD_BACK

#: the reference's block at the tests' sizes, by program
REF_BLOCK = {"potrf": 96}


def tiny_cell(w: dict, n: int, tile: int) -> harness.Cell:
    """The cell of workload entry ``w`` cut to ``n`` and ``tile``."""
    cell = harness.make_cell(w, BENCH)
    cell.config = dict(cell.config, n=n)
    if cell.config["program"] in REF_BLOCK:
        cell.config["ref_block"] = REF_BLOCK[cell.config["program"]]
    cell.traffic = dict(cell.traffic, tile=tile)
    return cell


# -- faults planted under the timed path ---------------------------------------
@contextlib.contextmanager
def unchanged():
    """Every task's results are dropped: the solve leaves its state as the
    input was."""
    with mock.patch("repro.core.blocks.Region.store", lambda self, v: None):
        yield


@contextlib.contextmanager
def half_batch():
    """Each batched wave group stores only its first half of tasks."""
    from repro.core.executor import StagedExecutor
    orig = StagedExecutor._store_group

    def store(self, group, result):
        from repro.core.graph import normalize_outputs
        k = len(group) // 2 or 1
        res = normalize_outputs(result, len(group[0].outputs), "")
        kept = tuple(r[:k] for r in res)
        orig(self, group[:k], kept if len(kept) > 1 else kept[0])
        for td in group[k:]:
            td.output_values = tuple(r[0] for r in res)

    with mock.patch.object(StagedExecutor, "_store_group", store):
        yield


@contextlib.contextmanager
def no_exchange():
    """Tiles homed on another chip are not sent: the consumer reads
    zeros in their place."""
    import jax
    import jax.numpy as jnp
    from repro.core import blocks
    orig = blocks._pull_tiles

    def pull(tiles, device, traffic, tile_nbytes, staged=False):
        if device is None:
            return orig(tiles, device, traffic, tile_nbytes, staged)
        return [t if blocks.device_of(t) in (None, device)
                else jax.device_put(jnp.zeros_like(t), device)
                for t in tiles]

    with mock.patch.object(blocks, "_pull_tiles", pull):
        yield


@contextlib.contextmanager
def altered():
    """In every solve, one entry of the result of the task that writes tile
    (0, 0) is off by a thousandth of the tile's largest entry, where the
    runtime commits it."""
    from repro.core.blocks import Region
    orig = Region.store

    def store(self, value):
        if self.tile_indices[0] == (0, 0):
            value = value.at[0, 0].add(1e-3 * abs(value).max())
        orig(self, value)

    with mock.patch.object(Region, "store", store):
        yield


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "no_exchange": no_exchange, "altered": altered}


def drive(name: str, n: int, tile: int, fault: str | None = None,
          seconds: float = 0.5, seed: int = 2 ** 33 + 17,
          trace: bool = False) -> dict:
    import io

    import jax
    (w,) = [w for w in CELLS if w["name"] == name]
    cell = tiny_cell(w, n, tile)
    counter = harness.CompileCounter().install()
    ctx = FAULTS[fault]() if fault else contextlib.nullcontext()
    with ctx:
        return harness.run_cell(cell, seed, seconds, trace, jax.devices(),
                                time.perf_counter(), counter,
                                log=io.StringIO())


if __name__ == "__main__":
    name, n, tile = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    fault = sys.argv[4] if len(sys.argv) > 4 else None
    print(json.dumps(drive(name, n, tile, fault)))
