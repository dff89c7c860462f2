"""The check's control: the plain reference in the program's place,
computed one precision below the configuration's (three bf16 passes for
f32 at HIGHEST, :func:`chipbench.refs.dots.three_pass`).  Its readings of
each compared number must lie above the number's limit.

    python chipbench/control.py <config> <seed> [<seed> ...]

prints one JSON line per seed with the control's readings beside the
limits, at the configuration's own size, on whatever device JAX finds.
The benchmark's runs never run it.
"""
from __future__ import annotations

import importlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(cfg: dict, seed: int, tile: int) -> dict:
    """The control's numbers for one seed: the three-pass reference's
    output, cut into ``tile``-wide tiles as the program's output comes,
    compared with the reference as a solve's output is."""
    from chipbench import harness
    from chipbench.refs import dots
    ref = importlib.import_module(f"chipbench.refs.{cfg['program']}")
    inputs = ref.make_inputs(harness.seed_key(seed), cfg)
    want = ref.reference(inputs, cfg)
    got = ref.reference(inputs, cfg, dot=dots.three_pass)
    tiles = {(i, j): got[i * tile:(i + 1) * tile, j * tile:(j + 1) * tile]
             for i, j in ref.output_indices(cfg["n"] // tile)}
    return ref.compare(tiles, want, tile)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    sys.path[:0] = [str(ROOT)]
    from chipbench import harness
    harness.enable_compile_cache()
    cfg = json.loads((ROOT / "chipbench" / "configs" / f"{argv[0]}.json")
                     .read_text())
    for seed in map(int, argv[1:]):
        t0 = time.perf_counter()
        r = readings(cfg, seed, tile=512)
        print(json.dumps({"config": argv[0], "seed": seed, "control": r,
                          "limits": cfg["limits"],
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
