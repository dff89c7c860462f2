#!/usr/bin/env python3
"""Chip benchmark of the @task runtime: time per verified tiled solve.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine and
prints, as its last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` a ``breakdown``) and, last, ``checks``: each number the
check compared, beside its limit.  With ``--trace 0`` the metrics are the
cell's end-to-end ones, with ``--trace 1`` its per-layer ones.  The same
numbers end standard error.  The run exits non-zero and prints no result
when JAX finds no TPU, or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # run as a script, Python puts chipbench/ itself first on the path,
    # where its modules would shadow top-level ones of the same name
    sys.path[:] = [str(ROOT / "src"), str(ROOT)] + [
        p for p in sys.path if Path(p or ".").resolve() != ROOT / "chipbench"]
    if not (ROOT / "src" / "repro").is_dir():
        print("chipbench: the runtime under test (src/repro) is not in "
              "this checkout", file=sys.stderr)
        return 2
    from chipbench import harness

    cell = harness.load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: platform is {devices[0].platform!r}, not a TPU",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    counter = harness.CompileCounter().install()
    print(f"chipbench: compilation cache {harness.enable_compile_cache()}",
          file=sys.stderr)
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           devices, T_PROCESS, counter)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
