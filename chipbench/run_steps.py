#!/usr/bin/env python3
"""A traced run of one benchmark cell that also reads the runtime's own
program spans.

    python3 chipbench/run_steps.py --workload <cell> --seed <n> --seconds <s>

The same run as ``chipbench/run.py ... --trace 1``, except that the
capture also keeps the step spans of ``chipbench/steps.py``: the result
line adds the six ``*_us_per_task`` metrics of ``steps.METRICS``, and
``breakdown.idle_gaps`` names the innermost program step (for example
``bddt/staged/store``) where it named a whole wave.
"""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    # as in run.py: chipbench/ itself must not shadow top-level modules
    sys.path[:] = [str(ROOT)] + [
        p for p in sys.path if Path(p or ".").resolve() != ROOT / "chipbench"]
    from chipbench import harness, run, steps

    load = harness.load_cell

    def load_cell(name, *args):
        cell = load(name, *args)
        cell.per_layer = cell.per_layer + steps.METRICS
        return cell

    harness.load_cell = load_cell
    argv = sys.argv[1:] if argv is None else list(argv)
    with steps.keep_program_spans():
        return run.main(argv + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
