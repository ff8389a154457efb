#!/usr/bin/env python3
"""One run of one benchmark cell in one process.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up, measures for ``--seconds`` and prints the result as the
last line of standard output: one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and ``breakdown``
with ``--trace 1``).  Without as many TPU chips as the cell asks for, or on
a device kind that ``roofline/peaks.json`` does not know, it prints no result
and exits with code 3.  ``--tiny`` rehearses the cell's control flow at toy
sizes on whatever platform is there; its line has no ``metrics`` and no
``device``, so it cannot be read as a result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes on any platform: a rehearsal, never a "
                         "result")
    args = ap.parse_args(argv)

    from benchmarks.harness import common

    cell = common.load_cell(args.workload, args.tiny)
    runner = importlib.import_module(
        f"benchmarks.harness.{cell['config']['runner']}")
    try:
        result = runner.run(cell, args, T_START)
    except common.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    if args.tiny:
        common.print_result({
            "tiny": True, "correct": result["correct"],
            "attempted": result["attempted"], "failed": result["failed"],
            "rehearsed": sorted(result["metrics"])})
        return 0
    common.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
