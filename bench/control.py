"""Read a cell's control: the reference in the program's place.

    python bench/control.py --workload <cell> --calls <n> --seeds <s> ...

For each seed it builds the run's graph and traffic as ``bench/run.py``
does, computes the control's output (``Traffic.control``: the reference
one step below the precision or guarantee that the configuration states)
for the first ``--calls`` calls of the window, and prints the numbers
that ``correct`` compares beside their limits. A control that stays
within every limit would make the comparison useless. Needs no chip:
the control is computed on the host, at the cell's own size.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)

from bench import cells, graph                                # noqa: E402


def readings(cell: cells.Cell, seed: int, calls: int) -> dict:
    g = graph.build(cell.config, seed)
    t = cells.client(cell.traffic["client"]).Traffic(
        g, cell.config, cell.traffic, seed)
    items = [t.items[i % len(t.items)] for i in range(calls)]
    return t.check([(k, t.control(k)) for k in items])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--calls", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = cells.resolve(cells.load_benchmark(), args.workload)
    failed_all = True
    for seed in args.seeds:
        numbers = readings(cell, seed, args.calls)
        limits = cell.traffic["limits"]
        over = {k: v for k, v in numbers.items() if v > limits[k]}
        failed_all &= bool(over)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "calls": args.calls, "control": numbers,
                          "limits": limits, "fails": bool(over)}))
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
