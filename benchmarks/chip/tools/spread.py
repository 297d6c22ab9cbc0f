#!/usr/bin/env python3
"""Spread of each end-to-end metric over sets of runs, as the bounds are
set from: per set the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median.

  python3 benchmarks/chip/tools/spread.py SET1_FILES -- SET2_FILES

Each file holds one run's standard output; its last line is the result.
"""
import json
import statistics
import sys


def load(paths):
    rows = []
    for p in paths:
        with open(p) as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
        rows.append(json.loads(lines[-1]))
    return rows


def spread(values):
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med, statistics.median(values)


def main():
    args = sys.argv[1:]
    cut = args.index("--")
    sets = [load(args[:cut]), load(args[cut + 1:])]
    names = sorted(sets[0][0]["metrics"])
    for name in names:
        out = []
        for s in sets:
            vals = [r["metrics"][name]["value"] for r in s]
            sp, med = spread(vals)
            out.append({"median": med, "spread": sp, "values": vals})
        widest = max(o["spread"] for o in out)
        print(json.dumps({"metric": name, "widest_spread": widest,
                          "bound_5x": max(0.01, 5 * widest),
                          "sets": out}))
    for i, s in enumerate(sets, 1):
        print(json.dumps({"set": i, "correct": [r["correct"] for r in s],
                          "checks": [{k: v["value"] for k, v in
                                      r["checks"].items()} for r in s]}))


if __name__ == "__main__":
    main()
