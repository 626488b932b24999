#!/usr/bin/env python3
"""Spreads of the end-to-end metrics over sets of runs, as the bound's
rule reads them: ``python3 perfbench/tools/spread.py set1.jsonl set2.jsonl``
(each file the result lines of one set of runs of one cell). A spread is the
distance between the first and third quartile (``statistics.quantiles``,
n=4) as a share of the median; the bound is about five times the wider of
the two sets' spreads."""

from __future__ import annotations

import json
import statistics
import sys


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> None:
    sets = []
    for path in sys.argv[1:]:
        with open(path) as f:
            sets.append([json.loads(line) for line in f if line.strip()])
    names = list(sets[0][0]["metrics"])
    for name in names:
        row = []
        for runs in sets:
            vals = [r["metrics"][name]["value"] for r in runs]
            row.append((statistics.median(vals), spread(vals), len(vals)))
        widest = max(s for _, s, _ in row)
        print(f"{name}: " + "  ".join(f"median {m:.6g} spread {100 * s:.3f}% (n={n})"
                                      for m, s, n in row)
              + f"  -> 5 x widest {100 * 5 * widest:.2f}%"
              + (f"  second/first median {row[1][0] / row[0][0]:.4f}" if len(row) > 1 else ""))
    for i, runs in enumerate(sets):
        bad = [r["seed"] for r in runs if not r["correct"]]
        print(f"set {i + 1}: {len(runs)} runs, correct false on seeds {bad}")


if __name__ == "__main__":
    main()
