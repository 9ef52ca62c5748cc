#!/usr/bin/env python3
"""Spreads of two sets of runs, as the builder's contract defines them:

    python3 benchmark/tools/spread.py chiprun_out/live

reads ``<prefix>_A_<seed>.out`` and ``<prefix>_B_<seed>.out`` (what
``sets.sh`` wrote), and prints for every metric each set's median
and spread (inter-quartile distance over the median), the wider of the
two, the bound that would follow (five times it, never under 1 %), the
check's own two statistics (``tight``: each set's run farthest from its
median left out, the two sets' mean — a bound under twice it is too
tight; ``loose``: the spread of all the runs — a bound over eight times
it is too loose), how far the second set's median lies from the
first's, and whether every run was correct.
"""

import glob
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.layers import spread  # noqa: E402


def last_line(path):
    with open(path) as f:
        lines = [ln for ln in f.read().strip().splitlines() if ln]
    try:
        out = json.loads(lines[-1])
    except (IndexError, ValueError):
        return None
    return out if "metrics" in out else None


def without_farthest(values):
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return [v for i, v in enumerate(values) if i != far]


def main(prefix: str) -> int:
    sets = {}
    for s in "AB":
        files = sorted(glob.glob(f"{prefix}_{s}_*.out"))
        sets[s] = [(f, last_line(f)) for f in files]
    bad = [f for runs in sets.values() for f, o in runs
           if o is None or not o["correct"]]
    names = sorted({n for runs in sets.values() for _, o in runs if o
                    for n in o["metrics"]})
    for n in names:
        vals = {s: [o["metrics"][n]["value"] for _, o in runs
                    if o and n in o["metrics"]] for s, runs in sets.items()}
        row = {"metric": n}
        for s, v in vals.items():
            if len(v) >= 2:
                row[f"median_{s}"] = statistics.median(v)
                row[f"spread_{s}"] = round(spread(v), 5)
                row[f"n_{s}"] = len(v)
        if "spread_A" in row and "spread_B" in row:
            widest = max(row["spread_A"], row["spread_B"])
            row["widest"] = widest
            row["bound_5x"] = round(max(0.01, 5 * widest), 4)
            row["tight"] = round(statistics.mean(
                spread(without_farthest(v)) for v in vals.values()), 5)
            row["loose"] = round(spread(vals["A"] + vals["B"]), 5)
            row["median_B_over_A"] = round(
                row["median_B"] / row["median_A"] - 1.0, 5)
        print(json.dumps(row))
    print(json.dumps({"runs": {s: len(r) for s, r in sets.items()},
                      "not_correct_or_no_result": bad}))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
