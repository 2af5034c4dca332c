"""Quartile spreads of each end-to-end metric over runs recorded by
``series.py``, per cell and per set, as a share of the median.

  python chipbench/tools/spread.py RUNS.jsonl [RUNS.jsonl ...]

Each file is one set.  A spread is (Q3 - Q1) / median with Python's
``statistics.quantiles(values, n=4)``; the bound for a metric is about
five times the wider of the two sets' spreads, and never under 1%.
"""
import json
import statistics
import sys
from collections import defaultdict


def main(paths) -> int:
    for path in paths:
        vals = defaultdict(list)
        with open(path) as f:
            for line in f:
                rec = json.loads(line)
                res = rec.get("result")
                if not res or rec["run"].endswith(":1"):
                    continue
                cell = rec["run"].split(":")[0]
                for k, v in res["metrics"].items():
                    vals[(cell, k)].append(v["value"])
        for (cell, k), v in sorted(vals.items()):
            if len(v) < 3:
                print(f"{path} {cell} {k}: {len(v)} runs")
                continue
            q1, med, q3 = statistics.quantiles(v, n=4)
            print(f"{path} {cell} {k}: n={len(v)} median={med:.6g} "
                  f"spread={(q3 - q1) / med:.4%} values={v}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
