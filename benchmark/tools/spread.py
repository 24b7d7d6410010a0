"""The spread of each metric over the runs a call of sets.sh left behind: the
distance between the first and the third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median, per set.

    python3 -m benchmark.tools.spread chiprun_out/<label> [chiprun_out/<label2> ...]
"""

from __future__ import annotations

import glob
import json
import statistics
import sys


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(dirs):
    for d in dirs:
        runs = {}
        for path in sorted(glob.glob(f"{d}/*.log")):
            last = open(path).read().strip().splitlines()[-1:]
            try:
                line = json.loads(last[0])
            except (IndexError, ValueError):
                print(f"{path}: no result line")
                continue
            cell = path.rsplit("/", 1)[1].split(".t")[0]
            for name, m in line["metrics"].items():
                runs.setdefault((cell, name), []).append(m["value"])
            if not line["correct"]:
                print(f"{path}: correct is false")
        for (cell, name), v in sorted(runs.items()):
            s = f"{spread(v):.4%}" if len(v) >= 2 else "-"
            print(f"{d} {cell} {name}: n {len(v)} median {statistics.median(v):.6g} "
                  f"spread {s} min {min(v):.6g} max {max(v):.6g}")


if __name__ == "__main__":
    main(sys.argv[1:])
