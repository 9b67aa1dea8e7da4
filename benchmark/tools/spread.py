"""Spreads of the runs `sets.sh` made, by the rule a bound is set from:
for every metric the interquartile distance over the median in each set,
the wider of the two, five times that, and how far the second set's
median lies from the first's.

    python3 benchmark/tools/spread.py chiprun_out/<outdir>
"""
from __future__ import annotations

import collections
import glob
import json
import pathlib
import statistics
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from benchmark.harness import stats  # noqa: E402


def main(outdir: str) -> int:
    values = collections.defaultdict(lambda: collections.defaultdict(list))
    for path in sorted(glob.glob(f"{outdir}/[AB].*.out")):
        which = pathlib.Path(path).name.split(".")[0]
        line = json.loads(pathlib.Path(path).read_text().strip()
                          .splitlines()[-1])
        if not line["correct"]:
            print(f"NOT CORRECT: {path}: {line.get('checks')}")
        for name, m in line["metrics"].items():
            values[name][which].append(m["value"])
        values["attempted"][which].append(line["attempted"])
        values["memory_peak_bytes"][which].append(
            line["device"]["memory_peak_bytes"])
    for name, sets in values.items():
        print(name)
        spreads, medians = [], []
        for which, vals in sorted(sets.items()):
            med = statistics.median(vals)
            sp = stats.spread(vals) if len(vals) > 1 and med else float("nan")
            spreads.append(sp)
            medians.append(med)
            print(f"  set {which}: median {med:.6g}  spread {100 * sp:.3f}%  "
                  f"{[round(v, 4) for v in vals]}")
        if len(medians) == 2 and medians[0]:
            print(f"  second median {medians[1] / medians[0] - 1:+.3%} from "
                  f"the first; widest spread {100 * max(spreads):.3f}%, "
                  f"five times it {500 * max(spreads):.2f}%")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
