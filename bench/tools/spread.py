"""Spread of each metric over the result lines of a set of runs.

    python3 bench/tools/spread.py <dir with <seed>.out files> [...]

For each directory (one set), and each metric: the median, and the
distance between the first and third quartile (``statistics.quantiles``,
n=4) as a share of the median; also that share with the run farthest
from the median left out. Prints the correctness check's numbers too.
"""
import json
import statistics
import sys
from pathlib import Path


def lines(d: Path):
    out = []
    for f in sorted(d.glob("*.out")):
        text = f.read_text().strip().splitlines()
        if text:
            out.append((f.stem, json.loads(text[-1])))
    return out


def spread(vals):
    med = statistics.median(vals)
    q = statistics.quantiles(vals, n=4)
    return med, (q[2] - q[0]) / abs(med) if med else float("nan")


def main(dirs) -> None:
    for d in map(Path, dirs):
        res = lines(d)
        print(f"== {d} ({len(res)} runs; correct "
              f"{sum(r['correct'] for _, r in res)})")
        names = sorted({k for _, r in res for k in r["metrics"]})
        for n in names:
            vals = [r["metrics"][n]["value"] for _, r in res
                    if n in r["metrics"]]
            if len(vals) < 2:
                print(f"  {n}: {vals}")
                continue
            med, s = spread(vals)
            far = max(vals, key=lambda v: abs(v - med))
            rest = list(vals)
            rest.remove(far)
            _, s2 = spread(rest) if len(rest) > 1 else (med, float("nan"))
            print(f"  {n}: median {med:.6g} spread {s:.4f} "
                  f"(without farthest {s2:.4f}) values "
                  f"{[round(v, 5) for v in vals]}")
        for name in res[0][1]["check"] if res else []:
            vals = [r["check"][name]["value"] for _, r in res]
            print(f"  check {name}: max {max(vals):.6g} values "
                  f"{[round(v, 5) for v in vals]}")
        for seed, r in res:
            print(f"  seed {seed}: attempted {r['attempted']} failed "
                  f"{r['failed']} peak {r['device']['memory_peak_bytes']}")


if __name__ == "__main__":
    main(sys.argv[1:])
