"""Readings that the correctness limit of a cell is set from.

For each seed: weights from the seed, a window of the cell's own mix at
its own rate, drained until every request has finished, then the
reference over the same seeded sample a benchmark run compares. Prints
percentiles of the program's served-token gaps (the lower readings)
and, with ``--control``, of the gap of the token that the same
reference computed in float8 e4m3 puts first at each of those positions
(the upper readings).

    python3 bench/tools/limits.py --workload <cell> --seeds 1,2,3 \\
        --seconds 20 --control
"""
import argparse
import gc
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                str(Path(__file__).resolve().parents[2] / "src")]

from bench import run as R  # noqa: E402
from bench import serve_paged as sp  # noqa: E402
from bench import spec, traffic  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--control", action="store_true")
    a = ap.parse_args()
    cell = spec.Cell(spec.load_benchmark(), a.workload)
    kind = R.find_chips(cell.chips)
    if kind is None:
        sys.exit(2)
    R.compile_cache(spec.ROOT)
    for i, seed in enumerate(int(s) for s in a.seeds.split(",")):
        prep = sp.prepare(cell.config, cell.mix, seed, kind, warm=i == 0)
        reqs = traffic.open_loop(cell.mix, rate=cell.params["rate_per_s"],
                                 seconds=a.seconds, vocab=prep.dm.vocab,
                                 seed=seed)
        obs, done, device, log = sp.serve(prep, reqs, seconds=a.seconds,
                                          trace=False, drain=True)
        gaps, ctrl = sp.check(prep, reqs, done, seed,
                              cell.params["sample"], control=a.control)
        print(json.dumps({
            "seed": seed, "tokens": len(gaps), "finished": len(done),
            "requests": len(reqs), "gap": sp.gap_summary(gaps),
            "control_gap": None if ctrl is None else sp.gap_summary(ctrl)}),
            flush=True)
        del prep, obs, done
        gc.collect()


if __name__ == "__main__":
    main()
