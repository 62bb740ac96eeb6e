"""Find a cell's knee: the highest rate at which the backlog does not
grow over the window. One process, one set of weights; for each rate a
fresh engine serves the cell's mix for ``--seconds``.

    python3 bench/tools/sweep.py --workload <cell> --rates 0.4,0.6,0.8 \\
        --seconds 40 --seed 5

Prints one JSON line per rate: the mean wait for a slot of the first and
the last third of the arrivals (a backlog that grows shows as the last
third waiting longer), requests admitted by the window's end, and the
end-to-end metrics at that rate.
"""
import argparse
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--give-up", type=float, default=20)
    a = ap.parse_args()
    cell = spec.Cell(spec.load_benchmark(), a.workload)
    kind = R.find_chips(cell.chips)
    if kind is None:
        sys.exit(2)
    R.compile_cache(spec.ROOT)
    prep = sp.prepare(cell.config, cell.mix, a.seed, kind)
    print(json.dumps(prep.log), flush=True)
    for rate in [float(r) for r in a.rates.split(",")]:
        reqs = traffic.open_loop(cell.mix, rate=rate, seconds=a.seconds,
                                 vocab=prep.dm.vocab, seed=a.seed)
        obs, done, device, log = sp.serve(prep, reqs, seconds=a.seconds,
                                          trace=False, give_up_s=a.give_up)
        order = sorted(obs.due, key=obs.due.get)
        third = max(len(order) // 3, 1)
        wait = {u: obs.admit_start.get(u, obs.run_end) - obs.due[u]
                for u in order}
        m = sp.end_to_end(obs, 0.0)
        print(json.dumps({
            "rate": rate, "requests": len(reqs),
            "admitted_by_window_end": sum(
                1 for t in obs.admit_start.values() if t <= obs.t1),
            "wait_first_third_s": sum(wait[u] for u in order[:third]) / third,
            "wait_last_third_s": sum(wait[u] for u in order[-third:]) / third,
            "failed": len(obs.failed), "memory_peak_bytes":
            device["memory_peak_bytes"], **log,
            **{k: v["value"] for k, v in m.items()}}), flush=True)


if __name__ == "__main__":
    main()
