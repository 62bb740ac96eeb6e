"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window. The last
line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``check``: each number the correctness check
compared, with its limit); the same check is the last lines of
standard error. Exits non-zero with no result where JAX finds no TPU or
fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_chips(n: int):
    """The device kind, or None (and a message) where the chips the cell
    asks for are not there."""
    import jax

    from repro.kernels import ops

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"bench: no TPU (JAX found {devices[0].platform!r}); a run "
            "measures the chip and has no CPU fallback")
        return None
    if len(devices) < n:
        log(f"bench: the cell needs {n} chips, JAX found {len(devices)}")
        return None
    if not ops.kernels_active():
        log("bench: the Pallas kernels would not be compiled")
        return None
    return devices[0].device_kind


def compile_cache(root: Path) -> str:
    """JAX's persistent cache inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), holding every program, the
    small ones the engine runs eagerly too."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run(args, *, root: Path = ROOT, chip: bool = True, fault=None,
        t_process: float = T_PROCESS):
    """The result dict of one run, or None where there is no chip."""
    from bench import serve_paged

    cell = spec.Cell(spec.load_benchmark(root), args.workload, root)
    kind = None
    if chip:
        kind = find_chips(cell.chips)
        if kind is None:
            return None
    log(f"bench: compile cache {compile_cache(root)}")
    runners = {"open_loop": serve_paged.run_cell}
    res = runners[cell.mix["kind"]](
        cell.config, cell.mix, cell.params, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), t_process=t_process,
        device_kind=kind, fault=fault)
    if args.trace:
        res["metrics"] = spec.read_layer_metrics(cell.per_layer(),
                                                 res["obs"], root)
    else:
        names = {m["name"] for m in cell.end_to_end()}
        res["metrics"] = {k: v for k, v in res["metrics"].items()
                          if k in names}
    return res


def result_line(res: dict) -> str:
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    if "breakdown" in res:
        keys.append("breakdown")
    keys.append("check")
    return json.dumps({k: res[k] for k in keys})


def main(argv=None) -> int:
    args = parse(argv)
    res = run(args)
    if res is None:
        return 2
    log("bench: " + json.dumps(res["log"]))
    for name, c in res["check"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(result_line(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
