"""Record the fixture of the span readers' tests: a short traced run of
a tiny paged engine through the harness, kept gzipped as
``spans.xplane.pb.gz``, beside ``spans.json`` with what the run counted
itself (traced steps, admits, table round trips, the window start and
the compile log). Run it on the chip, so that the trace holds the
device plane.

    python3 bench/tools/record_spans.py bench/tests/fixtures
"""
import dataclasses
import gzip
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import serve_paged, spec, traffic  # noqa: E402
from repro.runtime import telemetry  # noqa: E402

SEED, RATE, SECONDS = 2**31 + 5, 8.0, 0.5
#: the tiny test cell's sizes, with heads of 128 for the chip's kernels
CONFIG = {
    "name": "tiny-qwen2-128", "model_type": "qwen2", "hidden_size": 256,
    "intermediate_size": 512, "num_attention_heads": 2,
    "num_hidden_layers": 2, "num_key_value_heads": 1,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "vocab_size": 512,
    "qkv_bias": True, "sliding_window": None,
    "serving": {"dtype": "bfloat16", "page_tokens": 16, "prefill_chunk": 32,
                "slots": 4, "n_pages": 41},
}
MIX = {
    "kind": "open_loop", "size_seed": 5,
    "system_prompts": {"count": 4, "tokens": 32, "zipf_s": 1.0},
    "user_tokens": {"median": 32, "sigma": 0.5, "min": 16, "max": 64,
                    "round_to": 16},
    "output_tokens": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
}
READERS = ("step_host_ms", "admit_host_ms", "table_syncs_per_step",
           "setup_lower_s")


def main(out: str) -> None:
    out = Path(out)
    load = serve_paged.load_trace

    def keep(path):
        (out / "spans.xplane.pb.gz").write_bytes(
            gzip.compress(Path(path).read_bytes(), mtime=0))
        return load(path)

    serve_paged.load_trace = keep
    engines = []
    prep = serve_paged.prepare(CONFIG, MIX, SEED, device_kind=None)
    reqs = traffic.open_loop(MIX, rate=RATE, seconds=SECONDS,
                             vocab=prep.dm.vocab, seed=SEED)
    obs, _, device, _ = serve_paged.serve(prep, reqs, seconds=SECONDS,
                                          trace=True, fault=engines.append)
    log = telemetry.compile_log()
    meta = {"device": device, "traced_steps": len(obs.traced_steps()),
            "admits": len(obs.admit_start),
            "table_syncs": engines[0].kv.table_syncs, "t0": obs.t0,
            "compile_log": {"t_start": log.t_start, "spans": [
                dataclasses.astuple(s) for s in log.spans]}}
    (out / "spans.json").write_text(json.dumps(meta) + "\n")
    read = {m: spec.reader(m)(obs) for m in READERS}
    print(json.dumps({k: v for k, v in meta.items()
                      if k != "compile_log"}), json.dumps(read), flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
