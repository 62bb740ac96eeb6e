"""Record a small profiler trace on the chip and print its layout:
planes, lines, and the names and stats of a few events per line. The
trace is kept as the fixture of the trace-reduction tests.

    python3 bench/tools/probe_trace.py <out dir>
"""
import glob
import shutil
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.profiler import ProfileData  # noqa: E402

from repro.kernels import ops  # noqa: E402


def main(out: str) -> None:
    out = Path(out)
    B, H, hk, D, P, bs, nb = 4, 8, 2, 128, 16, 16, 4
    k = jax.random.normal(jax.random.key(0), (P, bs, hk, D), jnp.bfloat16)
    q = jax.random.normal(jax.random.key(1), (B, 1, H, D), jnp.bfloat16)
    table = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb) % P
    ln = jnp.array([5, 17, 33, 64], jnp.int32)
    w = jax.random.normal(jax.random.key(2), (1024, 1024), jnp.bfloat16)

    @jax.jit
    def step(q, k, w):
        o = ops.paged_verify(q, k, k, table, ln)
        return (o.reshape(B, -1)[:, :1024] @ w).sum()

    step(q, k, w).block_until_ready()
    tmp = out / "raw"
    jax.profiler.start_trace(str(tmp))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("engine.step"):
            step(q, k, w).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp / "**" / "*.xplane.pb"), recursive=True)[0]
    shutil.copy(path, out / "probe.xplane.pb")
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print("PLANE", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for ev in evs[:6]:
                print("     ", repr(ev.name), ev.start_ns, ev.duration_ns,
                      {k: v for k, v in ev.stats})
    d = jax.devices()[0]
    print("DEVICE", d.platform, d.device_kind, len(jax.devices()),
          d.memory_stats())


if __name__ == "__main__":
    main(sys.argv[1])
