"""Compile a configuration's decode step and full prefill chunk for a
described TPU v5e, without the chip, and print what each program needs
on the device (``memory_analysis``) and whether the Pallas kernels are
in it.

    JAX_PLATFORMS=cpu python3 bench/tools/rehearse_compile.py \\
        bench/configs/<config>.json
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench import spec  # noqa: E402

import json  # noqa: E402


def main(path: str) -> None:
    from repro.kernels import ops
    from repro.runtime import kvcache

    # the described chip is not the default backend: steer the program to
    # the kernels it would take on the chip
    ops.kernels_active = lambda: True
    ops._interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)

    conf = json.loads(Path(path).read_text())
    fam = spec.family(conf["model_type"])
    dm, s = fam.dims(conf), conf["serving"]
    cfg = fam.program_config(dm)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params: dict = {}
    for p, (shape, _, _) in fam.leaf_shapes(dm).items():
        node = params
        *parents, name = p.split("/")
        for k in parents:
            node = node.setdefault(k, {})
        node[name] = sds(shape, jnp.bfloat16)
    ctx = 512 + 1536 + 512
    max_pages = -(-ctx // s["page_tokens"])
    pages = {k: sds((dm.layers, s["n_pages"], s["page_tokens"]) + trail,
                    jnp.bfloat16)
             for k, trail in kvcache.paged_cache_spec(cfg).items()}
    B = s["slots"]
    cache = {"pages": pages, "block_table": sds((B, max_pages), jnp.int32),
             "len": sds((B,), jnp.int32)}
    view = {"pages": pages, "block_table": sds((1, max_pages), jnp.int32),
            "len": sds((1,), jnp.int32)}
    steps = {
        "decode": kvcache._decode_paged_jit.lower(
            params, cfg, cache, sds((B, 1), jnp.int32)),
        "prefill_chunk": kvcache._prefill_chunk_jit.lower(
            params, cfg, view, sds((1, s["prefill_chunk"]), jnp.int32),
            True),
    }
    for name, lowered in steps.items():
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        gb = 1e9
        print(f"{dm.name} {name}: arguments "
              f"{ma.argument_size_in_bytes / gb:.3f} GB, outputs "
              f"{ma.output_size_in_bytes / gb:.3f} GB, temporaries "
              f"{ma.temp_size_in_bytes / gb:.3f} GB, aliased "
              f"{ma.alias_size_in_bytes / gb:.3f} GB; kernels: "
              f"{'tpu_custom_call' in compiled.as_text()}", flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
