"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and its
phases (paged serving, the pipelined ring) pass at a tiny size with the
kernel check off, so the script cannot rot between chip runs."""
import dataclasses
import importlib.util
from pathlib import Path

import jax
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_mesh

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py")
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)


@pytest.fixture(autouse=True)
def cpu_tolerance(monkeypatch):
    """XLA's CPU bf16 dot rounds more than the chip's f32-accumulating
    MXU: a plain bf16 stack at this tiny width is off by about 3e-2 here
    (float8 by 0.4), against 6.9e-3 on a v5e at full width."""
    monkeypatch.setattr(cs, "REL_TOL", 5e-2)


@pytest.fixture
def no_kernel_check(monkeypatch):
    """Off the chip the paged steps take the jnp oracles, so there is no
    tpu_custom_call to find (tests/test_tpu_compile.py covers that)."""
    monkeypatch.setattr(cs, "check_kernels", lambda lowered, name: None)


def test_refuses_without_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        cs.main([])
    assert e.value.code == 1
    out = capsys.readouterr()
    assert "no TPU" in out.err and '"ok"' not in out.out


def test_serve_phase_tiny(no_kernel_check):
    cfg = dataclasses.replace(get_config(cs.ARCH).reduced(), n_layers=3)
    params = cs.init_weights(cfg, 0)
    res = cs.serve_phase(cfg, params, n_requests=5, slots=3,
                         lengths=(32, 48), max_new=5, prefill_chunk=16,
                         page_tokens=8, n_pages=64, n_probe=3)
    assert res["requests"] == 5
    assert res["err_prefill"] <= cs.REL_TOL
    assert res["err_decode"] <= cs.REL_TOL
    assert cs.tree_bytes(params) < res["live_bytes"]


@pytest.mark.skipif(jax.device_count() < 4, reason="needs 4 devices")
def test_ring_phase_tiny():
    cfg = dataclasses.replace(get_config(cs.ARCH).reduced(), n_layers=8)
    params = cs.init_weights(cfg, 0)
    res = cs.ring_phase(cfg, params, make_mesh((4, 1)), steps=3)
    assert cs.check_logits("ring", res["ring"], res["ref32"]) <= cs.REL_TOL
    assert cs.check_logits("ring vs decode_step", res["ring"],
                           res["ref_bf16"]) <= cs.REL_TOL
    cs.must_miss("misordered ring", res["misordered"], res["ref32"])
    cs.must_miss("misordered ring vs decode_step", res["misordered"],
                 res["ref_bf16"])
    held = {s.device for a in jax.tree.leaves(res["ring_params"]["blocks"])
            for s in a.addressable_shards}
    assert len(held) == 4
