"""Operation and byte counts against hand-computed values for the three
configurations (the ring's is the whole 48-layer qwen2.5-14b)."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from bench import work  # noqa: E402
from bench.model_spec import Dims  # noqa: E402

CONFIGS = HERE.parents[0] / "configs"


@pytest.fixture(scope="module")
def qwen():
    return Dims.load(CONFIGS / "qwen2.5-14b-l12.json")


@pytest.fixture(scope="module")
def mixtral():
    return Dims.load(CONFIGS / "mixtral-8x7b-l4.json")


def test_sizes_match_the_published_models(qwen, mixtral):
    assert (qwen.d, qwen.heads, qwen.kv_heads, qwen.head_dim, qwen.d_ff,
            qwen.vocab, qwen.layers) == (5120, 40, 8, 128, 13824, 152064, 12)
    assert qwen.kv_bytes_per_token == 48 * 1024
    assert qwen.params * 2 == 9_720_727_552
    assert (mixtral.experts, mixtral.top_k, mixtral.window) == (8, 2, 0)
    assert mixtral.kv_bytes_per_token == 16 * 1024
    assert mixtral.params * 2 == 12_134_457_344


def test_paged_verify_counts_live_tokens(qwen):
    w = work.paged_verify(qwen, [100, 200])
    assert w.flops == 4 * 40 * 128 * 300
    assert w.bytes == 2 * (2 * 8 * 128 * 300 + 2 * 2 * 40 * 128)


def test_paged_prefill_is_causal_over_its_prefix(qwen):
    w = work.paged_prefill(qwen, prefix=512, chunk=256)
    assert w.flops == 4 * 40 * 128 * (256 * 512 + 256 * 257 / 2)
    assert w.bytes == 2 * (2 * 8 * 128 * 768 + 2 * 256 * 40 * 128)


def test_decode_step_dense(qwen):
    per_tok = 2 * (12 * (5120 * 56 * 128 + 5120 * 5120 + 3 * 5120 * 13824)
                   + 5120 * 152064)
    assert per_tok == 8_163_164_160
    w = work.decode_step(qwen, [10] * 16)
    assert w.flops == per_tok * 16 + 12 * 4 * 40 * 128 * 160
    weights = 2 * (12 * (5120 * 56 * 128 + 5120 * 5120 + 3 * 5120 * 13824)
                   + 5120 * 152064 + 16 * 5120)
    attn = 12 * 2 * (2 * 8 * 128 * 160 + 2 * 16 * 40 * 128)
    kv_write = 2 * 12 * 2 * 8 * 128 * 16
    logits = 2 * 16 * 152064
    assert w.bytes == weights + attn + kv_write + logits


def test_moe_counts_top_k_flops_and_routed_expert_bytes(mixtral):
    assert work.active_experts(mixtral, 1) == pytest.approx(2.0)
    assert work.active_experts(mixtral, 2) == pytest.approx(3.5)
    assert work.active_experts(mixtral, 256) == pytest.approx(8.0)
    proj = 4096 * (32 + 16) * 128 + 4096 * 4096
    w = work.decode_step(mixtral, [1])
    per_tok = 2 * (4 * (proj + 3 * 4096 * 14336 * 2 + 4096 * 8)
                   + 4096 * 32000)
    assert w.flops == per_tok + 4 * 4 * 32 * 128 * 1
    weights = 2 * (4 * (proj + 3 * 4096 * 14336 * 2 + 4096 * 8)
                   + 4096 * 32000 + 4096)
    attn = 4 * 2 * (2 * 8 * 128 + 2 * 32 * 128)
    assert w.bytes == pytest.approx(weights + attn + 2 * 4 * 2 * 8 * 128
                                    + 2 * 32000)
    c = work.prefill_chunk(mixtral, 0, 256)
    assert c.flops == pytest.approx(per_tok * 256 + 4 * 4 * 32 * 128
                                    * 256 * 257 / 2)


def test_whole_48_layer_qwen_decode(qwen):
    whole = qwen.scaled(layers=48)
    d = work.decode_step(whole, [512] * 8)
    assert d.flops == pytest.approx(
        8 * 2 * (48 * 275_251_200 + 5120 * 152064)
        + 48 * 4 * 40 * 128 * 4096)
    assert whole.params * 2 == pytest.approx(29.5e9, rel=0.01)


def test_share_above_one_is_an_error():
    assert work.share(0.5, 1.0) == 0.5
    with pytest.raises(ValueError, match="above 1"):
        work.share(1.01, 1.0)
    with pytest.raises(ValueError):
        work.share(1.0, 0.0)
    p = work.Peak("x", 100.0, 10.0)
    assert p.least_time(work.Work(200.0, 10.0)) == 2.0
    assert p.least_time(work.Work(100.0, 50.0)) == 5.0
