"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True
executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_decode import flash_decode, flash_verify
from repro.kernels.paged_decode import (paged_decode, paged_decode_quant,
                                        paged_verify, paged_verify_quant)
from repro.kernels.paged_prefill import paged_prefill
from repro.kernels.q4_matmul import q4_matmul
from repro.kernels.ssd_scan import ssd_scan
from repro.quant import quantize_q4

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("M,K,N,bm,bn,group", [
    (128, 512, 256, 128, 128, 32),     # 2 K tiles of k_block(32) = 256
    (256, 1024, 512, 128, 256, 64),    # 2 K tiles of 512
    (64, 2048, 384, 64, 128, 64),      # 4 K tiles
    (256, 128, 128, 256, 128, 64),     # K below k_block: one whole tile
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_q4_matmul_sweep(M, K, N, bm, bn, group, dtype):
    x = jax.random.normal(KEY, (M, K), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N))
    qt = quantize_q4(w, group)
    out = q4_matmul(x, qt.packed, qt.scale, group=group, block_m=bm,
                    block_n=bn, interpret=True)
    want = ref.q4_matmul_ref(x, qt.packed, qt.scale, group=group)
    tol = 1e-3 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=tol, atol=tol * np.abs(want).max())


@pytest.mark.parametrize("B,H,hkv,D,S,bs", [
    (2, 8, 2, 64, 512, 128),
    (1, 4, 4, 128, 1024, 256),   # MHA
    (3, 8, 1, 64, 256, 256),     # MQA
    (2, 16, 2, 32, 512, 512),
])
@pytest.mark.parametrize("window", [None, 128])
def test_flash_decode_sweep(B, H, hkv, D, S, bs, window):
    q = jax.random.normal(KEY, (B, H, D))
    k = jax.random.normal(jax.random.PRNGKey(2), (B, S, hkv, D))
    v = jax.random.normal(jax.random.PRNGKey(3), (B, S, hkv, D))
    kv_len = jnp.asarray(
        np.random.default_rng(0).integers(1, S + 1, size=B), jnp.int32)
    out = flash_decode(q, k, v, kv_len, window=window, block_s=bs,
                       interpret=True)
    want = ref.flash_decode_ref(q, k, v, kv_len, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T", [1, 2, 4, 8])
@pytest.mark.parametrize("B,H,hkv,D,S,bs", [
    (2, 8, 2, 64, 512, 128),
    (1, 4, 4, 128, 512, 256),    # MHA
    (3, 8, 1, 64, 256, 256),     # MQA
])
def test_flash_verify_sweep(T, B, H, hkv, D, S, bs):
    """Multi-query verify kernel vs the reference attention path, T draft
    positions with causal masking among the drafts (1e-3 acceptance bar)."""
    q = jax.random.normal(KEY, (B, T, H, D))
    k = jax.random.normal(jax.random.PRNGKey(2), (B, S, hkv, D))
    v = jax.random.normal(jax.random.PRNGKey(3), (B, S, hkv, D))
    kv_len = jnp.asarray(
        np.random.default_rng(T).integers(T, S + 1, size=B), jnp.int32)
    out = flash_verify(q, k, v, kv_len, block_s=bs, interpret=True)
    want = ref.flash_verify_ref(q, k, v, kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("T", [2, 4])
def test_flash_verify_window(T):
    B, H, hkv, D, S = 2, 8, 2, 64, 512
    q = jax.random.normal(KEY, (B, T, H, D))
    k = jax.random.normal(jax.random.PRNGKey(2), (B, S, hkv, D))
    v = jax.random.normal(jax.random.PRNGKey(3), (B, S, hkv, D))
    kv_len = jnp.asarray([S, S // 2], jnp.int32)
    out = flash_verify(q, k, v, kv_len, window=64, block_s=128,
                       interpret=True)
    want = ref.flash_verify_ref(q, k, v, kv_len, window=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


def test_flash_verify_T1_matches_flash_decode():
    """T = 1 must reduce to ordinary decode attention."""
    B, H, hkv, D, S = 2, 8, 2, 64, 512
    q = jax.random.normal(KEY, (B, 1, H, D))
    k = jax.random.normal(jax.random.PRNGKey(2), (B, S, hkv, D))
    v = jax.random.normal(jax.random.PRNGKey(3), (B, S, hkv, D))
    kv_len = jnp.asarray([S, S // 3], jnp.int32)
    out = flash_verify(q, k, v, kv_len, block_s=128, interpret=True)
    want = flash_decode(q[:, 0], k, v, kv_len, block_s=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T", [1, 3, 4])
@pytest.mark.parametrize("B,H,hkv,D,P,bs,nb", [
    (2, 8, 2, 64, 16, 16, 4),
    (1, 4, 4, 128, 8, 32, 3),    # MHA
    (3, 8, 1, 64, 32, 8, 6),     # MQA, small pages
])
def test_paged_verify_sweep(T, B, H, hkv, D, P, bs, nb):
    """Paged verify kernel (block-table gather through scalar prefetch)
    vs the gather-then-verify oracle; tables are random permutations so
    physical != logical page order."""
    q = jax.random.normal(KEY, (B, T, H, D))
    kp = jax.random.normal(jax.random.PRNGKey(2), (P, bs, hkv, D))
    vp = jax.random.normal(jax.random.PRNGKey(3), (P, bs, hkv, D))
    rng = np.random.default_rng(T)
    table = jnp.asarray(rng.permutation(P)[:B * nb].reshape(B, nb)
                        if P >= B * nb else
                        rng.integers(0, P, (B, nb)), jnp.int32)
    kv_len = jnp.asarray(rng.integers(T, nb * bs + 1, size=B), jnp.int32)
    out = paged_verify(q, kp, vp, table, kv_len, interpret=True)
    want = ref.paged_verify_ref(q, kp, vp, table, kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


def test_paged_verify_window_and_T1_decode():
    B, T, H, hkv, D, P, bs, nb = 2, 2, 8, 2, 64, 16, 16, 4
    q = jax.random.normal(KEY, (B, T, H, D))
    kp = jax.random.normal(jax.random.PRNGKey(2), (P, bs, hkv, D))
    vp = jax.random.normal(jax.random.PRNGKey(3), (P, bs, hkv, D))
    table = jnp.asarray(
        np.random.default_rng(0).permutation(P)[:B * nb].reshape(B, nb),
        jnp.int32)
    kv_len = jnp.asarray([nb * bs, 17], jnp.int32)
    out = paged_verify(q, kp, vp, table, kv_len, window=16,
                       interpret=True)
    want = ref.paged_verify_ref(q, kp, vp, table, kv_len, window=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-3, atol=1e-3)
    # T = 1 wrapper reduces to paged decode attention
    out1 = paged_decode(q[:, 0], kp, vp, table, kv_len, interpret=True)
    want1 = ref.paged_decode_ref(q[:, 0], kp, vp, table, kv_len)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(want1),
                               rtol=1e-5, atol=1e-5)


def test_paged_verify_contiguous_table_matches_flash_verify():
    """With an identity block table the paged kernel must reproduce the
    contiguous flash_verify on the same bytes."""
    B, T, H, hkv, D, bs, nb = 2, 4, 8, 2, 64, 64, 4
    S = bs * nb
    k = jax.random.normal(jax.random.PRNGKey(2), (B, S, hkv, D))
    v = jax.random.normal(jax.random.PRNGKey(3), (B, S, hkv, D))
    q = jax.random.normal(KEY, (B, T, H, D))
    kv_len = jnp.asarray([S, S // 2], jnp.int32)
    # pages: batch-major split of the contiguous caches
    kp = k.reshape(B * nb, bs, hkv, D)
    vp = v.reshape(B * nb, bs, hkv, D)
    table = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    out = paged_verify(q, kp, vp, table, kv_len, interpret=True)
    want = flash_verify(q, k, v, kv_len, block_s=bs, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def _int8_pages(pages):
    """Per-(position, kv-head) int8 quantization of float pages —
    ``layers.quantize_kv`` convention (scale = amax/127 over D)."""
    scale = jnp.max(jnp.abs(pages), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(pages / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


@pytest.mark.parametrize("S", [1, 4, 8])
@pytest.mark.parametrize("B,H,hkv,D,P,bs,nb", [
    (2, 8, 2, 64, 16, 16, 4),
    (1, 4, 4, 128, 8, 32, 3),    # MHA
    (3, 8, 1, 64, 32, 8, 6),     # MQA, small pages
])
def test_paged_prefill_sweep(S, B, H, hkv, D, P, bs, nb):
    """Chunked-prefill flash kernel vs the gather oracle: S chunk rows
    sit at absolute positions kv_len - S + t, tables are permuted, and
    kv_len sweeps partial pages so dead table entries must be skipped."""
    q = jax.random.normal(KEY, (B, S, H, D))
    kp = jax.random.normal(jax.random.PRNGKey(2), (P, bs, hkv, D))
    vp = jax.random.normal(jax.random.PRNGKey(3), (P, bs, hkv, D))
    rng = np.random.default_rng(S)
    table = jnp.asarray(rng.permutation(P)[:B * nb].reshape(B, nb)
                        if P >= B * nb else
                        rng.integers(0, P, (B, nb)), jnp.int32)
    kv_len = jnp.asarray(rng.integers(S, nb * bs + 1, size=B), jnp.int32)
    out = paged_prefill(q, kp, vp, table, kv_len, interpret=True)
    want = ref.paged_prefill_ref(q, kp, vp, table, kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


def test_paged_prefill_windowed_dead_page_guard():
    """Sliding-window regression: the dead-page guard must keep pages
    the *first* chunk row's window still reaches (its window starts at
    kv_len - S - window, up to S - 1 positions before the last row's) —
    cutting at kv_len - window silently zeros those contributions."""
    B, S, H, hkv, D, P, bs, nb = 1, 4, 4, 2, 64, 8, 8, 4
    q = jax.random.normal(KEY, (B, S, H, D))
    kp = jax.random.normal(jax.random.PRNGKey(2), (P, bs, hkv, D))
    vp = jax.random.normal(jax.random.PRNGKey(3), (P, bs, hkv, D))
    table = jnp.asarray([[3, 1, 5, 0]], jnp.int32)
    # kv_len 24, window 8: row 0 (abs pos 20) attends 13..20 — page 1
    # (positions 8..15) ends exactly at kv_len - window, so a guard
    # keyed on the last row drops it
    kv_len = jnp.asarray([24], jnp.int32)
    out = paged_prefill(q, kp, vp, table, kv_len, window=8,
                        interpret=True)
    want = ref.paged_prefill_ref(q, kp, vp, table, kv_len, window=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_paged_prefill_S1_matches_paged_decode():
    """A one-token chunk is exactly paged decode attention."""
    B, H, hkv, D, P, bs, nb = 2, 8, 2, 64, 16, 16, 4
    q = jax.random.normal(KEY, (B, 1, H, D))
    kp = jax.random.normal(jax.random.PRNGKey(2), (P, bs, hkv, D))
    vp = jax.random.normal(jax.random.PRNGKey(3), (P, bs, hkv, D))
    table = jnp.asarray(
        np.random.default_rng(0).permutation(P)[:B * nb].reshape(B, nb),
        jnp.int32)
    kv_len = jnp.asarray([nb * bs, 21], jnp.int32)
    out = paged_prefill(q, kp, vp, table, kv_len, interpret=True)
    want = paged_decode(q[:, 0], kp, vp, table, kv_len, interpret=True)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T", [1, 3])
@pytest.mark.parametrize("B,H,hkv,D,P,bs,nb", [
    (2, 8, 2, 64, 16, 16, 4),
    (1, 4, 4, 128, 8, 32, 3),    # MHA
    (3, 8, 1, 64, 32, 8, 6),     # MQA
])
def test_paged_verify_quant_sweep(T, B, H, hkv, D, P, bs, nb):
    """int8-KV paged verify with in-kernel dequant vs the
    dequantize-then-attend oracle on the same quantized bytes."""
    q = jax.random.normal(KEY, (B, T, H, D))
    kp = jax.random.normal(jax.random.PRNGKey(2), (P, bs, hkv, D))
    vp = jax.random.normal(jax.random.PRNGKey(3), (P, bs, hkv, D))
    kq, ks = _int8_pages(kp)
    vq, vs = _int8_pages(vp)
    rng = np.random.default_rng(T)
    table = jnp.asarray(rng.permutation(P)[:B * nb].reshape(B, nb)
                        if P >= B * nb else
                        rng.integers(0, P, (B, nb)), jnp.int32)
    kv_len = jnp.asarray(rng.integers(T, nb * bs + 1, size=B), jnp.int32)
    out = paged_verify_quant(q, kq, vq, ks, vs, table, kv_len,
                             interpret=True)
    want = ref.paged_verify_quant_ref(q, kq, vq, ks, vs, table, kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


def test_paged_decode_quant_window_and_oracle():
    B, H, hkv, D, P, bs, nb = 2, 8, 2, 64, 16, 16, 4
    q = jax.random.normal(KEY, (B, H, D))
    kp = jax.random.normal(jax.random.PRNGKey(2), (P, bs, hkv, D))
    vp = jax.random.normal(jax.random.PRNGKey(3), (P, bs, hkv, D))
    kq, ks = _int8_pages(kp)
    vq, vs = _int8_pages(vp)
    table = jnp.asarray(
        np.random.default_rng(0).permutation(P)[:B * nb].reshape(B, nb),
        jnp.int32)
    kv_len = jnp.asarray([nb * bs, 17], jnp.int32)
    for window in (None, 16):
        out = paged_decode_quant(q, kq, vq, ks, vs, table, kv_len,
                                 window=window, interpret=True)
        want = ref.paged_decode_quant_ref(q, kq, vq, ks, vs, table,
                                          kv_len, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-3, atol=1e-3)


def test_paged_verify_quant_exact_scales_recover_float():
    """With unit scales the int8 kernel must equal the float kernel on
    integer-valued pages — the dequant path adds no extra error."""
    B, T, H, hkv, D, P, bs, nb = 1, 2, 4, 2, 64, 8, 16, 3
    q = jax.random.normal(KEY, (B, T, H, D))
    rng = np.random.default_rng(1)
    kq = jnp.asarray(rng.integers(-127, 128, (P, bs, hkv, D)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, (P, bs, hkv, D)), jnp.int8)
    ones = jnp.ones((P, bs, hkv), jnp.float32)
    table = jnp.asarray(rng.permutation(P)[:B * nb].reshape(B, nb),
                        jnp.int32)
    kv_len = jnp.asarray([nb * bs - 5], jnp.int32)
    out = paged_verify_quant(q, kq, vq, ones, ones, table, kv_len,
                             interpret=True)
    want = paged_verify(q, kq.astype(jnp.float32),
                        vq.astype(jnp.float32), table, kv_len,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_dtypes(dtype):
    B, H, hkv, D, S = 2, 8, 2, 64, 512
    q = jax.random.normal(KEY, (B, H, D), dtype)
    k = jax.random.normal(jax.random.PRNGKey(2), (B, S, hkv, D), dtype)
    v = jax.random.normal(jax.random.PRNGKey(3), (B, S, hkv, D), dtype)
    kv_len = jnp.full((B,), S, jnp.int32)
    out = flash_decode(q, k, v, kv_len, block_s=256, interpret=True)
    want = ref.flash_decode_ref(q, k, v, kv_len)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("B,S,nh,P,N,chunk", [
    (2, 256, 4, 32, 64, 64),
    (1, 128, 2, 64, 128, 128),
    (2, 512, 8, 16, 32, 128),
    (1, 192, 3, 32, 64, 64),     # S not a multiple of a power of two
])
def test_ssd_scan_sweep(B, S, nh, P, N, chunk):
    if S % chunk:
        pytest.skip("kernel requires S % chunk == 0")
    x = jax.random.normal(KEY, (B, S, nh, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(4),
                                           (B, S, nh)))
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(5), (nh,)) * 0.3)
    Bm = jax.random.normal(jax.random.PRNGKey(6), (B, S, N)) * 0.3
    Cm = jax.random.normal(jax.random.PRNGKey(7), (B, S, N)) * 0.3
    y, h = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    y_ref, h_ref = ref.ssd_sequential_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               rtol=2e-4, atol=2e-4)


def test_ssd_chunked_jnp_vs_sequential():
    """The model-layer chunked scan (used in training) against the O(S)
    recurrence."""
    B, S, nh, P, N = 2, 200, 4, 16, 32
    x = jax.random.normal(KEY, (B, S, nh, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(4),
                                           (B, S, nh)))
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(5), (nh,)) * 0.3)
    Bm = jax.random.normal(jax.random.PRNGKey(6), (B, S, N)) * 0.3
    Cm = jax.random.normal(jax.random.PRNGKey(7), (B, S, N)) * 0.3
    y_c, h_c = ref.ssd_scan_ref(x, dt, A, Bm, Cm, chunk=64)
    y_r, h_r = ref.ssd_sequential_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_r),
                               rtol=2e-4, atol=2e-4)
