"""Pallas kernels vs their jnp oracles: shape/dtype sweeps in interpret
mode (the kernel body runs in Python on CPU; TPU is the compile target)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.matmul import (MAX_BLOCKS, VMEM_LIMIT_BYTES, choose_blocks,
                                  vmem_bytes)
from repro.kernels.matmul import matmul as pl_matmul
from repro.kernels.matadd import matadd as pl_matadd
from repro.kernels.flash_attention import flash_attention as pl_flash
from repro.kernels.wkv6 import wkv6 as pl_wkv6


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-4, atol=2e-4)


# (M, K, N); the last runs the chosen blocks on a (2, 2, 2) grid
@pytest.mark.parametrize("shape", [(128, 128, 128), (256, 384, 128),
                                   (128, 256, 512), (2048, 1024, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_sweep(shape, dtype):
    M, K, N = shape
    a = jax.random.normal(jax.random.PRNGKey(0), (M, K), dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (K, N), dtype)
    out = pl_matmul(a, b, interpret=True)
    expect = ref.matmul(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


@pytest.mark.parametrize("M, N, K, itemsize", [
    (128, 128, 128, 4), (256, 128, 384, 4), (2048, 2048, 2048, 4),
    (2048, 2048, 1024, 4), (2048, 2048, 2048, 2), (1024, 128, 2048, 4),
    (384, 640, 1152, 4), (128, 4096, 128, 4), (4096, 4096, 4096, 8)])
def test_choose_blocks(M, N, K, itemsize):
    """Multiples of 128 that divide the dims, no larger than ``MAX_BLOCKS``,
    whose pipelined tiles fit the VMEM limit the kernel sets."""
    blocks = choose_blocks(M, N, K, itemsize)
    for dim, b, cap in zip((M, N, K), blocks, MAX_BLOCKS):
        assert b % 128 == 0 and dim % b == 0 and b <= cap
    assert vmem_bytes(*blocks, itemsize) <= VMEM_LIMIT_BYTES
    if itemsize <= 4:  # nothing shrinks below the largest divisor
        assert all(b == max(d for d in range(128, min(dim, cap) + 1, 128)
                            if dim % d == 0)
                   for dim, b, cap in zip((M, N, K), blocks, MAX_BLOCKS))


@pytest.mark.parametrize("M, N, K, itemsize, expect", [
    (128, 128, 128, 4, (128, 128, 128)),  # a 128-sided problem keeps 128^3
    (2048, 2048, 2048, 4, (1024, 1024, 512)),  # the fastest timed on a v5e
    (2048, 2048, 1024, 4, (1024, 1024, 512)),  # test_matmul_sweep's (2, 2, 2) grid
    (4096, 4096, 4096, 8, (512, 1024, 512))])  # 8-byte blocks overflow: shrink
def test_choose_blocks_picks(M, N, K, itemsize, expect):
    assert choose_blocks(M, N, K, itemsize) == expect


@pytest.mark.parametrize("M, N, K, itemsize, matmul_blocks", [
    (128, 128, 128, 4, (128, 128, 128)),
    (2048, 2048, 2048, 4, (1024, 1024, 512)),
    (2048, 2048, 2048, 2, (1024, 1024, 512)),
    (4096, 4096, 4096, 8, (512, 1024, 512))])
def test_choose_blocks_with_c_leaves_the_matmul_alone(M, N, K, itemsize, matmul_blocks):
    """Counting gemm_nt's C block changes nothing for the matmul's shapes;
    gemm_nt's own blocks fit the VMEM limit with C double-buffered, and at
    2048 f32 they are the matmul's."""
    assert choose_blocks(M, N, K, itemsize) == matmul_blocks
    assert choose_blocks(M, N, K, itemsize, reads_c=False) == matmul_blocks
    assert vmem_bytes(*matmul_blocks, itemsize) == vmem_bytes(
        *matmul_blocks, itemsize, reads_c=False)
    blocks = choose_blocks(M, N, K, itemsize, reads_c=True)
    assert vmem_bytes(*blocks, itemsize, reads_c=True) <= VMEM_LIMIT_BYTES
    if (M, itemsize) == (2048, 4):
        assert blocks == matmul_blocks


@pytest.mark.parametrize("shape", [(256, 256), (512, 384), (64, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_matadd_sweep(shape, dtype):
    if dtype == jnp.int32:
        a = jnp.arange(shape[0] * shape[1], dtype=dtype).reshape(shape)
        b = a[::-1]
    else:
        a = jax.random.normal(jax.random.PRNGKey(0), shape, dtype)
        b = jax.random.normal(jax.random.PRNGKey(1), shape, dtype)
    out = pl_matadd(a, b, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref.matadd(a, b)))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("seq", [64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(causal, seq, dtype):
    B, H, hd = 2, 3, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, seq, hd), dtype)
    k = jax.random.normal(ks[1], (B, H, seq, hd), dtype)
    v = jax.random.normal(ks[2], (B, H, seq, hd), dtype)
    out = pl_flash(q, k, v, causal=causal, bq=32, bk=32, interpret=True)
    expect = ref.flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


def test_flash_attention_kv_len_mask():
    B, H, S, hd = 1, 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(x, (B, H, S, hd)) for x in ks)
    out = pl_flash(q, k, v, causal=False, bq=32, bk=32, kv_len=40,
                   interpret=True)
    expect = ref.flash_attention(q, k, v, causal=False, kv_len=40)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_cross_shapes():
    """Sq != Sk (cross attention / cached prefill)."""
    B, H, hd = 2, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, H, 32, hd))
    k = jax.random.normal(ks[1], (B, H, 96, hd))
    v = jax.random.normal(ks[2], (B, H, 96, hd))
    out = pl_flash(q, k, v, causal=False, bq=32, bk=32, interpret=True)
    expect = ref.flash_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("S", [16, 33])
@pytest.mark.parametrize("N", [8, 16])
def test_wkv6_sweep(S, N):
    B, H = 2, 3
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    r = jax.random.normal(ks[0], (B, H, S, N))
    k = jax.random.normal(ks[1], (B, H, S, N))
    v = jax.random.normal(ks[2], (B, H, S, N))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, H, S, N)))
    u = jnp.full((H, N), 0.1)
    out = pl_wkv6(r, k, v, w, u, interpret=True)
    expect, _ = ref.wkv6(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def test_ops_dispatch_cpu_uses_ref():
    from repro.kernels import ops
    a = jax.random.normal(jax.random.PRNGKey(0), (100, 100))
    b = jax.random.normal(jax.random.PRNGKey(1), (100, 100))
    np.testing.assert_allclose(np.asarray(ops.matmul(a, b)),
                               np.asarray(ref.matmul(a, b)), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(ops.matadd(a, b)),
                                  np.asarray(a + b))


def test_model_flash_oracle_matches_kernel():
    """The model's fusedkernel_flash_fwd region == the Pallas kernel (same
    math, different blocking)."""
    from repro.models.layers import fusedkernel_flash_fwd
    B, Sq, K, G, hd = 1, 64, 2, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, Sq, K, G, hd))
    k = jax.random.normal(ks[1], (B, Sq, K, hd))
    v = jax.random.normal(ks[2], (B, Sq, K, hd))
    out, _ = fusedkernel_flash_fwd(q, k, v, 0, causal=True,
                                   scale=1.0 / np.sqrt(hd), Cq=32, Ck=32,
                                   logit_cap=0.0)
    # rearrange to kernel layout (B, H, S, hd) with kv repeated over groups
    qh = q.reshape(B, Sq, K * G, hd).transpose(0, 2, 1, 3)
    kh = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1)
    vh = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1)
    expect = pl_flash(qh, kh, vh, causal=True, bq=32, bk=32, interpret=True)
    got = out.reshape(B, Sq, K * G, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_ops_device_probe_raises_instead_of_falling_back(monkeypatch):
    """A backend that fails to come up is an error, not a silent switch to
    the jnp oracle."""
    from repro.kernels import ops

    def broken():
        raise RuntimeError("backend failed to initialise")

    monkeypatch.setattr(ops, "KERNEL_MODE", "auto")
    monkeypatch.setattr(ops.jax, "devices", broken)
    a = jnp.ones((8, 8))
    for fn in (ops.matmul, ops.matadd):
        with pytest.raises(RuntimeError, match="failed to initialise"):
            fn(a, a)


@pytest.mark.parametrize("side", [48, 100, 1000])
def test_ops_matadd_padded_pallas_matches_oracle(monkeypatch, side):
    """The padded Pallas path (interpret mode here) returns the unpadded sum."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "KERNEL_MODE", "pallas")
    a = jax.random.normal(jax.random.PRNGKey(0), (side, side))
    b = jax.random.normal(jax.random.PRNGKey(1), (side, side))
    np.testing.assert_array_equal(np.asarray(ops.matadd(a, b)),
                                  np.asarray(a + b))


def test_ops_matmul_padded_pallas_matches_oracle(monkeypatch):
    """An unaligned side pads to 1024, where the chosen blocks split K."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "KERNEL_MODE", "pallas")
    a = jax.random.normal(jax.random.PRNGKey(0), (1000, 1000))
    b = jax.random.normal(jax.random.PRNGKey(1), (1000, 1000))
    out = ops.matmul(a, b)
    assert out.shape == (1000, 1000)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.matmul(a, b)),
                               **_tol(jnp.float32))
