"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import (decode_attention_op, flash_attention_op,
                           paged_decode_attention_op, rmsnorm_op,
                           ssd_scan_op)
from repro.kernels.ref import (decode_attention_ref, flash_attention_ref,
                               paged_decode_attention_ref, rmsnorm_ref,
                               ssd_scan_ref)

TOL = {jnp.float32: 2e-4, jnp.bfloat16: 4e-2}


@pytest.mark.parametrize("b,s,hq,hkv,hd", [
    (1, 128, 4, 4, 64),     # MHA
    (2, 256, 8, 2, 64),     # GQA
    (1, 192, 4, 1, 128),    # MQA, non-pow2 seq
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention(b, s, hq, hkv, hd, dtype, causal, window):
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(k1, (b, s, hq, hd), dtype)
    k = jax.random.normal(k2, (b, s, hkv, hd), dtype)
    v = jax.random.normal(k3, (b, s, hkv, hd), dtype)
    out = flash_attention_op(q, k, v, causal=causal, window=window,
                             block_q=64, block_k=64, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    err = jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))
    assert float(err) < TOL[dtype], float(err)


@pytest.mark.parametrize("b,c,hq,hkv,hd", [
    (2, 128, 8, 2, 64),
    (3, 300, 4, 1, 64),
    (1, 64, 16, 16, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(b, c, hq, hkv, hd, dtype):
    k1, k2, k3 = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(k1, (b, hq, hd), dtype)
    k = jax.random.normal(k2, (b, c, hkv, hd), dtype)
    v = jax.random.normal(k3, (b, c, hkv, hd), dtype)
    lens = jnp.arange(1, b + 1, dtype=jnp.int32) * (c // (b + 1)) + 1
    out = decode_attention_op(q, k, v, lens, block_k=64, interpret=True)
    ref = decode_attention_ref(q, k, v, lens)
    err = jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))
    assert float(err) < TOL[dtype], float(err)


def _paged_inputs(key, b, hq, hkv, hd, n_blocks, bs, mb, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    q = jax.random.normal(k1, (b, hq, hd), dtype)
    kp = jax.random.normal(k2, (n_blocks, bs, hkv, hd), dtype)
    vp = jax.random.normal(k3, (n_blocks, bs, hkv, hd), dtype)
    # tables draw WITH junk: rows past the valid length point at random
    # physical blocks, exactly like a scheduler table mid-flight
    tables = jax.random.randint(k4, (b, mb), 0, n_blocks, jnp.int32)
    return q, kp, vp, tables


@pytest.mark.parametrize("b,hq,hkv,hd,bs,mb", [
    (2, 8, 2, 64, 16, 4),       # GQA
    (3, 4, 1, 64, 8, 6),        # MQA, small blocks
    (1, 16, 16, 128, 32, 2),    # MHA, wide blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention(b, hq, hkv, hd, bs, mb, dtype):
    n_blocks = 2 * b * mb
    q, kp, vp, tables = _paged_inputs(jax.random.key(7), b, hq, hkv, hd,
                                      n_blocks, bs, mb, dtype)
    lens = jnp.asarray([(i * mb * bs) // b + 1 for i in range(b)], jnp.int32)
    out = paged_decode_attention_op(q, kp, vp, tables, lens, interpret=True)
    ref = paged_decode_attention_ref(q, kp, vp, tables, lens)
    err = jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))
    assert float(err) < TOL[dtype], float(err)


@pytest.mark.parametrize("length", [
    0,          # empty sequence: exact-zero output, no NaN
    8,          # exactly one full block
    13,         # last block partially filled
    32,         # every table slot full (max-blocks)
])
def test_paged_decode_attention_edges(length):
    b, hq, hkv, hd, bs, mb, n_blocks = 2, 8, 2, 64, 8, 4, 16
    q, kp, vp, tables = _paged_inputs(jax.random.key(11), b, hq, hkv, hd,
                                      n_blocks, bs, mb, jnp.float32)
    lens = jnp.asarray([length, 32 - length], jnp.int32)
    out = paged_decode_attention_op(q, kp, vp, tables, lens, interpret=True)
    ref = paged_decode_attention_ref(q, kp, vp, tables, lens)
    assert not bool(jnp.any(jnp.isnan(out)))
    err = jnp.max(jnp.abs(out - ref))
    assert float(err) < TOL[jnp.float32], float(err)
    if length == 0:
        assert float(jnp.max(jnp.abs(out[0]))) == 0.0


def test_paged_decode_matches_dense_gather():
    """Gathering the pool through the table and running the dense decode
    kernel must agree with the paged kernel reading through the table."""
    b, hq, hkv, hd, bs, mb, n_blocks = 2, 8, 2, 64, 8, 4, 16
    q, kp, vp, tables = _paged_inputs(jax.random.key(13), b, hq, hkv, hd,
                                      n_blocks, bs, mb, jnp.float32)
    lens = jnp.asarray([9, 25], jnp.int32)
    paged = paged_decode_attention_op(q, kp, vp, tables, lens,
                                      interpret=True)
    kd = kp[tables].reshape(b, mb * bs, hkv, hd)
    vd = vp[tables].reshape(b, mb * bs, hkv, hd)
    dense = decode_attention_op(q, kd, vd, lens, block_k=bs, interpret=True)
    err = jnp.max(jnp.abs(paged - dense))
    assert float(err) < TOL[jnp.float32], float(err)


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (1, 64, 2, 32, 16, 32),
    (2, 100, 3, 32, 16, 32),      # padded tail
    (1, 256, 1, 64, 64, 64),
])
def test_ssd_scan(b, s, h, p, n, chunk):
    ks = jax.random.split(jax.random.key(2), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    y, fin = ssd_scan_op(x, dt, A, B, C, chunk=chunk, interpret=True)
    yr, finr = ssd_scan_ref(x, dt, A, B, C)
    assert float(jnp.max(jnp.abs(y - yr))) < 2e-3
    assert float(jnp.max(jnp.abs(fin - finr))) < 2e-3


def test_ssd_scan_matches_model_chunked():
    """Pallas kernel == the model's jnp chunked path == naive recurrence."""
    from repro.models.ssm import ssd_chunked
    ks = jax.random.split(jax.random.key(3), 5)
    b, s, h, p, n = 2, 96, 2, 32, 16
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    y1, f1 = ssd_chunked(x, dt, A, B, C, chunk=32)
    y2, f2 = ssd_scan_op(x, dt, A, B, C, chunk=32, interpret=True)
    assert float(jnp.max(jnp.abs(y1 - y2))) < 2e-3
    assert float(jnp.max(jnp.abs(f1 - f2))) < 2e-3


@pytest.mark.parametrize("shape", [(8, 128), (5, 7, 96), (300, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(shape, dtype):
    x = jax.random.normal(jax.random.key(4), shape, dtype)
    scale = jax.random.normal(jax.random.key(5), shape[-1:], jnp.float32)
    out = rmsnorm_op(x, scale, block_rows=64, interpret=True)
    ref = rmsnorm_ref(x, scale)
    err = jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))
    assert float(err) < TOL[dtype]


def test_default_interpret_follows_backend(monkeypatch):
    """Interpret on the CPU backend, compile on the TPU, and refuse any
    other backend instead of quietly interpreting there."""
    from repro.kernels.ops import default_interpret
    assert default_interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert default_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        default_interpret()
