"""Jit'd public wrappers around the Pallas kernels.

Handle layout/padding so callers use natural (B, S, H, hd) shapes, and pick
``interpret=True`` on the CPU backend so the same call sites work in CPU
CI and on real hardware.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention as _fa
from .decode_attention import decode_attention as _dec
from .decode_attention import paged_decode_attention as _paged_dec
from .ssd_scan import ssd_scan as _ssd
from .rmsnorm import rmsnorm as _rms


def default_interpret() -> bool:
    """Interpret the Pallas kernels on the CPU backend, compile them on the
    TPU; any other backend is an error, never a silent fallback."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernel path for backend {backend!r}")


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention_op(q, k, v, *, causal=True, window=0, block_q=128,
                       block_k=128, interpret=None):
    """q: (B,S,Hq,hd); k/v: (B,S,Hkv,hd) -> (B,S,Hq,hd)."""
    interpret = default_interpret() if interpret is None else interpret
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    pad = (-s) % max(block_q, block_k)
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, s, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, hd)
    if pad:
        qf = jnp.pad(qf, ((0, 0), (0, pad), (0, 0)))
        kf = jnp.pad(kf, ((0, 0), (0, pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad), (0, 0)))
    out = _fa(qf, kf, vf, causal=causal, window=window,
              block_q=block_q, block_k=block_k, interpret=interpret)
    out = out[:, :s].reshape(b, hq, s, hd).transpose(0, 2, 1, 3)
    return out


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention_op(q, k, v, lengths, *, block_k=256, interpret=None):
    """q: (B,Hq,hd); k/v: (B,C,Hkv,hd); lengths: (B,) -> (B,Hq,hd)."""
    interpret = default_interpret() if interpret is None else interpret
    c = k.shape[1]
    block_k = min(block_k, c)
    pad = (-c) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return _dec(q, k, v, lengths, block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_op(q, k_pool, v_pool, block_tables, lengths, *,
                              interpret=None):
    """q: (B,Hq,hd); pools: (n_blocks,bs,Hkv,hd); block_tables: (B,MB);
    lengths: (B,) -> (B,Hq,hd). Zero-length rows return exact zeros."""
    interpret = default_interpret() if interpret is None else interpret
    return _paged_dec(q, k_pool, v_pool, block_tables, lengths,
                      interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_op(x, dt, A, B, C, *, chunk=256, interpret=None):
    """Chunked SSD; pads s to a chunk multiple (dt=0 padding is
    state-neutral). Returns (y, final_state)."""
    interpret = default_interpret() if interpret is None else interpret
    s = x.shape[1]
    chunk = min(chunk, s) if s < chunk else chunk
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    y, fin = _ssd(x, dt, A, B, C, chunk=chunk, interpret=interpret)
    return y[:, :s], fin


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm_op(x, scale, eps=1e-5, *, block_rows=256, interpret=None):
    """x: (..., d) -> same shape."""
    interpret = default_interpret() if interpret is None else interpret
    shape = x.shape
    d = shape[-1]
    rows = 1
    for dim in shape[:-1]:
        rows *= dim
    xf = x.reshape(rows, d)
    block_rows = min(block_rows, rows)
    pad = (-rows) % block_rows
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    out = _rms(xf, scale, eps, block_rows=block_rows, interpret=interpret)
    return out[:rows].reshape(shape)
