"""Mamba2 SSD chunked scan for TPU (training / prefill hot loop).

Grid: (batch*heads, n_chunks); the chunk axis is minor/sequential, so the
carried SSM state (head_dim × d_state, fp32) lives in VMEM scratch across
chunk steps — the TPU-idiomatic mapping of the SSD inter-chunk recurrence
(GPU implementations use a separate state-passing kernel; on TPU the
sequential grid gives us the recurrence for free).

Per chunk (all MXU matmuls):
  intra:  y_d = ((C B^T) ⊙ decay_seg) (x·dt)
  carry:  y_o = (C ⊙ decay_in) h_prev
  update: h   = decay_chunk · h_prev + (B ⊙ decay_out)^T (x·dt)

B/C are shared across heads (ngroups=1): their BlockSpec maps head h of
batch b to row b — no replication in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, fin_ref, h_scr,
                *, chunk: int, n_chunks: int, n_heads: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)          # (cs, p)
    dt = dt_ref[0].astype(jnp.float32)        # (cs, 1)
    A = a_ref[pl.program_id(0) % n_heads]     # scalar decay rate (SMEM)
    B = b_ref[0].astype(jnp.float32)          # (cs, n)
    C = c_ref[0].astype(jnp.float32)          # (cs, n)

    a = dt * A                                # (cs, 1) log-decay per step
    xb = x * dt                               # discretized input
    # inclusive prefix sum as a lower-triangular matmul (Mosaic has no
    # cumsum lowering)
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    cum = jax.lax.dot(tri.astype(jnp.float32), a,
                      precision=jax.lax.Precision.HIGHEST)   # (cs, 1)

    # intra-chunk (quadratic) term
    seg = cum - cum.T                         # (cs, cs): sum_{s+1..l}
    L = jnp.where(tri, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())))  # (cs, cs)
    y_d = jax.lax.dot(scores * L, xb)         # (cs, p)

    # carried-state contribution
    h_prev = h_scr[...]                       # (n, p)
    y_o = jax.lax.dot(C * jnp.exp(cum), h_prev)

    y_ref[0] = (y_d + y_o).astype(y_ref.dtype)

    # state update
    total = cum[-1:, :]                       # (1,1)
    decay_out = jnp.exp(total - cum)          # (cs, 1)
    S = jax.lax.dot_general(B * decay_out, xb, (((0,), (0,)), ((), ())))
    # the chunk's total decay as a (1, p) row: Mosaic cannot broadcast a
    # (1, 1) value along sublanes and lanes at once
    p = h_prev.shape[1]
    total_row = jnp.sum(jnp.broadcast_to(a, (chunk, p)), axis=0,
                        keepdims=True)
    h_scr[...] = jnp.exp(total_row) * h_prev + S   # (n, p)

    @pl.when(ci == n_chunks - 1)
    def _final():
        fin_ref[0] = h_scr[...].astype(fin_ref.dtype)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, *, chunk: int = 256,
             interpret: bool = False):
    """x: (b, s, h, p); dt: (b, s, h); A: (h,); B/C: (b, s, n).
    Returns (y (b,s,h,p), final_state (b,h,p,n)). Requires s % chunk == 0."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    nc = s // chunk

    xf = x.transpose(0, 2, 1, 3).reshape(b * h, s, p)
    dtf = dt.transpose(0, 2, 1).reshape(b * h, s, 1)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=nc,
                               n_heads=h)

    y, fin = pl.pallas_call(
        kernel,
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bh, ci: (bh, ci, 0)),
            # per-head scalars: the whole (h,) vector sits in SMEM (a
            # (1, 1) VMEM block would break the (8, 128) tiling rule)
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, n), lambda bh, ci, H=h: (bh // H, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bh, ci, H=h: (bh // H, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, n, p), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, p), x.dtype),
            jax.ShapeDtypeStruct((b * h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(xf, dtf, A.astype(jnp.float32), B, C)

    y = y.reshape(b, h, s, p).transpose(0, 2, 1, 3)
    fin = fin.reshape(b, h, n, p).transpose(0, 1, 3, 2)  # (b,h,p,n)
    return y, fin
