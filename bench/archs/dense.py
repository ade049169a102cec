"""The dense decoder: what the benchmark draws, computes and counts for a
configuration whose model block has ``"arch_type": "dense"``.

One stack of identical layers: RMSNorm, Q/K/V projections (with bias
where the configuration has it), rotary embedding on halves of each
head, causal attention with grouped K/V heads (one K/V head is MQA, as
many as the query heads is MHA), the output projection and the residual;
RMSNorm and a GELU (tanh form) or SwiGLU MLP and the residual.

An architecture module gives the harness ``leaf_specs``, ``layers``,
``layer`` and ``counts``; ``bench/harness/spec.py`` (``arch``) says what
each must do.
"""
from __future__ import annotations

import functools
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from harness.counts import BYTES, Counts
from reference import _mm, _q8, _rms, attention


def leaf_specs(m: dict) -> List[Tuple[str, Optional[int], Tuple[int, ...],
                                      str]]:
    """(path, stack, per-layer shape, kind) of every leaf; paths under
    ``layers/`` carry a leading ``n_layers`` axis in the program."""
    d, V, L = m["d_model"], m["vocab_size"], m["n_layers"]
    hd = m["head_dim"]
    qd, kvd = m["n_heads"] * hd, m["n_kv_heads"] * hd
    specs = [("embed", None, (V, d), "embed"),
             ("final_norm", None, (d,), "norm")]
    if not m.get("tie_embeddings", False):
        specs.append(("lm_head", None, (d, V), "mat"))
    specs += [("layers/attn_norm", L, (d,), "norm"),
              ("layers/mlp_norm", L, (d,), "norm"),
              ("layers/attn/wq", L, (d, qd), "mat"),
              ("layers/attn/wk", L, (d, kvd), "mat"),
              ("layers/attn/wv", L, (d, kvd), "mat"),
              ("layers/attn/wo", L, (qd, d), "mat")]
    if m.get("qkv_bias", False):
        specs += [("layers/attn/bq", L, (qd,), "bias"),
                  ("layers/attn/bk", L, (kvd,), "bias"),
                  ("layers/attn/bv", L, (kvd,), "bias")]
    ff = m["d_ff"]
    if m["mlp_type"] == "swiglu":
        specs.append(("layers/mlp/w_gate", L, (d, ff), "mat"))
    specs += [("layers/mlp/w_up", L, (d, ff), "mat"),
              ("layers/mlp/w_down", L, (ff, d), "mat")]
    return specs


def layers(m: dict) -> List[Tuple[str, int]]:
    """The program's order of layers: (group, index in the group)."""
    return [("layers", i) for i in range(m["n_layers"])]


def _rope(x, pos, theta):
    """x: (T, H, hd); rotate the first and second halves of each head."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs           # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("m", "fp8", "block"))
def layer(h, w, *, m, fp8, block):
    """One decoder layer over a whole sequence h: (T, d), T % block == 0."""
    m = dict(m)
    T = h.shape[0]
    H, Hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]
    pos = jnp.arange(T)
    x = _rms(h, w["attn_norm"], eps)
    q, k, v = (_mm(x, w["wq"], fp8), _mm(x, w["wk"], fp8),
               _mm(x, w["wv"], fp8))
    if m.get("qkv_bias"):
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = _rope(q.reshape(T, H, hd), pos, m["rope_theta"])
    k = _rope(k.reshape(T, Hkv, hd), pos, m["rope_theta"])
    v = v.reshape(T, Hkv, hd)
    if fp8:
        q, k, v = _q8(q), _q8(k), _q8(v)
    o = attention(q, k, v, block)
    h = h + _mm(o, w["wo"], fp8)
    x = _rms(h, w["mlp_norm"], eps)
    if m["mlp_type"] == "swiglu":
        u = jax.nn.silu(_mm(x, w["w_gate"], fp8)) * _mm(x, w["w_up"], fp8)
    else:
        u = _gelu_tanh(_mm(x, w["w_up"], fp8))
    return h + _mm(u, w["w_down"], fp8)


def counts(m: dict, dtype: str) -> Counts:
    d, V, L = m["d_model"], m["vocab_size"], m["n_layers"]
    hd = m["head_dim"]
    qd, kvd = m["n_heads"] * hd, m["n_kv_heads"] * hd
    n_mlp = 3 if m["mlp_type"] == "swiglu" else 2
    per_layer_mm = d * qd + 2 * d * kvd + qd * d + n_mlp * d * m["d_ff"]
    per_layer_vec = 2 * d + ((qd + 2 * kvd) if m.get("qkv_bias") else 0)
    tied = m.get("tie_embeddings", False)
    head = d * V
    b = BYTES[dtype]
    return Counts(
        matmul_params=L * per_layer_mm + head,
        weight_params=L * (per_layer_mm + per_layer_vec) + d
        + (0 if tied else head),
        attn_width=qd,
        n_layers=L,
        kv_bytes_per_token=2 * L * kvd * b,
        dtype_bytes=b)
