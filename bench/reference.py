"""Plain float32 reference of a dense decoder, and its float8 control.

The forward pass a configuration's ``model`` block describes, written
out in ``jax.numpy`` with ``default_matmul_precision("highest")``: token
embedding; per layer, RMSNorm, Q/K/V projections (with bias where the
configuration has it), rotary embedding on halves of each head, causal
attention with grouped K/V heads (one K/V head is MQA, as many as the
query heads is MHA), the output projection and the residual; RMSNorm
and a GELU (tanh form) or SwiGLU MLP and the residual; a final RMSNorm
and the head.  No cache, no batching, no padding tricks.  It imports
nothing of the program: its weights are drawn from the seed by the
benchmark's own generator, layer by layer, so that a published width fits
beside nothing else on the chip.

``mode="fp8"`` is the control: the same pass with every matrix product's
operands rounded to float8 (e4m3, scaled to the tensor's largest value),
the precision one step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp

from harness import weights

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(x: jax.Array) -> jax.Array:
    """Round to e4m3 at the tensor's own scale, back to float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(a, w, fp8: bool):
    if fp8:
        a, w = _q8(a), _q8(w)
    return a @ w


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


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
def _layer(h, w, *, m, fp8, block):
    """One decoder layer over a whole sequence h: (T, d), T % block == 0."""
    m = dict(m)
    T = h.shape[0]
    H, Hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    g = H // Hkv
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
    qb = q.reshape(T // block, block, Hkv, g, hd)

    def attend(args):
        qi, start = args
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k) / math.sqrt(hd)
        causal = jnp.arange(T)[None, :] <= (start + jnp.arange(block))[:, None]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v)

    starts = jnp.arange(T // block) * block
    o = jax.lax.map(attend, (qb, starts)).reshape(T, H * hd)
    h = h + _mm(o, w["wo"], fp8)
    x = _rms(h, w["mlp_norm"], eps)
    if m["mlp_type"] == "swiglu":
        u = jax.nn.silu(_mm(x, w["w_gate"], fp8)) * _mm(x, w["w_up"], fp8)
    else:
        u = _gelu_tanh(_mm(x, w["w_up"], fp8))
    return h + _mm(u, w["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(h, norm, head, *, eps, fp8):
    return _mm(_rms(h, norm, eps), head, fp8)


def logits(m: dict, seed: int, served_dtype, seqs: Sequence[List[int]],
           want: Sequence[Sequence[int]], mode: str = "f32",
           block: int = 512, pad_to: int = 2048) -> List[jax.Array]:
    """Logits (len(want[i]), V) at positions ``want[i]`` of each token
    sequence ``seqs[i]``: the logits there predict the next token.

    The weights are the benchmark's seeded draw in ``served_dtype``,
    widened to float32.  Each sequence is padded at its end to a multiple
    of ``pad_to`` (padding follows every real position, so under the
    causal mask it changes nothing); the layers run one at a time over
    every sequence, so one layer's weights are on the device at once."""
    fp8 = mode == "fp8"
    frozen = tuple(sorted((k, v) for k, v in m.items()
                          if not isinstance(v, (dict, list))))
    f32 = jnp.float32

    def up(x):
        return x.astype(f32)

    with jax.default_matmul_precision("highest"):
        embed = up(weights.top_leaf(m, seed, "embed", served_dtype))
        hs = []
        for ids in seqs:
            T = -(-len(ids) // pad_to) * pad_to
            tok = jnp.asarray(list(ids) + [0] * (T - len(ids)), jnp.int32)
            hs.append(embed[tok])
        head = (embed.T if m.get("tie_embeddings") else
                up(weights.top_leaf(m, seed, "lm_head", served_dtype)))
        del embed
        for layer in range(m["n_layers"]):
            w = {k: up(v) for k, v in weights.layer_leaves(
                m, seed, layer, served_dtype).items()}
            hs = [_layer(h, w, m=frozen, fp8=fp8, block=block) for h in hs]
            del w
        norm = up(weights.top_leaf(m, seed, "final_norm", served_dtype))
        out = []
        for h, pos in zip(hs, want):
            rows = h[jnp.asarray(list(pos), jnp.int32)]
            out.append(_head(rows, norm, head, eps=float(m["norm_eps"]),
                             fp8=fp8))
        return out


def sampling_noise(engine_seed: int, rid: int, steps: int, vocab: int,
                   dtype) -> jax.Array:
    """The Gumbel noise a keyed sampler at temperature 1 adds to a
    request's logits, (steps, vocab) as float32: key ``(seed + 1)``
    folded with the request id, then with the output step.  The served
    token is the first maximum of ``logits + noise``."""
    base = jax.random.fold_in(jax.random.key(engine_seed + 1), rid)
    padded = -(-steps // 128) * 128         # few shapes, few compiles
    return _noise(base, steps=padded, vocab=vocab, dtype=dtype)[:steps]


@functools.partial(jax.jit, static_argnames=("steps", "vocab", "dtype"))
def _noise(base, *, steps, vocab, dtype):
    keys = jax.vmap(lambda s: jax.random.fold_in(base, s))(
        jnp.arange(steps, dtype=jnp.int32))
    g = jax.vmap(lambda k: jax.random.gumbel(k, (vocab,), dtype))(keys)
    return g.astype(jnp.float32)
