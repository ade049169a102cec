"""Plain float32 reference of a decoder, and its float8 control.

The forward pass a configuration's ``model`` block describes, written
out in ``jax.numpy`` with ``default_matmul_precision("highest")``: token
embedding; each layer in the program's order, by the configuration's
architecture module (``bench/archs/<arch>.py``: the dense decoder's is
``archs/dense.py``); a final RMSNorm and the head.  No cache, no
batching, no padding tricks.  It imports nothing of the program: its
weights are drawn from the seed by the benchmark's own generator, layer
by layer, so that a published width fits beside nothing else on the
chip.  What every architecture shares is here: the float8 rounding of a
matrix product, RMSNorm and blocked causal attention.

``mode="fp8"`` is the control: the same pass with every matrix product's
operands rounded to float8 (e4m3, scaled to the tensor's largest value),
the precision one step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math
from typing import List, Sequence

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


def attention(q, k, v, block):
    """Causal attention of q: (T, H, hd) over k, v: (T, Hkv, hd) with
    grouped K/V heads (H % Hkv == 0), scores scaled by 1/sqrt(hd), one
    block of ``block`` query rows at a time (T % block == 0).  Returns
    (T, H * v's head size)."""
    T, H, hd = q.shape
    Hkv = k.shape[1]
    g = H // Hkv
    qb = q.reshape(T // block, block, Hkv, g, hd)

    def attend(args):
        qi, start = args
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k) / math.sqrt(hd)
        causal = jnp.arange(T)[None, :] <= (start + jnp.arange(block))[:, None]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v)

    starts = jnp.arange(T // block) * block
    return jax.lax.map(attend, (qb, starts)).reshape(T, H * v.shape[-1])


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(h, norm, head, *, eps, fp8):
    return _mm(_rms(h, norm, eps), head, fp8)


def _freeze(v):
    """A hashable copy of a configuration value: a dict as its sorted
    (key, value) pairs and a list as a tuple, all the way down."""
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    if isinstance(v, list):
        return tuple(_freeze(x) for x in v)
    return v


def logits(m: dict, seed: int, served_dtype, seqs: Sequence[List[int]],
           want: Sequence[Sequence[int]], arch, mode: str = "f32",
           block: int = 512, pad_to: int = 2048) -> List[jax.Array]:
    """Logits (len(want[i]), V) at positions ``want[i]`` of each token
    sequence ``seqs[i]``: the logits there predict the next token.

    ``arch`` is the configuration's architecture module (``Cell.arch``),
    which gives the layers.  The weights are the benchmark's seeded draw
    in ``served_dtype``, widened to float32.  Each sequence is padded at
    its end to a multiple of ``pad_to`` (padding follows every real
    position, so under the causal mask it changes nothing); the layers
    run one at a time over every sequence, so one layer's weights are on
    the device at once."""
    fp8 = mode == "fp8"
    frozen = _freeze(m)
    f32 = jnp.float32

    def up(x):
        return x.astype(f32)

    with jax.default_matmul_precision("highest"):
        embed = up(weights.top_leaf(m, seed, "embed", served_dtype, arch))
        hs = []
        for ids in seqs:
            T = -(-len(ids) // pad_to) * pad_to
            tok = jnp.asarray(list(ids) + [0] * (T - len(ids)), jnp.int32)
            hs.append(embed[tok])
        head = (embed.T if m.get("tie_embeddings") else
                up(weights.top_leaf(m, seed, "lm_head", served_dtype, arch)))
        del embed
        for layer in range(len(arch.layers(m))):
            w = {k: up(v) for k, v in weights.layer_leaves(
                m, seed, layer, served_dtype, arch).items()}
            hs = [arch.layer(h, w, m=frozen, fp8=fp8, block=block)
                  for h in hs]
            del w
        norm = up(weights.top_leaf(m, seed, "final_norm", served_dtype,
                                   arch))
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
