"""Seeded weights for a dense decoder, made by the benchmark.

Every leaf is drawn from ``--seed`` alone, in the layout the serving
program takes (``{"embed", "final_norm", "lm_head", "layers": {...}}``
with a leading layer axis), and in the type it is served in.  The plain
reference (``bench/reference.py``) draws the same leaves one layer at a
time, so the two share the seed and nothing the program has made.

Values are exact functions of threefry bits: a 24-bit integer scaled
into ``[-a, a)`` by exactly rounded float32 operations, then one cast to
the served type.  Fusion cannot change them, so a layer drawn alone
equals the same layer inside the whole-model draw, on any backend.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

# leaf kinds and their spread: "mat" is fan-in scaled, "embed" a small
# table, "norm" a scale around 1, "bias" a small offset
_STD = {"embed": 0.02, "norm": 0.05, "bias": 0.02}


def leaf_specs(m: dict) -> List[Tuple[str, Tuple[int, ...], str]]:
    """(path, per-layer shape, kind) of every leaf; paths under
    ``layers/`` carry a leading ``n_layers`` axis in the program."""
    d, V = m["d_model"], m["vocab_size"]
    hd = m["head_dim"]
    qd, kvd = m["n_heads"] * hd, m["n_kv_heads"] * hd
    specs = [("embed", (V, d), "embed"), ("final_norm", (d,), "norm")]
    if not m.get("tie_embeddings", False):
        specs.append(("lm_head", (d, V), "mat"))
    specs += [("layers/attn_norm", (d,), "norm"),
              ("layers/mlp_norm", (d,), "norm"),
              ("layers/attn/wq", (d, qd), "mat"),
              ("layers/attn/wk", (d, kvd), "mat"),
              ("layers/attn/wv", (d, kvd), "mat"),
              ("layers/attn/wo", (qd, d), "mat")]
    if m.get("qkv_bias", False):
        specs += [("layers/attn/bq", (qd,), "bias"),
                  ("layers/attn/bk", (kvd,), "bias"),
                  ("layers/attn/bv", (kvd,), "bias")]
    ff = m["d_ff"]
    if m["mlp_type"] == "swiglu":
        specs.append(("layers/mlp/w_gate", (d, ff), "mat"))
    specs += [("layers/mlp/w_up", (d, ff), "mat"),
              ("layers/mlp/w_down", (ff, d), "mat")]
    return specs


def root_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, also one wider than 32 bits."""
    k = jax.random.key(seed % (1 << 32))
    return jax.random.fold_in(k, seed >> 32)


def draw(key: jax.Array, idx: int, layer: int, shape, kind: str,
         dtype) -> jax.Array:
    """One leaf (or one layer of a stacked leaf)."""
    k = jax.random.fold_in(jax.random.fold_in(key, idx), layer)
    bits = jax.random.bits(k, shape, jnp.uint32) >> 8        # 24 bits
    u = bits.astype(jnp.float32) * jnp.float32(2.0 ** -23) - 1.0
    std = _STD.get(kind, 1.0 / math.sqrt(shape[0] if kind == "mat" else 1))
    x = u * jnp.float32(std * math.sqrt(3.0))                 # unit-std box
    if kind == "norm":
        x = x + 1.0
    return x.astype(dtype)


def make_params(m: dict, seed: int, dtype) -> Dict:
    """All weights, in one jitted call on the default device."""
    specs = leaf_specs(m)
    L = m["n_layers"]

    @jax.jit
    def build(key):
        out: Dict = {}
        for idx, (path, shape, kind) in enumerate(specs):
            if path.startswith("layers/"):
                leaf = jnp.stack([draw(key, idx, l, shape, kind, dtype)
                                  for l in range(L)])
            else:
                leaf = draw(key, idx, 0, shape, kind, dtype)
            node = out
            parts = path.split("/")
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            node[parts[-1]] = leaf
        return out

    return build(root_key(seed))


def layer_leaves(m: dict, seed: int, layer: int, dtype) -> Dict[str, jax.Array]:
    """One layer's leaves by short name (``wq``, ``attn_norm``, ...),
    drawn exactly as :func:`make_params` draws them."""
    key = root_key(seed)
    out = {}
    for idx, (path, shape, kind) in enumerate(leaf_specs(m)):
        if path.startswith("layers/"):
            out[path.rsplit("/", 1)[1]] = draw(key, idx, layer, shape, kind,
                                               dtype)
    return out


def top_leaf(m: dict, seed: int, name: str, dtype) -> jax.Array:
    """``embed``, ``final_norm`` or ``lm_head``."""
    key = root_key(seed)
    for idx, (path, shape, kind) in enumerate(leaf_specs(m)):
        if path == name:
            return draw(key, idx, 0, shape, kind, dtype)
    raise KeyError(name)
