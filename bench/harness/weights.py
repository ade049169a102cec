"""Seeded weights, made by the benchmark.

Every leaf is drawn from ``--seed`` alone, in the layout the serving
program takes (for the dense decoder ``{"embed", "final_norm",
"lm_head", "layers": {...}}`` with a leading layer axis), and in the
type it is served in.  Which leaves there are is the architecture
module's (``bench/archs/<arch>.py``, ``leaf_specs``): leaf ``i`` of its
list is drawn from the seed folded with ``i``, then with its index in
its layer group.  The plain reference (``bench/reference.py``) draws
the same leaves one layer at a time, so the two share the seed and
nothing the program has made.

Values are exact functions of threefry bits: a 24-bit integer scaled
into ``[-a, a)`` by exactly rounded float32 operations, then one cast to
the served type.  Fusion cannot change them, so a layer drawn alone
equals the same layer inside the whole-model draw, on any backend.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

# leaf kinds and their spread: "mat" is fan-in scaled (its second-to-last
# axis, so each matrix of a stack of experts keeps its own), "embed" a
# small table, "norm" a scale around 1, "bias" a small offset
_STD = {"embed": 0.02, "norm": 0.05, "bias": 0.02}


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
    std = _STD.get(kind, 1.0 / math.sqrt(shape[-2] if kind == "mat" else 1))
    x = u * jnp.float32(std * math.sqrt(3.0))                 # unit-std box
    if kind == "norm":
        x = x + 1.0
    return x.astype(dtype)


def make_params(m: dict, seed: int, dtype, arch) -> Dict:
    """All weights, in one jitted call on the default device.  ``arch``
    is the configuration's architecture module (``Cell.arch``)."""
    specs = arch.leaf_specs(m)

    @jax.jit
    def build(key):
        out: Dict = {}
        for idx, (path, stack, shape, kind) in enumerate(specs):
            if stack is None:
                leaf = draw(key, idx, 0, shape, kind, dtype)
            else:
                leaf = jnp.stack([draw(key, idx, l, shape, kind, dtype)
                                  for l in range(stack)])
            node = out
            parts = path.split("/")
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            node[parts[-1]] = leaf
        return out

    return build(root_key(seed))


def layer_leaves(m: dict, seed: int, layer: int, dtype,
                 arch) -> Dict[str, jax.Array]:
    """The leaves of the ``layer``-th layer in the program's order
    (``arch.layers``), drawn exactly as :func:`make_params` draws them:
    those of its group, at its index there (a leaf of the group with no
    stack is drawn once, for every layer of the group).  Each is named
    by the last part of its path (``wq``, ``attn_norm``), or by its path
    inside the group where two leaves of the group end alike."""
    group, i = arch.layers(m)[layer]
    key = root_key(seed)
    mine = [(idx, path[len(group) + 1:], stack, shape, kind)
            for idx, (path, stack, shape, kind)
            in enumerate(arch.leaf_specs(m)) if path.startswith(group + "/")]
    last = [rel.rsplit("/", 1)[-1] for _, rel, _, _, _ in mine]
    out = {}
    for (idx, rel, stack, shape, kind), name in zip(mine, last):
        out[rel if last.count(name) > 1 else name] = draw(
            key, idx, 0 if stack is None else i, shape, kind, dtype)
    return out


def top_leaf(m: dict, seed: int, name: str, dtype, arch) -> jax.Array:
    """``embed``, ``final_norm`` or ``lm_head``."""
    key = root_key(seed)
    for idx, (path, _, shape, kind) in enumerate(arch.leaf_specs(m)):
        if path == name:
            return draw(key, idx, 0, shape, kind, dtype)
    raise KeyError(name)
