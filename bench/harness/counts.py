"""The work a dense decoder needs, counted from its sizes.

These are the yardstick's counts of operations and bytes, kept with the
benchmark so that no change to the program can move them: what a token
or a decode step needs, not what an implementation happens to do.
"""
from __future__ import annotations

import dataclasses

_BYTES = {"bfloat16": 2, "float32": 4}


@dataclasses.dataclass(frozen=True)
class Counts:
    """Per-model constants; ``m`` is a configuration's ``model`` block."""
    matmul_params: int      # weights each token multiplies (incl. the head)
    weight_params: int      # every weight a decode step reads (no embedding)
    attn_width: int         # n_heads * head_dim
    n_layers: int
    kv_bytes_per_token: int  # K and V of one position, all layers
    dtype_bytes: int

    @classmethod
    def of(cls, m: dict, dtype: str) -> "Counts":
        d, V, L = m["d_model"], m["vocab_size"], m["n_layers"]
        hd = m["head_dim"]
        qd, kvd = m["n_heads"] * hd, m["n_kv_heads"] * hd
        n_mlp = 3 if m["mlp_type"] == "swiglu" else 2
        per_layer_mm = d * qd + 2 * d * kvd + qd * d + n_mlp * d * m["d_ff"]
        per_layer_vec = 2 * d + ((qd + 2 * kvd) if m.get("qkv_bias") else 0)
        tied = m.get("tie_embeddings", False)
        head = d * V
        b = _BYTES[dtype]
        return cls(
            matmul_params=L * per_layer_mm + head,
            weight_params=L * (per_layer_mm + per_layer_vec) + d
            + (0 if tied else head),
            attn_width=qd,
            n_layers=L,
            kv_bytes_per_token=2 * L * kvd * b,
            dtype_bytes=b)

    def token_flops(self, ctx: int) -> int:
        """Operations one token needs at a context of ``ctx`` positions
        (itself included): every weight once (multiply and add), and
        attention's scores and weighted sum over the context."""
        return 2 * self.matmul_params + 4 * self.n_layers * self.attn_width * ctx

    def decode_step_bytes(self, ctxs) -> int:
        """Least bytes one decode step moves: the weights once, the K/V
        of every live slot's context, and the one row each slot writes.
        Not the padded cache, not copies of it."""
        return (self.weight_params * self.dtype_bytes
                + sum(int(c) for c in ctxs) * self.kv_bytes_per_token)
