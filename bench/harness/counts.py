"""The work a decoder needs, counted from its sizes.

These are the yardstick's counts of operations and bytes, kept with the
benchmark so that no change to the program can move them: what a token
or a decode step needs, not what an implementation happens to do.  Each
architecture module (``bench/archs/<arch>.py``, ``counts``) fills them
from a configuration's sizes; one whose work the two formulas below do
not describe returns a subclass that overrides them.
"""
from __future__ import annotations

import dataclasses

BYTES = {"bfloat16": 2, "float32": 4}


@dataclasses.dataclass(frozen=True)
class Counts:
    """Per-model constants of a configuration's ``model`` block."""
    matmul_params: int      # weights each token multiplies (incl. the head)
    weight_params: int      # every weight a decode step reads (no embedding)
    attn_width: int         # n_heads * head_dim
    n_layers: int
    kv_bytes_per_token: int  # K and V of one position, all layers
    dtype_bytes: int

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
