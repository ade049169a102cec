"""shard_map expert-parallel MoE (inference path).

GSPMD cannot shard the data-dependent dispatch gather: with tokens on
"data" and the (E·C, d) buffer on "model" it falls back to mask +
all-reduce of the full buffer (~2×10 GB f32 per deepseek layer — see
EXPERIMENTS.md §Perf iteration 6). Under shard_map the structure is
explicit and fully local:

  - activations are replicated across "model" and sharded over "data"
    (the serving layout), so every (data_i, model_j) chip routes its OWN
    tokens locally;
  - each model shard owns E/16 experts (weights P("model", None, None))
    and computes only its experts over the local tokens;
  - one bf16 psum over "model" combines expert outputs per local token.

Per-layer collective cost: T_local × d × 2 B (the psum) — for deepseek
prefill_32k that is 64 MB vs ~39 GB under GSPMD.

Inference-only by design: expert weights are E/model-sharded (4.7 GB bf16
per chip for deepseek — fine without optimizer state; training keeps the
gather-based path where FSDP covers m/v).
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from .moe import router


def _local_moe(w_router, w_gate, w_up, w_down, shared, x, *,
               cfg: ModelConfig, capacity: int, model_axis: str, data_axis):
    """Per-shard body. x: (B_l, S, d) local tokens (replicated over model);
    w_gate/w_up: (E_l, d, ffe); w_down: (E_l, ffe, d).

    ``capacity`` is computed by the caller from the GLOBAL token count with
    the exact formula of the gather path — deriving it from the local T
    here would shrink the per-expert buffers by the data-shard count and
    drop tokens the gather path keeps."""
    moe = cfg.moe
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    T = xt.shape[0]
    E, k = moe.n_experts, moe.top_k
    E_l = w_gate.shape[0]
    m_idx = jax.lax.axis_index(model_axis)

    gate, idx, _ = router({"w_router": w_router}, xt, moe)

    N = T * k
    flat_e = idx.reshape(N)
    sort_ord = jnp.argsort(flat_e)
    se = flat_e[sort_ord]
    rank = jnp.arange(N) - jnp.searchsorted(se, se, side="left")
    slot = jnp.where(rank < capacity, se * capacity + rank, E * capacity)
    tok_of_assign = sort_ord // k
    inv = jnp.full((E * capacity + 1,), N, jnp.int32)
    inv = inv.at[slot].set(jnp.arange(N, dtype=jnp.int32), mode="drop")
    inv = inv[: E * capacity]
    filled = inv < N
    src_tok = jnp.where(filled, tok_of_assign[jnp.minimum(inv, N - 1)], 0)
    xe = (xt[src_tok] * filled[:, None].astype(xt.dtype)
          ).reshape(E, capacity, d)
    # only this shard's experts
    own = jax.lax.dynamic_slice_in_dim(xe, m_idx * E_l, E_l, axis=0)

    g = jnp.einsum("ecd,edf->ecf", own, w_gate)
    u = jnp.einsum("ecd,edf->ecf", own, w_up)
    h = jax.nn.silu(g) * u
    ye_own = jnp.einsum("ecf,efd->ecd", h, w_down)       # (E_l, C, d)

    # place own experts' outputs back into the full (E*C, d) frame
    ye_full = jnp.zeros((E * capacity + 1, d), xt.dtype)
    ye_full = jax.lax.dynamic_update_slice_in_dim(
        ye_full, ye_own.reshape(E_l * capacity, d),
        m_idx * E_l * capacity, axis=0)
    y_assign_sorted = ye_full[slot]
    y_assign = jnp.zeros((N, d), xt.dtype).at[sort_ord].set(y_assign_sorted)
    y = jnp.sum(y_assign.reshape(T, k, d) * gate[..., None].astype(xt.dtype),
                axis=1)
    # combine expert contributions across model shards (ONE bf16 psum)
    y = jax.lax.psum(y, model_axis)

    if moe.n_shared:
        sg = xt @ shared["w_gate"]
        su = xt @ shared["w_up"]
        y = y + (jax.nn.silu(sg) * su) @ shared["w_down"]
    return y.reshape(b, s, d)


def moe_ffn_shardmap(params: dict, x: jax.Array, cfg: ModelConfig, mesh,
                     data_axes=("data",), model_axis: str = "model"
                     ) -> Tuple[jax.Array, jax.Array]:
    """Drop-in for moe_ffn under an active mesh (inference)."""
    moe = cfg.moe
    # Capacity from the GLOBAL (pre-shard) token count, same formula as
    # moe_ffn: max(4, ceil(T*k/E*cf)) clamped to T.  Each shard then ranks
    # its local assignments against the global per-expert budget, so in the
    # no-drop regime (capacity >= demand) both dispatch paths process the
    # identical assignment set; under overflow the local ranking can only
    # over-admit relative to global ranking, never drop extra tokens.
    b, s, _ = x.shape
    T = b * s
    E, k = moe.n_experts, moe.top_k
    capacity = min(max(4, int(math.ceil(T * k / E * moe.capacity_factor))), T)
    body = functools.partial(_local_moe, cfg=cfg, capacity=capacity,
                             model_axis=model_axis, data_axis=data_axes)
    shared_spec = jax.tree_util.tree_map(lambda _: P(None, None),
                                         params.get("shared", {}))
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None),                       # router replicated
                  P(model_axis, None, None),           # w_gate
                  P(model_axis, None, None),           # w_up
                  P(model_axis, None, None),           # w_down
                  shared_spec,
                  P(data_axes, None, None)),           # x
        out_specs=P(data_axes, None, None),
        check_vma=False)
    y = fn(params["w_router"], params["experts"]["w_gate"],
           params["experts"]["w_up"], params["experts"]["w_down"],
           params.get("shared", {}), x)
    return y, jnp.float32(0.0)
