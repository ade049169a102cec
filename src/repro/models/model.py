"""Unified decoder LM covering all six assigned architecture families.

Entry points (all pure functions over a params pytree):
  forward(params, cfg, tokens, frontend_embeds=None)  -> logits (train path)
  loss_fn(params, cfg, batch)                          -> (loss, metrics)
  prefill(params, cfg, tokens, frontend_embeds=None)   -> (last_logits, cache)
  decode_step(params, cfg, cache, token, pos)          -> (logits, cache)
      pos: scalar OR (B,) per-sequence position vector (slot batching)
  init_cache(cfg, batch, cache_len, dtype)             -> cache pytree

Layers are lax.scan-stacked; hybrid (Zamba2) uses a two-level scan with a
weight-shared attention block closed over by the group body.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import layers as L
from .moe import moe_ffn
from .ssm import mamba2_block
from .sharding_ctx import constrain

Params = Dict[str, Any]

# When True, lax.scan over layers is fully unrolled. Used by the roofline
# probes (repro.launch.probe): XLA cost_analysis counts while-bodies once,
# so probes compile small unrolled variants to get per-layer costs.
SCAN_UNROLL = False

# When set to a Mesh, MoE layers use the shard_map expert-parallel path
# (inference; see repro.models.moe_shardmap).
MOE_SHARDMAP_MESH = None

# Remat policy for the layer scan: None = full remat (recompute everything
# in backward), "dots" = save matmul outputs, recompute only cheap
# elementwise ops (jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
REMAT_POLICY = None


def _checkpoint(f):
    if REMAT_POLICY == "dots":
        return jax.checkpoint(
            f, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(f)


def _scan(body, init, xs):
    return jax.lax.scan(body, init, xs, unroll=True if SCAN_UNROLL else 1)


# ---------------------------------------------------------------------------
# Layer bodies


def _attn_block(p: Params, x: jax.Array, cfg: ModelConfig,
                positions: jax.Array, window: int) -> Tuple[jax.Array, jax.Array]:
    h = L.rms_norm(x, p["attn_norm"], cfg.norm_eps)
    if cfg.attention == "mla":
        a = L.mla_attention(p["attn"], h, cfg, positions, window)
    else:
        a = L.gqa_attention(p["attn"], h, cfg, positions, window)
    x = x + a
    h = L.rms_norm(x, p["mlp_norm"], cfg.norm_eps)
    if cfg.is_moe:
        if MOE_SHARDMAP_MESH is not None:
            from .moe_shardmap import moe_ffn_shardmap
            y, aux = moe_ffn_shardmap(p["moe"], h, cfg, MOE_SHARDMAP_MESH)
        else:
            y, aux = moe_ffn(p["moe"], h, cfg)
    else:
        y, aux = L.mlp(p["mlp"], h, cfg.mlp_type), jnp.float32(0.0)
    return x + y, aux


def _ssm_block(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    h = L.rms_norm(x, p["norm"], cfg.norm_eps)
    return x + mamba2_block(p["ssm"], h, cfg)


# ---------------------------------------------------------------------------
# Embedding / head


def _embed(params: Params, cfg: ModelConfig, tokens: jax.Array,
           frontend_embeds: Optional[jax.Array]) -> jax.Array:
    x = jnp.take(params["embed"], tokens, axis=0)
    if frontend_embeds is not None:
        x = jnp.concatenate([frontend_embeds.astype(x.dtype), x], axis=1)
    return constrain(x, "activations")


def _lm_head(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T
    else:
        logits = x @ params["lm_head"]
    return constrain(logits, "logits")


# ---------------------------------------------------------------------------
# Forward (training / scoring)


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            frontend_embeds: Optional[jax.Array] = None,
            remat: bool = True,
            return_hidden: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Returns (logits (B, S_total, V), aux_loss); with
    ``return_hidden``, returns the final-norm hidden states instead of
    logits (chunked-xent path)."""
    x = _embed(params, cfg, tokens, frontend_embeds)
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    window = cfg.sliding_window

    if cfg.arch_type == "hybrid":
        x, aux = _hybrid_stack(params, cfg, x, positions, window, remat)
        if return_hidden:
            return L.rms_norm(x, params["final_norm"], cfg.norm_eps), aux
        return _lm_head(params, cfg, x), aux
    elif cfg.arch_type == "ssm":
        def body(carry, lp):
            return _ssm_block(lp, carry, cfg), None
        if remat:
            body = _checkpoint(body)
        x, _ = _scan(body, x, params["layers"])
        aux = jnp.float32(0.0)
        if return_hidden:
            return L.rms_norm(x, params["final_norm"], cfg.norm_eps), aux
        return _lm_head(params, cfg, x), aux
    else:
        def body(carry, lp):
            x, aux = carry
            x, a = _attn_block(lp, x, cfg, positions, window)
            return (x, aux + a), None
        if remat:
            body = _checkpoint(body)
        (x, aux), _ = _scan(body, (x, jnp.float32(0.0)), params["layers"])
    if return_hidden:
        return L.rms_norm(x, params["final_norm"], cfg.norm_eps), aux
    return _lm_head(params, cfg, x), aux


def _hybrid_stack(params, cfg, x, positions, window, remat):
    def ssm_body(carry, lp):
        return _ssm_block(lp, carry, cfg), None
    if remat:
        ssm_body = _checkpoint(ssm_body)

    shared = params["shared_attn"]

    def group_body(carry, gp):
        x, aux = carry
        x, _ = _scan(ssm_body, x, gp)
        x, a = _attn_block(shared, x, cfg, positions, window)
        return (x, aux + a), None

    (x, aux), _ = _scan(group_body, (x, jnp.float32(0.0)),
                               params["groups"])
    if "rem" in params:
        x, _ = _scan(ssm_body, x, params["rem"])
    return x, aux


# >0: cross-entropy computed in sequence chunks of this many positions —
# the (B, S, V) logits tensor never materializes (peak-memory lever for
# large-vocab training; EXPERIMENTS.md §Perf deepseek iteration 7).
XENT_CHUNK = 0


def _chunked_xent(params, cfg, hidden, targets):
    """hidden: (B, S, d) final-norm states; targets: (B, S) int32."""
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"])
    B, S, d = hidden.shape
    C = XENT_CHUNK
    pad = (-S) % C
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
    nc = (S + pad) // C
    hc = hidden.reshape(B, nc, C, d).transpose(1, 0, 2, 3)
    tc = targets.reshape(B, nc, C).transpose(1, 0, 2)
    valid = (jnp.arange(S + pad) < S).reshape(nc, C)

    @jax.checkpoint
    def chunk(carry, inp):
        h, t, v = inp                              # (B,C,d),(B,C),(C,)
        logits = (h @ head).astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
        return carry + jnp.sum((logz - gold) * v[None, :]), None

    total, _ = jax.lax.scan(chunk, jnp.float32(0.0), (hc, tc, valid))
    return total / (B * S)


def loss_fn(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array]
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """batch: {"tokens": (B,S), optional "frontend_embeds": (B,P,d)}.
    Next-token loss over the token positions only."""
    tokens = batch["tokens"]
    fe = batch.get("frontend_embeds")
    if XENT_CHUNK:
        hidden, aux = forward(params, cfg, tokens, fe, return_hidden=True)
        P = 0 if fe is None else fe.shape[1]
        if P == 0:
            h = hidden[:, :-1]
            tgt = tokens[:, 1:]
        else:
            h = hidden[:, P - 1:-1]
            tgt = tokens
        nll = _chunked_xent(params, cfg, h, tgt)
        loss = nll + aux
        return loss, {"nll": nll, "aux": aux}
    logits, aux = forward(params, cfg, tokens, fe)
    P = 0 if fe is None else fe.shape[1]
    # logits position P+i-1 predicts tokens[:, i]
    if P == 0:
        pred = logits[:, :-1]
        tgt = tokens[:, 1:]
    else:
        pred = logits[:, P - 1:-1]
        tgt = tokens
    pred = pred.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(pred, axis=-1)
    gold = jnp.take_along_axis(pred, tgt[..., None], axis=-1)[..., 0]
    nll = jnp.mean(logz - gold)
    loss = nll + aux
    return loss, {"nll": nll, "aux": aux}


# ---------------------------------------------------------------------------
# Caches


def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               dtype=jnp.float32) -> Dict[str, Any]:
    window = cfg.sliding_window
    C = min(cache_len, window) if window else cache_len

    def gqa_cache(stack=()):
        shape = (*stack, batch, C, cfg.n_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def mla_cache(stack=()):
        m = cfg.mla
        return {"ckv": jnp.zeros((*stack, batch, C, m.kv_lora_rank), dtype),
                "kpe": jnp.zeros((*stack, batch, C, m.qk_rope_dim), dtype)}

    def ssm_state(stack=()):
        s = cfg.ssm
        di = s.d_inner(cfg.d_model)
        nh = s.n_heads(cfg.d_model)
        ch = di + 2 * s.d_state
        return {
            "conv": jnp.zeros((*stack, batch, s.conv_width - 1, ch), dtype),
            "ssd": jnp.zeros((*stack, batch, nh, s.head_dim, s.d_state), dtype),
        }

    if cfg.arch_type == "hybrid":
        G = cfg.n_layers // cfg.hybrid_attn_every
        per = cfg.hybrid_attn_every - 1
        R = cfg.n_layers - G * cfg.hybrid_attn_every
        cache = {"groups": ssm_state((G, per)),
                 "attn": (mla_cache((G,)) if cfg.attention == "mla"
                          else gqa_cache((G,)))}
        if R:
            cache["rem"] = ssm_state((R,))
        return cache
    if cfg.arch_type == "ssm":
        return {"layers": ssm_state((cfg.n_layers,))}
    if cfg.attention == "mla":
        return {"layers": mla_cache((cfg.n_layers,))}
    return {"layers": gqa_cache((cfg.n_layers,))}


# ---------------------------------------------------------------------------
# Paged caches (block-paged serving; see repro.serving.paging)
#
# The paged decode cache replaces each attention leaf's dense
# (stack, B, C, ...) layout with a block *pool* (stack, n_blocks,
# block_size, ...) plus per-sequence block tables (B, max_blocks) int32.
# Row r of sequence b lives at pool[..., table[b, r // bs], r % bs, ...]
# — every attention-cache leaf (k/v/ckv/kpe) has its block axes at tree
# positions 1 and 2, so gather/scatter are uniform tree_maps.
#
# Paged decode (``paged_decode_rows`` + ``scatter_decode_rows``, one
# donated program in the scheduler) never holds a whole-cache view: each
# layer of its scan gathers that layer's dense (B, C, ...) view — the
# exact rows the contiguous cache holds at those positions, junk rows
# from unallocated table entries masked by decode's validity mask like
# the contiguous cache's zero rows — and attends over it through the
# same layer body as ``decode_step``, with the new row merged in by a
# select rather than written.  Only the B new rows leave the scan, and
# they are written into the pool in place.  Same values, same shapes,
# same reductions: paged decode is bit-identical to contiguous on the
# CPU.  What it still reads is each slot's full table width (trash
# blocks included); the kernel that reads live blocks only is
# ``repro.kernels.decode_attention.paged_decode_attention``.
# ``gather_cache`` materializes a whole view for the batch-1 admissions
# that resume from shared prefix blocks.


def supports_paged_cache(cfg: ModelConfig) -> bool:
    """Paged layout covers the attention-cache archs with absolute
    positions (GQA/MLA, no sliding-window ring, no frontend offset, no
    recurrent state — SSM/hybrid states are position-free and gain
    nothing from paging)."""
    return (cfg.arch_type not in ("ssm", "hybrid")
            and not cfg.sliding_window and not cfg.frontend)


def init_paged_cache(cfg: ModelConfig, n_blocks: int, block_size: int,
                     dtype=jnp.float32) -> Dict[str, Any]:
    """Block pool pytree: ``init_cache``'s attention leaves with the
    (batch, cache_len) axes replaced by (n_blocks, block_size)."""
    if not supports_paged_cache(cfg):
        raise NotImplementedError(
            f"paged KV covers attention-cache archs; {cfg.name} "
            f"({cfg.arch_type}) keeps the contiguous layout")
    if cfg.attention == "mla":
        m = cfg.mla
        return {"layers": {
            "ckv": jnp.zeros((cfg.n_layers, n_blocks, block_size,
                              m.kv_lora_rank), dtype),
            "kpe": jnp.zeros((cfg.n_layers, n_blocks, block_size,
                              m.qk_rope_dim), dtype)}}
    shape = (cfg.n_layers, n_blocks, block_size, cfg.n_kv_heads,
             cfg.head_dim)
    return {"layers": {"k": jnp.zeros(shape, dtype),
                       "v": jnp.zeros(shape, dtype)}}


def gather_cache(pool: Dict[str, Any], tables: jax.Array) -> Dict[str, Any]:
    """Materialize the dense cache view of ``tables`` (B, max_blocks)
    from a block pool: leaf (L, NB, bs, ...) -> (L, B, max_blocks*bs, ...).
    Unallocated table entries point at the trash block — their junk rows
    sit beyond every sequence's valid length and are masked by decode."""
    def g(leaf):
        v = leaf[:, tables]                    # (L, B, MB, bs, ...)
        return v.reshape(v.shape[0], v.shape[1], v.shape[2] * v.shape[3],
                         *v.shape[4:])
    return jax.tree_util.tree_map(g, pool)


def scatter_cache(pool: Dict[str, Any], cache: Dict[str, Any],
                  table: jax.Array, start: jax.Array) -> Dict[str, Any]:
    """Write a batch-1 dense cache's rows into the pool blocks of one
    sequence.  ``table``: (max_blocks,) int32; rows with position <
    ``start`` are redirected to the trash block (prefix-cache hits: the
    leading blocks are SHARED and already hold identical data — they are
    never rewritten), as are rows in unallocated tail blocks (their
    table entries already point at trash).  One trace total: the write
    always covers the full cache length."""
    def s(pool_leaf, cache_leaf):
        bs = pool_leaf.shape[2]
        c = cache_leaf.shape[2]
        positions = jnp.arange(c)
        blk = table[positions // bs]
        trash = pool_leaf.shape[1] - 1
        blk = jnp.where(positions < start, trash, blk)
        return pool_leaf.at[:, blk, positions % bs].set(
            cache_leaf[:, 0].astype(pool_leaf.dtype))
    return jax.tree_util.tree_map(s, pool, cache)


def _dense_view(leaf: jax.Array, layer: jax.Array,
                tables: jax.Array) -> jax.Array:
    """One layer's dense view, gathered straight from the stacked pool
    leaf (L, NB, bs, ...) through ``tables`` (B, max_blocks) -> (B,
    max_blocks * bs, ...).  Slicing the layer out first would make XLA
    copy the whole layer of the pool before the gather."""
    v = leaf[layer, tables]                    # (B, MB, bs, ...)
    return v.reshape(v.shape[0], v.shape[1] * v.shape[2], *v.shape[3:])


def scatter_decode_rows(pool: Dict[str, Any], rows: Dict[str, Any],
                        tables: jax.Array, pos: jax.Array) -> Dict[str, Any]:
    """Write each sequence's new decode row into the pool, in place when
    the pool is donated.  ``rows``: the pool tree with leaves (L, B, ...)
    from :func:`paged_decode_rows`; ``tables``: (B, max_blocks) int32 —
    dead slots' all-trash tables land their rows in the trash block.
    One ``dynamic_update_slice`` of (L, 1, 1, ...) per sequence, which
    XLA does in place: a scatter over the block axes behind the layer
    axis made it copy the whole pool leaf, in and out, on the TPU."""
    b = tables.shape[0]
    pos = L.decode_positions(pos, b)

    def s(pool_leaf, row_leaf):
        bs = pool_leaf.shape[2]
        slot = L.cache_slots(pos, tables.shape[1] * bs, 0)
        blk = tables[jnp.arange(b), slot // bs]
        off = slot % bs
        row_leaf = row_leaf.astype(pool_leaf.dtype)
        zeros = (0,) * (pool_leaf.ndim - 3)
        for i in range(b):
            pool_leaf = jax.lax.dynamic_update_slice(
                pool_leaf, row_leaf[:, i, None, None],
                (0, blk[i], off[i]) + zeros)
        return pool_leaf
    return jax.tree_util.tree_map(s, pool, rows)


def copy_block(pool: Dict[str, Any], src: jax.Array,
               dst: jax.Array) -> Dict[str, Any]:
    """Copy one physical block (copy-on-write fork: the allocator moved
    a shared reference onto ``dst``; the data follows here)."""
    return jax.tree_util.tree_map(
        lambda leaf: leaf.at[:, dst].set(leaf[:, src]), pool)


# ---------------------------------------------------------------------------
# Prefill


def prefill(params: Params, cfg: ModelConfig, tokens: jax.Array,
            frontend_embeds: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Full-sequence prefill; returns (last-position logits, cache of len S).

    Note: the serving engine copies this cache into its ring/max-len buffers;
    for dry-run purposes the cache length equals the prompt length.
    """
    x = _embed(params, cfg, tokens, frontend_embeds)
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    window = cfg.sliding_window

    if cfg.arch_type in ("ssm", "hybrid"):
        return _recurrent_prefill(params, cfg, x, positions, window)

    def body(x, lp):
        h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        if cfg.attention == "mla":
            a, kv = L.mla_prefill(lp["attn"], h, cfg, positions, window)
        else:
            a, kv = L.gqa_prefill(lp["attn"], h, cfg, positions, window)
        x = x + a
        h = L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        if cfg.is_moe:
            if MOE_SHARDMAP_MESH is not None:
                from .moe_shardmap import moe_ffn_shardmap
                y, _ = moe_ffn_shardmap(lp["moe"], h, cfg,
                                        MOE_SHARDMAP_MESH)
            else:
                y, _ = moe_ffn(lp["moe"], h, cfg)
        else:
            y = L.mlp(lp["mlp"], h, cfg.mlp_type)
        return x + y, kv

    x, cache = _scan(body, x, params["layers"])
    logits = _lm_head(params, cfg, x[:, -1:])
    return logits[:, 0], {"layers": cache}


def _recurrent_prefill(params, cfg, x, positions, window):
    def ssm_body(x, lp):
        h = L.rms_norm(x, lp["norm"], cfg.norm_eps)
        out, st = mamba2_block(lp["ssm"], h, cfg, return_state=True)
        return x + out, st

    if cfg.arch_type == "ssm":
        x, states = _scan(ssm_body, x, params["layers"])
        logits = _lm_head(params, cfg, x[:, -1:])
        return logits[:, 0], {"layers": states}

    shared = params["shared_attn"]

    def group_body(x, gp):
        x, st = _scan(ssm_body, x, gp)
        h = L.rms_norm(x, shared["attn_norm"], cfg.norm_eps)
        a, kv = L.gqa_prefill(shared["attn"], h, cfg, positions, window)
        x = x + a
        h = L.rms_norm(x, shared["mlp_norm"], cfg.norm_eps)
        x = x + L.mlp(shared["mlp"], h, cfg.mlp_type)
        return x, {"ssm": st, "attn": kv}

    x, out = _scan(group_body, x, params["groups"])
    cache = {"groups": out["ssm"], "attn": out["attn"]}
    if "rem" in params:
        x, st = _scan(ssm_body, x, params["rem"])
        cache["rem"] = st
    logits = _lm_head(params, cfg, x[:, -1:])
    return logits[:, 0], cache


# ---------------------------------------------------------------------------
# Fixed-shape prefill (serving admission: bucketed batches + chunks)


def prefill_attend(params: Params, cfg: ModelConfig, cache: Dict[str, Any],
                   tokens: jax.Array, off: jax.Array, lengths: jax.Array
                   ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Prefill ``tokens`` (B, L) into an explicit full-length cache at
    absolute offset ``off``.

    The serving engine's fixed-shape prefill recipe: rows are
    right-padded to a shared length L (a power-of-two bucket or a chunk),
    K/V are scattered into the cache at absolute positions, and every
    query attends over the full cache width under a validity mask — so
    the attention reduction shape never depends on the prompt length.
    One jitted trace serves a whole bucket (no per-length recompiles),
    and a prompt prefilled whole, in chunks, or inside a batch produces
    bit-identical cache rows and logits.

    tokens: (B, L) int32 right-padded rows; off: scalar int32 absolute
    position of column 0 (0 for whole prompts, the running offset for
    chunk continuation); lengths: (B,) valid token counts in this call.
    Returns (logits (B, V) at each row's last valid position, new cache).
    Attention-cache archs without sliding window / frontend only —
    recurrent-state archs keep the exact-length recipe
    (:func:`prefill`).
    """
    if cfg.arch_type in ("ssm", "hybrid") or cfg.sliding_window or cfg.frontend:
        raise NotImplementedError(
            "fixed-shape prefill covers non-windowed attention caches; "
            f"{cfg.name} ({cfg.arch_type}) uses the exact-length recipe")
    x = _embed(params, cfg, tokens, None)
    b, s, _ = x.shape
    positions = off + jnp.arange(s)[None, :]

    def body(x, inp):
        lp, kv = inp
        h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        if cfg.attention == "mla":
            a, kv2 = L.mla_prefill_attend(lp["attn"], h, kv, cfg, positions)
        else:
            a, kv2 = L.gqa_prefill_attend(lp["attn"], h, kv, cfg, positions)
        x = x + a
        h = L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        if cfg.is_moe:
            if MOE_SHARDMAP_MESH is not None:
                from .moe_shardmap import moe_ffn_shardmap
                y, _ = moe_ffn_shardmap(lp["moe"], h, cfg, MOE_SHARDMAP_MESH)
            else:
                y, _ = moe_ffn(lp["moe"], h, cfg)
        else:
            y = L.mlp(lp["mlp"], h, cfg.mlp_type)
        return x + y, kv2

    x, kvs = _scan(body, x, (params["layers"], cache["layers"]))
    idx = jnp.clip(lengths - 1, 0, s - 1)[:, None, None]
    hid = jnp.take_along_axis(x, jnp.broadcast_to(idx, (b, 1, x.shape[-1])),
                              axis=1)
    logits = _lm_head(params, cfg, hid)
    return logits[:, 0], {"layers": kvs}


def prefill_fresh(params: Params, cfg: ModelConfig, tokens: jax.Array,
                  lengths: jax.Array, cache_len: int
                  ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Whole-prompt fixed-shape prefill: a zero cache of ``cache_len``
    built inside the jit, then :func:`prefill_attend` at offset 0 —
    THE admission recipe for bucketed (batched) prefill."""
    cache = init_cache(cfg, tokens.shape[0], cache_len,
                       dtype=params["embed"].dtype)
    return prefill_attend(params, cfg, cache, tokens, jnp.int32(0), lengths)


# ---------------------------------------------------------------------------
# Decode


def decode_step(params: Params, cfg: ModelConfig, cache: Dict[str, Any],
                token: jax.Array, pos: jax.Array
                ) -> Tuple[jax.Array, Dict[str, Any]]:
    """token: (B, 1) int32; pos: scalar int32 absolute position shared by
    the batch, OR a (B,) int32 vector of per-sequence positions — the
    continuous-batching serving path advances all live slots in one call,
    each at its own position.  Returns (logits (B, V), new cache)."""
    x = jnp.take(params["embed"], token, axis=0)
    x = constrain(x, "activations")
    window = cfg.sliding_window
    pos = L.decode_positions(pos, token.shape[0])

    if cfg.arch_type == "hybrid":
        x, new_cache = _hybrid_decode(params, cfg, cache, x, pos, window)
    elif cfg.arch_type == "ssm":
        def body(x, inp):
            lp, st = inp
            h = L.rms_norm(x, lp["norm"], cfg.norm_eps)
            out, st2 = mamba2_block(lp["ssm"], h, cfg, state=st)
            return x + out, st2
        x, states = _scan(body, x, (params["layers"], cache["layers"]))
        new_cache = {"layers": states}
    else:
        attn = L.mla_decode if cfg.attention == "mla" else L.gqa_decode

        def body(x, inp):
            lp, kv = inp
            return _decode_block(
                lp, x, cfg, lambda p, h: attn(p, h, kv, cfg, pos, window))
        x, kvs = _scan(body, x, (params["layers"], cache["layers"]))
        new_cache = {"layers": kvs}

    logits = _lm_head(params, cfg, x)
    return logits[:, 0], new_cache


def _decode_block(lp: Params, x: jax.Array, cfg: ModelConfig, attention
                  ) -> Tuple[jax.Array, Dict[str, Any]]:
    """One attention-cache layer at decode: ``attention(attn_params, h)
    -> (a, kv)`` on the normed input, then the MLP or MoE.  Returns (x,
    kv) — the body the contiguous and the paged decode share."""
    h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    a, kv2 = attention(lp["attn"], h)
    x = x + a
    h = L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    if cfg.is_moe:
        y, _ = moe_ffn(lp["moe"], h, cfg)
    else:
        y = L.mlp(lp["mlp"], h, cfg.mlp_type)
    return x + y, kv2


def paged_decode_rows(params: Params, cfg: ModelConfig,
                      pool: Dict[str, Any], tables: jax.Array,
                      token: jax.Array, pos: jax.Array
                      ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Decode against the block pool without a whole-cache view: one scan
    over the layers gathers each layer's dense view through ``tables``
    (B, max_blocks) — a per-layer temporary, (B, max_blocks * bs, ...) —
    and attends over it with each sequence's new row merged in
    (``L.gqa_decode_view`` / ``L.mla_decode_view``: the values and shapes
    :func:`decode_step` attends over, the view never written).  Returns
    (logits (B, V), rows): ``rows`` is the pool tree with leaves
    (L, B, ...), the new rows for :func:`scatter_decode_rows`."""
    x = jnp.take(params["embed"], token, axis=0)
    x = constrain(x, "activations")
    pos = L.decode_positions(pos, token.shape[0])
    attn = L.mla_decode_view if cfg.attention == "mla" else L.gqa_decode_view

    def body(x, inp):
        lp, layer = inp
        view = jax.tree_util.tree_map(
            lambda leaf: _dense_view(leaf, layer, tables), pool["layers"])
        return _decode_block(lp, x, cfg,
                             lambda p, h: attn(p, h, view, cfg, pos))

    x, rows = _scan(body, x, (params["layers"], jnp.arange(cfg.n_layers)))
    logits = _lm_head(params, cfg, x)
    return logits[:, 0], {"layers": rows}


def _hybrid_decode(params, cfg, cache, x, pos, window):
    shared = params["shared_attn"]

    def ssm_body(x, inp):
        lp, st = inp
        h = L.rms_norm(x, lp["norm"], cfg.norm_eps)
        out, st2 = mamba2_block(lp["ssm"], h, cfg, state=st)
        return x + out, st2

    def group_body(x, inp):
        gp, st, kv = inp
        x, st2 = _scan(ssm_body, x, (gp, st))
        h = L.rms_norm(x, shared["attn_norm"], cfg.norm_eps)
        a, kv2 = L.gqa_decode(shared["attn"], h, kv, cfg, pos, window)
        x = x + a
        h = L.rms_norm(x, shared["mlp_norm"], cfg.norm_eps)
        x = x + L.mlp(shared["mlp"], h, cfg.mlp_type)
        return x, (st2, kv2)

    x, (sts, kvs) = _scan(
        group_body, x, (params["groups"], cache["groups"], cache["attn"]))
    new_cache = {"groups": sts, "attn": kvs}
    if "rem" in params:
        x, st = _scan(ssm_body, x, (params["rem"], cache["rem"]))
        new_cache["rem"] = st
    return x, new_cache
