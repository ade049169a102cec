"""Core transformer layers: RMSNorm, RoPE, MLPs, GQA and MLA attention.

Pure-function style: every layer is ``f(params: dict, x, ...) -> y``.
Parameter dictionaries are created in ``repro.models.params``.

Decode variants operate on an explicit KV cache and one new token per
sequence. Caches are plain dicts of arrays so they shard/scan cleanly.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .sharding_ctx import constrain

# ---------------------------------------------------------------------------
# Norms & MLP


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(dtype)


def mlp(params: dict, x: jax.Array, mlp_type: str = "swiglu") -> jax.Array:
    if mlp_type == "swiglu":
        gate = x @ params["w_gate"]
        up = x @ params["w_up"]
        h = jax.nn.silu(gate) * up
    else:  # gelu, 2-matrix
        h = jax.nn.gelu(x @ params["w_up"])
    h = constrain(h, "ffn_hidden")
    return h @ params["w_down"]


# ---------------------------------------------------------------------------
# Rotary embeddings


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq)."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]                        # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Masks


def causal_mask(q_len: int, kv_len: int, q_offset: int = 0,
                window: int = 0) -> jax.Array:
    """Boolean (q_len, kv_len) mask. True = attend."""
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    kv_pos = jnp.arange(kv_len)[None, :]
    mask = kv_pos <= q_pos
    if window:
        mask &= kv_pos > q_pos - window
    return mask


# ---------------------------------------------------------------------------
# GQA attention (full-sequence / prefill)


def _sdpa(q: jax.Array, k: jax.Array, v: jax.Array,
          mask: Optional[jax.Array]) -> jax.Array:
    """q: (B,S,Hq,hd) k/v: (B,T,Hkv,hd) with Hq % Hkv == 0."""
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    q = q.reshape(b, s, hkv, group, hd)
    scores = jnp.einsum("bshgd,bthd->bhgst", q, k).astype(jnp.float32)
    scores *= 1.0 / math.sqrt(hd)
    if mask is not None:
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgst,bthd->bshgd", probs, v)
    return out.reshape(b, s, hq * hd)


def gqa_attention(params: dict, x: jax.Array, cfg: ModelConfig,
                  positions: jax.Array, window: int = 0) -> jax.Array:
    """Full-sequence (training / prefill) GQA attention."""
    b, s, _ = x.shape
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, "attn_q")
    k = constrain(k, "attn_kv")
    mask = causal_mask(s, s, window=window)
    out = _sdpa(q, k, v, mask)
    return out @ params["wo"]


def gqa_prefill(params: dict, x: jax.Array, cfg: ModelConfig,
                positions: jax.Array, window: int = 0
                ) -> Tuple[jax.Array, dict]:
    """Prefill: same as full attention but also returns the KV cache."""
    b, s, _ = x.shape
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    mask = causal_mask(s, s, window=window)
    out = _sdpa(q, k, v, mask)
    cache = {"k": k, "v": v}
    return out @ params["wo"], cache


def gqa_prefill_attend(params: dict, x: jax.Array, cache: dict,
                       cfg: ModelConfig, positions: jax.Array
                       ) -> Tuple[jax.Array, dict]:
    """Fixed-shape GQA prefill against an explicit cache (serving
    admission path: bucketed and chunked prefill).

    x: (B, L, d) — a whole right-padded prompt bucket or one prompt
    chunk. cache: {"k","v"}: (B, C, Hkv, hd) holding the already-prefilled
    prefix (zeros on the first call). positions: (1, L) or (B, L) absolute
    positions of this call's tokens (``off + arange(L)``).

    This call's K/V rows are scattered into the cache at their absolute
    positions FIRST, then every query attends over the full C-column
    cache under a validity mask (col <= q_pos) — so the attention
    reduction has the exact same shape as ``gqa_decode``'s and as every
    other chunk's, which is what keeps chunked, bucketed-batch and serial
    prefill bit-identical (out-of-range scatter rows are dropped; padded
    rows beyond a prompt's true length are masked for real queries and
    later overwritten by decode before ever becoming visible).
    Non-ring caches only: sliding-window archs keep the exact-length
    prefill + ring re-roll recipe.
    """
    b, s, _ = x.shape
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    rows = jnp.arange(b)[:, None]
    ck = cache["k"].at[rows, positions].set(k.astype(cache["k"].dtype))
    cv = cache["v"].at[rows, positions].set(v.astype(cache["v"].dtype))
    ck = constrain(ck, "kv_cache")
    cv = constrain(cv, "kv_cache")
    cache_len = ck.shape[1]
    valid = jnp.arange(cache_len)[None, None, :] <= positions[..., None]
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    group = hq // hkv
    qh = q.reshape(b, s, hkv, group, cfg.head_dim)
    scores = jnp.einsum("bshgd,bthd->bhgst", qh, ck).astype(jnp.float32)
    scores *= 1.0 / math.sqrt(cfg.head_dim)
    scores = jnp.where(valid[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(cv.dtype)
    out = jnp.einsum("bhgst,bthd->bshgd", probs, cv)
    out = out.reshape(b, s, hq * cfg.head_dim)
    return out @ params["wo"], {"k": ck, "v": cv}


def mla_prefill_attend(params: dict, x: jax.Array, cache: dict,
                       cfg: ModelConfig, positions: jax.Array
                       ) -> Tuple[jax.Array, dict]:
    """Fixed-shape MLA prefill against an explicit compressed cache —
    the MLA counterpart of :func:`gqa_prefill_attend` (same scatter +
    validity-mask scheme over {"ckv","kpe"} rows, absorbed-form
    attention)."""
    b, s, _ = x.shape
    q_nope, q_rope, ckv_new, k_pe_new = _mla_qkv(params, x, cfg, positions)
    rows = jnp.arange(b)[:, None]
    ckv = cache["ckv"].at[rows, positions].set(
        ckv_new.astype(cache["ckv"].dtype))
    kpe = cache["kpe"].at[rows, positions].set(
        k_pe_new[:, :, 0, :].astype(cache["kpe"].dtype))
    ckv = constrain(ckv, "mla_cache")
    cache_len = ckv.shape[1]
    valid = jnp.arange(cache_len)[None, None, :] <= positions[..., None]
    mask = valid[:, None]                                 # (b,1,s,C)
    out = _mla_attend(params, q_nope, q_rope, ckv, kpe[:, :, None, :],
                      cfg, mask)
    return out, {"ckv": ckv, "kpe": kpe}


def decode_positions(pos: jax.Array, batch: int) -> jax.Array:
    """Normalize a decode position to a per-sequence ``(B,)`` vector.

    Accepts the historical scalar form (one position shared by the whole
    batch) or a ``(B,)`` vector (continuous batching: every slot sits at
    its own absolute position).
    """
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (batch,))
    return pos


def cache_slots(pos: jax.Array, cache_len: int, window: int) -> jax.Array:
    """Per-sequence cache row to write the new token into: ring slot for
    sliding-window caches, clamped absolute position otherwise."""
    return jnp.where(window > 0, pos % cache_len,
                     jnp.minimum(pos, cache_len - 1))


def _gqa_decode_qkv(params: dict, x: jax.Array, cfg: ModelConfig,
                    pos: jax.Array):
    """The new token's rotated q (B, 1, Hq, hd), its k, v (B, 1, Hkv, hd)
    and the positions as a (B,) vector."""
    b = x.shape[0]
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(b, 1, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
    pos = decode_positions(pos, b)
    posv = pos[:, None]
    q = apply_rope(q, posv, cfg.rope_theta)
    k = apply_rope(k, posv, cfg.rope_theta)
    return q, k, v, pos


def _gqa_decode_attend(params: dict, q: jax.Array, ck: jax.Array,
                       cv: jax.Array, cfg: ModelConfig, pos: jax.Array,
                       window: int) -> jax.Array:
    """One query per sequence against its cache rows (B, C, Hkv, hd),
    the new row included; rows past each sequence's position masked."""
    b = q.shape[0]
    cache_len = ck.shape[1]
    # validity: cache rows written so far, per sequence
    idx = jnp.arange(cache_len)
    if window > 0:
        # ring fully valid once warm
        valid = idx[None, :] <= jnp.minimum(pos, cache_len - 1)[:, None]
    else:
        valid = idx[None, :] <= pos[:, None]
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    group = hq // hkv
    qh = q.reshape(b, hkv, group, cfg.head_dim)
    scores = jnp.einsum("bhgd,bthd->bhgt", qh, ck).astype(jnp.float32)
    scores *= 1.0 / math.sqrt(cfg.head_dim)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(cv.dtype)
    out = jnp.einsum("bhgt,bthd->bhgd", probs, cv).reshape(b, 1, hq * cfg.head_dim)
    return out @ params["wo"]


def gqa_decode(params: dict, x: jax.Array, cache: dict, cfg: ModelConfig,
               pos: jax.Array, window: int = 0) -> Tuple[jax.Array, dict]:
    """One-token decode against a cache.

    x: (B, 1, d). cache: {"k","v"}: (B, C, Hkv, hd) where C is either the
    full context length or the sliding window size (ring buffer).
    pos: scalar int32 or (B,) int32 vector — absolute position of each
    sequence's new token (per-slot under continuous batching).
    """
    b, s, _ = x.shape
    assert s == 1
    cache_len = cache["k"].shape[1]
    q, k, v, pos = _gqa_decode_qkv(params, x, cfg, pos)
    slot = cache_slots(pos, cache_len, window)
    rows = jnp.arange(b)
    ck = cache["k"].at[rows, slot].set(k[:, 0])
    cv = cache["v"].at[rows, slot].set(v[:, 0])
    ck = constrain(ck, "kv_cache")
    cv = constrain(cv, "kv_cache")
    out = _gqa_decode_attend(params, q, ck, cv, cfg, pos, window)
    return out, {"k": ck, "v": cv}


def _with_new_rows(view: dict, new: dict, slot: jax.Array) -> dict:
    """A read-only cache view (leaves (B, C, ...)) as the attention sees
    it once each sequence's new row (``new``, leaves (B, ...)) sits at
    row ``slot`` (B,): a select that the attention's reads can fuse.  A
    row write would make the view a buffer of its own (on the TPU, a
    copy of it in the write's layout, then another back)."""
    def merge(v, n):
        at = jnp.arange(v.shape[1])[None, :] == slot[:, None]
        at = at.reshape(at.shape + (1,) * (v.ndim - 2))
        return jnp.where(at, n[:, None].astype(v.dtype), v)
    return jax.tree_util.tree_map(merge, view, new)


def gqa_decode_view(params: dict, x: jax.Array, view: dict,
                    cfg: ModelConfig, pos: jax.Array
                    ) -> Tuple[jax.Array, dict]:
    """:func:`gqa_decode` against a read-only dense view (B, C, Hkv, hd),
    positions below C (no ring): the same values at the same shapes,
    but the new rows are returned, (B, Hkv, hd) each, for the caller to
    store, and the view is never written."""
    q, k, v, pos = _gqa_decode_qkv(params, x, cfg, pos)
    new = {"k": k[:, 0].astype(view["k"].dtype),
           "v": v[:, 0].astype(view["v"].dtype)}
    kv = _with_new_rows(view, new, cache_slots(pos, view["k"].shape[1], 0))
    return _gqa_decode_attend(params, q, kv["k"], kv["v"], cfg, pos, 0), new


# ---------------------------------------------------------------------------
# MLA attention (DeepSeek-V2): low-rank KV compression; cache holds the
# compressed c_kv (kv_lora_rank) + shared rope key (qk_rope_dim) per token.


def _mla_qkv(params: dict, x: jax.Array, cfg: ModelConfig,
             positions: jax.Array):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    cq = x @ params["w_dq"]                                  # (b,s,q_lora)
    q = (cq @ params["w_uq"]).reshape(b, s, h, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    ckv = x @ params["w_dkv"]                                # (b,s,kv_lora)
    k_pe = (x @ params["w_kpe"]).reshape(b, s, 1, m.qk_rope_dim)
    k_pe = apply_rope(k_pe, positions, cfg.rope_theta)
    return q_nope, q_rope, ckv, k_pe


def _mla_attend(params: dict, q_nope, q_rope, ckv, k_pe, cfg: ModelConfig,
                mask: Optional[jax.Array]):
    """Attention over the *compressed* cache (weight-absorbed form).

    q_nope: (b,s,h,dn)  q_rope: (b,s,h,dr)
    ckv: (b,t,r)        k_pe: (b,t,1,dr)
    mask: broadcastable to the (b,h,s,t) score tensor.
    """
    m = cfg.mla
    h = cfg.n_heads
    w_uk = params["w_uk"].reshape(m.kv_lora_rank, h, m.qk_nope_dim)
    w_uv = params["w_uv"].reshape(m.kv_lora_rank, h, m.v_head_dim)
    # absorb W_uk into q: q_lat (b,s,h,r)
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk)
    scores = jnp.einsum("bshr,btr->bhst", q_lat, ckv)
    scores += jnp.einsum("bshd,btod->bhst", q_rope, k_pe)
    scores = scores.astype(jnp.float32) / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(ckv.dtype)
    o_lat = jnp.einsum("bhst,btr->bshr", probs, ckv)          # (b,s,h,r)
    out = jnp.einsum("bshr,rhd->bshd", o_lat, w_uv)           # (b,s,h,dv)
    b, s = out.shape[:2]
    return out.reshape(b, s, h * m.v_head_dim) @ params["wo"]


def mla_attention(params: dict, x: jax.Array, cfg: ModelConfig,
                  positions: jax.Array, window: int = 0) -> jax.Array:
    """Training/prefill MLA: NON-absorbed form — decompress c_kv into
    per-head k/v once per token (cost T·r·h·(dn+dv)), then attend at
    (dn+dr)-wide scores. The absorbed form (_mla_attend) pays r-wide
    (512) scores per pair: ~2.6x more attention FLOPs at S=4k (§Perf
    iteration 4); it only wins at decode, where re-decompressing the whole
    cache per token would dominate."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    q_nope, q_rope, ckv, k_pe = _mla_qkv(params, x, cfg, positions)
    w_uk = params["w_uk"].reshape(m.kv_lora_rank, h, m.qk_nope_dim)
    w_uv = params["w_uv"].reshape(m.kv_lora_rank, h, m.v_head_dim)
    k_nope = jnp.einsum("btr,rhd->bthd", ckv, w_uk)
    v = jnp.einsum("btr,rhd->bthd", ckv, w_uv)
    scores = jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
    scores += jnp.einsum("bshd,btod->bhst", q_rope, k_pe)
    scores = scores.astype(jnp.float32) / math.sqrt(
        m.qk_nope_dim + m.qk_rope_dim)
    mask = causal_mask(s, s, window=window)
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhst,bthd->bshd", probs, v)
    return out.reshape(b, s, h * m.v_head_dim) @ params["wo"]


def mla_prefill(params: dict, x: jax.Array, cfg: ModelConfig,
                positions: jax.Array, window: int = 0):
    q_nope, q_rope, ckv, k_pe = _mla_qkv(params, x, cfg, positions)
    s = x.shape[1]
    mask = causal_mask(s, s, window=window)
    out = _mla_attend(params, q_nope, q_rope, ckv, k_pe, cfg,
                      mask[None, None])
    return out, {"ckv": ckv, "kpe": k_pe[:, :, 0, :]}


def _mla_decode_attend(params: dict, q_nope, q_rope, ckv, kpe,
                       cfg: ModelConfig, pos: jax.Array) -> jax.Array:
    cache_len = ckv.shape[1]
    idx = jnp.arange(cache_len)
    valid = idx[None, :] <= jnp.minimum(pos, cache_len - 1)[:, None]
    mask = valid[:, None, None, :]                            # (b,1,s=1,C)
    return _mla_attend(params, q_nope, q_rope, ckv, kpe[:, :, None, :], cfg,
                       mask)


def mla_decode(params: dict, x: jax.Array, cache: dict, cfg: ModelConfig,
               pos: jax.Array, window: int = 0):
    """cache: {"ckv": (B,C,r), "kpe": (B,C,dr)}.
    pos: scalar int32 or (B,) int32 vector (per-slot positions)."""
    b = x.shape[0]
    cache_len = cache["ckv"].shape[1]
    pos = decode_positions(pos, b)
    posv = pos[:, None]
    q_nope, q_rope, ckv_new, k_pe_new = _mla_qkv(params, x, cfg, posv)
    slot = cache_slots(pos, cache_len, window)
    rows = jnp.arange(b)
    ckv = cache["ckv"].at[rows, slot].set(ckv_new[:, 0])
    kpe = cache["kpe"].at[rows, slot].set(k_pe_new[:, 0, 0, :])
    ckv = constrain(ckv, "mla_cache")
    out = _mla_decode_attend(params, q_nope, q_rope, ckv, kpe, cfg, pos)
    return out, {"ckv": ckv, "kpe": kpe}


def mla_decode_view(params: dict, x: jax.Array, view: dict,
                    cfg: ModelConfig, pos: jax.Array
                    ) -> Tuple[jax.Array, dict]:
    """:func:`mla_decode` against a read-only view {"ckv": (B,C,r),
    "kpe": (B,C,dr)}, as :func:`gqa_decode_view` is to
    :func:`gqa_decode`: returns the new rows, (B, r) and (B, dr)."""
    b = x.shape[0]
    pos = decode_positions(pos, b)
    q_nope, q_rope, ckv_new, k_pe_new = _mla_qkv(params, x, cfg, pos[:, None])
    new = {"ckv": ckv_new[:, 0].astype(view["ckv"].dtype),
           "kpe": k_pe_new[:, 0, 0, :].astype(view["kpe"].dtype)}
    kv = _with_new_rows(view, new, cache_slots(pos, view["ckv"].shape[1], 0))
    out = _mla_decode_attend(params, q_nope, q_rope, kv["ckv"], kv["kpe"],
                             cfg, pos)
    return out, new
