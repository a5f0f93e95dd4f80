"""Core transformer layers: norms, RoPE, GQA attention, SwiGLU — pure JAX.

All layers are functional: ``init_*`` returns a params pytree (bf16 by
default), ``apply`` fns are jit/scan/shard-friendly. Layer params for a
depth-L stack are stacked along a leading axis by the caller
(``transformer.py``) so the decoder is a single ``lax.scan``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

Array = jax.Array


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def dense_init(key, in_dim: int, out_dim: int, dtype) -> Array:
    scale = 1.0 / np.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype) -> Array:
    return jnp.ones((d,), dtype)


def rmsnorm(x: Array, scale: Array, eps: float = 1e-5) -> Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * scale.astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> Array:
    """Inverse frequencies [head_dim//2], fp32."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: [..., seq, heads, head_dim]; positions: broadcastable to [..., seq]."""
    head_dim = x.shape[-1]
    inv_freq = rope_frequencies(head_dim, theta)
    # angles [..., seq, 1, head_dim//2]
    ang = positions[..., None, None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, causal, chunked-q blockwise softmax)
# ---------------------------------------------------------------------------

def attention_init(key, cfg: ModelConfig) -> dict:
    dt = _dtype(cfg)
    d, h, kvh, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, d, h * hd, dt),
        "wk": dense_init(kk, d, kvh * hd, dt),
        "wv": dense_init(kv, d, kvh * hd, dt),
        "wo": dense_init(ko, h * hd, d, dt),
    }


def _qkv(params, x: Array, cfg: ModelConfig, positions: Array):
    b, s, _ = x.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(b, s, h, hd)
    k = (x @ params["wk"]).reshape(b, s, kvh, hd)
    v = (x @ params["wv"]).reshape(b, s, kvh, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa_chunk(q: Array, k: Array, v: Array, mask: Optional[Array],
                scale: float) -> Array:
    """One q-chunk of GQA attention. q:[b,qc,h,hd] k/v:[b,skv,kvh,hd]."""
    b, qc, h, hd = q.shape
    kvh = k.shape[2]
    grp = h // kvh
    qg = q.reshape(b, qc, kvh, grp, hd)
    # scores [b, kvh, grp, qc, skv] in fp32
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v)
    return out.reshape(b, qc, h, hd)


def causal_attention(q: Array, k: Array, v: Array, cfg: ModelConfig,
                     q_offset: int = 0) -> Array:
    """Chunked causal attention: scan over q chunks keeps peak memory at
    one [b, qc, seq] score block (flash-style memory footprint; the Pallas
    kernel in ``repro.kernels.flash_attention`` is the TPU version)."""
    b, s, h, hd = q.shape
    scale = 1.0 / np.sqrt(hd)
    qc = min(cfg.attn_q_chunk, s)
    if s % qc != 0:  # fall back to single chunk for ragged smoke shapes
        qc = s
    n_chunks = s // qc
    kv_pos = jnp.arange(k.shape[1])

    def chunk_fn(carry, idx):
        q_chunk = jax.lax.dynamic_slice_in_dim(q, idx * qc, qc, axis=1)
        q_pos = q_offset + idx * qc + jnp.arange(qc)
        mask = kv_pos[None, None, :] <= q_pos[None, :, None]  # [1, qc, skv]
        mask = jnp.broadcast_to(mask, (b, qc, k.shape[1]))
        out = _sdpa_chunk(q_chunk, k, v, mask, scale)
        return carry, out

    _, outs = jax.lax.scan(chunk_fn, None, jnp.arange(n_chunks))
    # outs: [n_chunks, b, qc, h, hd] -> [b, s, h, hd]
    return jnp.moveaxis(outs, 0, 1).reshape(b, s, h, hd)


def attention_impl(q: Array, k: Array, v: Array, cfg: ModelConfig) -> Array:
    """Dispatch on cfg.attn_impl: flash (custom-vjp, default) | xla
    (naive chunked; baseline in EXPERIMENTS §Perf) | pallas (TPU)."""
    if cfg.attn_impl == "pallas":
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=True)
    if cfg.attn_impl == "flash":
        from repro.models.attention_flash import flash_attention
        return flash_attention(q, k, v, True, cfg.attn_q_chunk,
                               cfg.attn_kv_chunk)
    return causal_attention(q, k, v, cfg)


def attention_block(params, x: Array, cfg: ModelConfig, positions: Array) -> Array:
    q, k, v = _qkv(params, x, cfg, positions)
    out = attention_impl(q, k, v, cfg)
    b, s = x.shape[:2]
    return out.reshape(b, s, -1) @ params["wo"]


def attention_decode(params, x: Array, cfg: ModelConfig, k_cache: Array,
                     v_cache: Array, pos: Array,
                     window: int = 0) -> Tuple[Array, Array, Array]:
    """Single-token decode. x:[b,1,d]; caches [b, S_max, kvh, hd]; pos [b].

    Returns (out [b,1,d], new_k_cache, new_v_cache). With ``window`` > 0 the
    cache is a ring buffer of that length (used by zamba2's shared block).
    """
    b = x.shape[0]
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(b, 1, h, hd)
    k = (x @ params["wk"]).reshape(b, 1, kvh, hd)
    v = (x @ params["wv"]).reshape(b, 1, kvh, hd)
    q = apply_rope(q, pos[:, None], cfg.rope_theta)
    k = apply_rope(k, pos[:, None], cfg.rope_theta)

    s_max = k_cache.shape[1]
    slot = pos % window if window else pos
    k_cache = jax.vmap(lambda c, kk, i: jax.lax.dynamic_update_slice_in_dim(
        c, kk, i, axis=0))(k_cache, k, slot)
    v_cache = jax.vmap(lambda c, vv, i: jax.lax.dynamic_update_slice_in_dim(
        c, vv, i, axis=0))(v_cache, v, slot)

    kv_pos = jnp.arange(s_max)
    if window:
        valid = kv_pos[None, :] < jnp.minimum(pos + 1, window)[:, None]
    else:
        valid = kv_pos[None, :] <= pos[:, None]
    mask = valid[:, None, :]  # [b, 1, s_max]
    out = _sdpa_chunk(q, k_cache, v_cache, mask, 1.0 / np.sqrt(hd))
    return out.reshape(b, 1, -1) @ params["wo"], k_cache, v_cache


def attention_decode_paged(params, x: Array, cfg: ModelConfig,
                           k_pages: Array, v_pages: Array,
                           block_tables: Array, pos: Array
                           ) -> Tuple[Array, Array, Array]:
    """Single-token decode against a paged KV pool (serving tier).

    x:[b,1,d]; pages [num_blocks, kvh, bs, hd] (this layer's slice of the
    pool); block_tables [b, nblk] maps each session's logical block k to
    a physical page; pos [b] = tokens already cached. The new K/V row is
    scattered into page ``block_tables[i, pos // bs]`` slot ``pos % bs``;
    attention runs through ``kernels.ops.paged_decode_attention`` (TPU
    split-K kernel / CPU gather+dense). Inactive batch rows should point
    their whole table at the scratch page 0 with pos 0.

    Returns (out [b,1,d], k_pages, v_pages).
    """
    b = x.shape[0]
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    bs = k_pages.shape[2]
    q = (x @ params["wq"]).reshape(b, 1, h, hd)
    k = (x @ params["wk"]).reshape(b, 1, kvh, hd)
    v = (x @ params["wv"]).reshape(b, 1, kvh, hd)
    q = apply_rope(q, pos[:, None], cfg.rope_theta)
    k = apply_rope(k, pos[:, None], cfg.rope_theta)

    blk = jnp.take_along_axis(block_tables, (pos // bs)[:, None], axis=1)[:, 0]
    slot = pos % bs
    # duplicate (blk, slot) targets only occur on the scratch page 0
    # (inactive rows) — the undefined winner there is never read.
    k_pages = k_pages.at[blk, :, slot].set(k[:, 0])   # [b, kvh, hd]
    v_pages = v_pages.at[blk, :, slot].set(v[:, 0])

    from repro.kernels import ops as kops
    out = kops.paged_decode_attention(q[:, 0], k_pages, v_pages,
                                      block_tables, pos + 1)
    return out.reshape(b, 1, -1) @ params["wo"], k_pages, v_pages


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    dt = _dtype(cfg)
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    kg, ku, kd = jax.random.split(key, 3)
    return {
        "wg": dense_init(kg, d, ff, dt),
        "wu": dense_init(ku, d, ff, dt),
        "wd": dense_init(kd, ff, d, dt),
    }


def mlp_block(params, x: Array) -> Array:
    return (jax.nn.silu(x @ params["wg"]) * (x @ params["wu"])) @ params["wd"]


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embedding_init(key, cfg: ModelConfig) -> dict:
    dt = _dtype(cfg)
    k1, k2 = jax.random.split(key)
    p = {"tok": (jax.random.normal(k1, (cfg.vocab_size, cfg.d_model), jnp.float32)
                 * 0.02).astype(dt)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(k2, cfg.d_model, cfg.vocab_size, dt)
    return p


def embed(params, tokens: Array) -> Array:
    return params["tok"][tokens]


def unembed(params, x: Array, cfg: ModelConfig) -> Array:
    if cfg.tie_embeddings:
        w = params["tok"].T
    else:
        w = params["unembed"]
    return (x @ w).astype(jnp.float32)
