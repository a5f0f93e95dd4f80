"""Pallas TPU decode attention: one query token vs a long KV cache.

Split-K layout: grid (batch*kv_head, kv_split) — each grid cell reduces
one contiguous cache segment into partial (acc, m, l) carried in VMEM
scratch across the split axis (innermost, "arbitrary"), exactly the
flash recurrence with a single q row per (b, kv-head, group).

The hot spot of decode_32k is pure HBM bandwidth (read the whole cache
per token); the kernel streams [bk, d] cache tiles through VMEM and
keeps everything else resident. Out-of-range positions (beyond the
filled length) are masked with the same lane-position iota used for
causality in the prefill kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, bk: int, scale: float,
                   nk: int):
    i, ik = pl.program_id(0), pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[i]
    k_start = ik * bk

    @pl.when(k_start < length)
    def _step():
        q = q_ref[0].astype(jnp.float32)             # [g, d]
        k = k_ref[0].astype(jnp.float32)             # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [g, bk]
        pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention_fwd(q: jax.Array, k_cache: jax.Array,
                         v_cache: jax.Array, lengths: jax.Array,
                         block_k: int = 512,
                         interpret: bool = False) -> jax.Array:
    """q [b,h,d]; caches [b,S,kvh,d]; lengths [b] -> [b,h,d]."""
    b, h, d = q.shape
    s, kvh = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    # Keep the full block size and pad the cache view up to a block
    # multiple instead of shrinking bk to a divisor of s (the old
    # ``while s % bk: bk //= 2`` silently degraded to bk=1-ish tiles for
    # non-power-of-two caches). Padded positions sit at pos >= s >=
    # length, so the existing length mask (and the k_start < length
    # block skip) already excludes them.
    bk = min(block_k, s)
    nk = -(-s // bk)
    s_pad = nk * bk
    scale = 1.0 / np.sqrt(d)

    qr = q.reshape(b, kvh, g, d).reshape(b * kvh, g, d)
    kr = k_cache.transpose(0, 2, 1, 3).reshape(b * kvh, s, d)
    vr = v_cache.transpose(0, 2, 1, 3).reshape(b * kvh, s, d)
    if s_pad != s:
        pad = ((0, 0), (0, s_pad - s), (0, 0))
        kr, vr = jnp.pad(kr, pad), jnp.pad(vr, pad)
    lens = jnp.repeat(lengths.astype(jnp.int32), kvh)      # [b*kvh]

    kernel = functools.partial(_decode_kernel, bk=bk, scale=scale, nk=nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,       # per-row filled lengths
        grid=(b * kvh, nk),
        in_specs=[
            pl.BlockSpec((1, g, d), lambda i, kk, ln: (i, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda i, kk, ln: (i, kk, 0)),
            pl.BlockSpec((1, bk, d), lambda i, kk, ln: (i, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, d), lambda i, kk, ln: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * kvh, g, d), q.dtype),
        interpret=interpret,
    )(lens, qr, kr, vr)
    return out.reshape(b, kvh, g, d).reshape(b, h, d)
