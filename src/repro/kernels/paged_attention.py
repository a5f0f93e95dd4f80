"""Pallas TPU paged decode attention: one query token vs a block-paged cache.

The serving tier stores KV in fixed-size *blocks* (``[num_blocks, kvh,
block_size, d]``) owned by a host-side allocator; each session
holds an ordered *block table* row mapping its logical positions to
physical blocks (``serve/paged_cache.py``). This kernel is the paged
variant of ``decode_attention.py``: the same split-K flash recurrence
over grid ``(batch*kv_head, blocks_per_session)``, but the K/V tile for
grid cell ``(i, kk)`` is *gathered through the block table* — the table
(and the per-session filled lengths) ride in as scalar-prefetch
operands so the BlockSpec index map can pick the physical page before
the tile DMA is issued. Out-of-range positions (beyond ``lengths[b]``,
including the garbage tail of a partially-filled last block and any
scratch-page padding rows of the table) are masked by the same
lane-position iota as the dense kernel.

One KV head's page is a ``(block_size, d)`` tile, so the page block
``(1, 1, block_size, d)`` meets the TPU tiling rule (last two block dims
equal to the array's or multiples of (8, 128)) for any ``kvh``; keep
``block_size`` a multiple of the bf16 sublane tile (16).

On the CPU the serving path does not run the kernel at all:
``gather_dense_decode`` materializes the session's pages into a dense
cache view and applies the exact einsum/softmax used by the dense
decode path (``interpret=True`` on the kernel itself is kept for
parity tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_decode_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, bs: int, scale: float,
                         nblk: int, kvh: int):
    i, kk = pl.program_id(0), pl.program_id(1)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[i // kvh]
    k_start = kk * bs

    @pl.when(k_start < length)
    def _step():
        q = q_ref[0].astype(jnp.float32)             # [g, d]
        k = k_ref[0, 0].astype(jnp.float32)          # [bs, d]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [g, bs]
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

    @pl.when(kk == nblk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def paged_decode_attention_fwd(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array, block_tables: jax.Array,
                               lengths: jax.Array,
                               interpret: bool = False) -> jax.Array:
    """q [b,h,d]; pages [nb,kvh,bs,d]; block_tables [b,nblk]; lengths [b]
    -> [b,h,d]."""
    b, h, d = q.shape
    kvh, bs = k_pages.shape[1], k_pages.shape[2]
    nblk = block_tables.shape[1]
    g = h // kvh
    scale = 1.0 / np.sqrt(d)

    qr = q.reshape(b, kvh, g, d).reshape(b * kvh, g, d)
    kernel = functools.partial(_paged_decode_kernel, bs=bs, scale=scale,
                               nblk=nblk, kvh=kvh)
    page_spec = pl.BlockSpec(
        (1, 1, bs, d),
        lambda i, kk, bt, ln: (bt[i // kvh, kk], i % kvh, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,       # block_tables, lengths
        grid=(b * kvh, nblk),
        in_specs=[
            pl.BlockSpec((1, g, d), lambda i, kk, bt, ln: (i, 0, 0)),
            page_spec,
            page_spec,
        ],
        out_specs=pl.BlockSpec((1, g, d), lambda i, kk, bt, ln: (i, 0, 0)),
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
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      qr, k_pages, v_pages)
    return out.reshape(b, kvh, g, d).reshape(b, h, d)


def gather_dense_decode(q: jax.Array, k_pages: jax.Array,
                        v_pages: jax.Array, block_tables: jax.Array,
                        lengths: jax.Array) -> jax.Array:
    """CPU path: gather the session's pages into a dense
    [b, nblk*bs, kvh, d] view and run the dense decode einsum.

    Mirrors ``layers._sdpa_chunk`` op-for-op (fp32 scores/softmax, probs
    cast back to the value dtype) so the paged serve path stays
    numerically aligned with the dense-cache path on identical shapes.
    """
    b, h, d = q.shape
    nblk = block_tables.shape[1]
    kvh, s = k_pages.shape[1], nblk * k_pages.shape[2]
    g = h // kvh
    scale = 1.0 / np.sqrt(d)

    # [b, nblk, kvh, bs, d] -> dense [b, nblk*bs, kvh, d], position order
    k = k_pages[block_tables].swapaxes(2, 3).reshape(b, s, kvh, d)
    v = v_pages[block_tables].swapaxes(2, 3).reshape(b, s, kvh, d)
    qg = q.reshape(b, 1, kvh, g, d)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    valid = jnp.arange(s)[None, :] < lengths[:, None]          # [b, s]
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v)
    return out.reshape(b, 1, h, d)[:, 0]
