"""Pallas TPU flash attention (causal GQA, forward).

Canonical 3-D grid (batch*kv_head, q_block, kv_block) with VMEM scratch
accumulators — the kv axis is the innermost ("arbitrary") dimension so
the online-softmax state (acc, m, l) lives in scratch across kv steps.

TPU adaptation notes (DESIGN.md §2): VMEM working set per grid cell =
q block [g*bq, d] + k/v blocks [bk, d] + acc [g*bq, d] f32 + score tile
[g*bq, bk] f32. ``bq`` shrinks with the group size ``g`` so that
``g*bq <= block_q`` rows: at the defaults (block_q=bk=512, d=128) the
cell stays near 2 MB for any ``g``, inside the 16 MB of scoped VMEM a
v5e kernel gets by default. Causal skipping: kv blocks entirely above the diagonal do no
work (``pl.when``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  bq: int, bk: int, causal: bool, scale: float, nk: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * bq
    k_start = ik * bk

    def _step():
        q = q_ref[0].astype(jnp.float32)            # [g*bq, d]
        k = k_ref[0].astype(jnp.float32)            # [bk, d]
        v = v_ref[0].astype(jnp.float32)            # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [g*bq, bk]
        if causal:
            rows = q.shape[0]
            q_pos = q_start + (jax.lax.broadcasted_iota(
                jnp.int32, (rows, bk), 0) % bq)     # row layout [g, bq]
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (rows, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        pl.when(k_start <= q_start + bq - 1)(_step)
    else:
        _step()

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True, block_q: int = 512,
                        block_k: int = 512,
                        interpret: bool = False) -> jax.Array:
    """q [b,s,h,d]; k,v [b,skv,kvh,d] -> [b,s,h,d]."""
    b, s, h, d = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    # the q block holds g*bq rows (all heads of a KV group); shrink bq
    # with g so that block, the [g*bq, bk] score tile and the f32
    # accumulator stay inside the scoped VMEM budget
    bq = min(block_q, s)
    while bq > 8 and g * bq > block_q:
        bq //= 2
    while s % bq:
        bq //= 2
    bk = min(block_k, skv)
    while skv % bk:
        bk //= 2
    nq, nk = s // bq, skv // bk
    scale = 1.0 / np.sqrt(d)

    # [b*kvh, nq*g*bq, d]: q block j holds rows [g, bq] flattened
    qr = q.reshape(b, s, kvh, g, d).transpose(0, 2, 3, 1, 4) \
        .reshape(b * kvh, g, s, d)
    qr = qr.transpose(0, 2, 1, 3).reshape(b * kvh, nq, bq, g, d) \
        .transpose(0, 1, 3, 2, 4).reshape(b * kvh, nq * g * bq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * kvh, skv, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * kvh, skv, d)

    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, causal=causal,
                               scale=scale, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(b * kvh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, g * bq, d), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, bk, d), lambda i, j, kk: (i, kk, 0)),
            pl.BlockSpec((1, bk, d), lambda i, j, kk: (i, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, g * bq, d), lambda i, j, kk: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kvh, nq * g * bq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g * bq, d), jnp.float32),
            pltpu.VMEM((g * bq,), jnp.float32),
            pltpu.VMEM((g * bq,), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)

    out = out.reshape(b * kvh, nq, g, bq, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, kvh, g, s, d).transpose(0, 3, 1, 2, 4) \
        .reshape(b, s, h, d)
    return out
