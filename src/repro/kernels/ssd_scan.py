"""Pallas TPU chunked SSD scan (Mamba2 / mLSTM shared recurrence).

Computes the gated linear recurrence

    H_t = exp(a_t) H_{t-1} + k_t^T v_t;     y_t = q_t . H_t

in chunk-parallel form: grid (batch*head, n_chunks) with the chunk axis
innermost and the running state H [dk, dv] carried in f32 VMEM scratch.
Per chunk (all in VMEM, MXU matmuls):

    cum_i   = cumsum(a)    (a triangular matmul)  # [c]
    intra   = (q k^T * exp(cum_i - cum_j) * causal) v        (3 matmuls)
    inter   = (q . H) * exp(cum_i)
    H'      = exp(cum_c) H + (k * exp(cum_c - cum_j))^T v

which matches ``repro.models.ssm.chunked_linear_scan`` (the jnp
reference used for training) and ``ref.ssd_scan_ref`` (the sequential
oracle). This is the long_500k hot spot for zamba2/xlstm decode-train.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(q_ref, k_ref, v_ref, ar_ref, ac_ref, h0_ref, y_ref,
                hout_ref, h_ref, *, chunk: int, nchunks: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    q = q_ref[0].astype(jnp.float32)                 # [c, dk]
    k = k_ref[0].astype(jnp.float32)                 # [c, dk]
    v = v_ref[0].astype(jnp.float32)                 # [c, dv]
    a_row = ar_ref[0].astype(jnp.float32)            # [1, c]
    a_col = ac_ref[0].astype(jnp.float32)            # [c, 1]
    h = h_ref[...]                                   # [dk, dv]

    # cumsum as triangular matmuls (Mosaic has no scan primitive); the
    # row and column copies of log_a avoid an in-kernel transpose
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = rows >= cols
    tril = causal.astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    cum_col = jax.lax.dot_general(tril, a_col, (((1,), (0,)), ((), ())),
                                  precision=hi,
                                  preferred_element_type=jnp.float32)
    cum_row = jax.lax.dot_general(a_row, tril, (((1,), (1,)), ((), ())),
                                  precision=hi,
                                  preferred_element_type=jnp.float32)
    total = jnp.sum(a_row)
    qk = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [c, c]
    decay = cum_col - cum_row                        # [c, c]
    gate = jnp.where(causal, jnp.exp(jnp.minimum(decay, 0.0)), 0.0)
    y_intra = jax.lax.dot_general(qk * gate, v, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_inter = jax.lax.dot_general(q, h, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32) \
        * jnp.exp(cum_col)
    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    w = jnp.exp(total - cum_col)                     # [c, 1]
    h_new = h * jnp.exp(total) + jax.lax.dot_general(
        k * w, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    h_ref[...] = h_new

    @pl.when(ic == nchunks - 1)
    def _finish():
        hout_ref[0] = h_new


def ssd_scan_fwd(q: jax.Array, k: jax.Array, v: jax.Array,
                 log_a: jax.Array, h0: jax.Array, chunk: int = 256,
                 interpret: bool = False):
    """q,k [b,nh,S,dk]; v [b,nh,S,dv]; log_a [b,nh,S]; h0 [b,nh,dk,dv].

    Returns (y [b,nh,S,dv], h_final [b,nh,dk,dv] f32).
    """
    b, nh, s, dk = q.shape
    dv = v.shape[-1]
    c = min(chunk, s)
    while s % c:
        c //= 2
    nchunks = s // c

    qr = q.reshape(b * nh, s, dk)
    kr = k.reshape(b * nh, s, dk)
    vr = v.reshape(b * nh, s, dv)
    # log_a rides in twice, as a [1, c] row block and a [c, 1] column
    # block: both are tile-legal (a unit dim equal to the array's)
    ar_row = log_a.reshape(b * nh, 1, s)
    ar_col = log_a.reshape(b * nh, s, 1)
    hr = h0.reshape(b * nh, dk, dv)

    kernel = functools.partial(_ssd_kernel, chunk=c, nchunks=nchunks)
    y, h_final = pl.pallas_call(
        kernel,
        grid=(b * nh, nchunks),
        in_specs=[
            pl.BlockSpec((1, c, dk), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, c, dk), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, c, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, c), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, c, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, dk, dv), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, c, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, dk, dv), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * nh, s, dv), v.dtype),
            jax.ShapeDtypeStruct((b * nh, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret,
    )(qr, kr, vr, ar_row, ar_col, hr)
    return (y.reshape(b, nh, s, dv), h_final.reshape(b, nh, dk, dv))
