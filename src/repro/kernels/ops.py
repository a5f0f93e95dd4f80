"""Public jit'd wrappers for the Pallas kernels.

The platform alone picks how a kernel runs: on a TPU it is compiled by
Mosaic; on any other backend it runs through the Pallas interpreter
(``interpret=True`` — the kernel body runs in Python, semantics-exact),
which is how the CPU test suite checks it against ``ref.py``. The
kernels compile for v5e at the published widths of the registry's
models (``tests/test_tpu_compile.py``). Two are on main paths:
``DecodeServer`` calls the paged decode kernel on every decode step, and
every xLSTM sLSTM block runs ``slstm_scan``, in training and prefill.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import decode_attention_fwd
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.group_mean import group_mean_fwd
from repro.kernels.paged_attention import (gather_dense_decode,
                                           paged_decode_attention_fwd)
from repro.kernels.slstm_scan import slstm_scan as _slstm_scan
from repro.kernels.ssd_scan import ssd_scan_fwd

Array = jax.Array


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _check(cond, msg):
    if not cond:
        raise ValueError(msg)


@functools.partial(jax.jit, static_argnames=("causal",))
def flash_attention(q: Array, k: Array, v: Array,
                    causal: bool = True) -> Array:
    """q [b,s,h,d]; k,v [b,skv,kvh,d] -> [b,s,h,d]."""
    _check(q.ndim == 4 and k.ndim == 4 and v.ndim == 4, "rank-4 inputs")
    _check(k.shape == v.shape, "k/v shape mismatch")
    _check(q.shape[3] == k.shape[3], "head_dim mismatch")
    _check(q.shape[2] % k.shape[2] == 0, "GQA heads must divide")
    return flash_attention_fwd(q, k, v, causal, interpret=_interpret())


@jax.jit
def decode_attention(q: Array, k_cache: Array, v_cache: Array,
                     lengths: Array) -> Array:
    """q [b,h,d]; caches [b,S,kvh,d]; lengths [b] -> [b,h,d]."""
    _check(q.ndim == 3 and k_cache.ndim == 4, "bad ranks")
    _check(q.shape[2] == k_cache.shape[3], "head_dim mismatch")
    return decode_attention_fwd(q, k_cache, v_cache, lengths,
                                interpret=_interpret())


@jax.jit
def paged_decode_attention(q: Array, k_pages: Array, v_pages: Array,
                           block_tables: Array, lengths: Array) -> Array:
    """q [b,h,d]; pages [nb,kvh,bs,d]; block_tables [b,nblk]; lengths [b]
    -> [b,h,d].

    TPU: split-K kernel gathering pages via the scalar-prefetched block
    table. Other backends: the gathered dense einsum (running the
    kernel through the Python interpreter per page would be the slow
    path; the gathered einsum is semantics-exact).
    """
    _check(q.ndim == 3 and k_pages.ndim == 4, "bad ranks")
    _check(q.shape[2] == k_pages.shape[3], "head_dim mismatch")
    _check(q.shape[1] % k_pages.shape[1] == 0, "GQA heads must divide")
    _check(k_pages.shape == v_pages.shape, "k/v pages mismatch")
    _check(block_tables.ndim == 2 and block_tables.shape[0] == q.shape[0],
           "block_tables must be [b, nblk]")
    if _interpret():
        return gather_dense_decode(q, k_pages, v_pages, block_tables,
                                   lengths)
    return paged_decode_attention_fwd(q, k_pages, v_pages, block_tables,
                                      lengths, interpret=False)


@jax.jit
def ssd_scan(q: Array, k: Array, v: Array, log_a: Array, h0: Array):
    """Chunked gated linear recurrence; see ssd_scan.py."""
    _check(q.shape == k.shape, "q/k shape mismatch")
    _check(q.shape[:3] == v.shape[:3], "v batch/seq mismatch")
    return ssd_scan_fwd(q, k, v, log_a, h0, interpret=_interpret())


@jax.jit
def slstm_scan(xin: Array, r_rec: Array, bias: Array, s0: Array):
    """sLSTM over a sequence, differentiable; see slstm_scan.py.
    xin [b,S,4d]; r_rec [nh,hd,4hd]; bias [4d]; s0 [b,4d] the carry
    (h, c, n, m) -> (h [b,S,d] f32, final carry [b,4d])."""
    _check(xin.ndim == 3 and s0.shape == (xin.shape[0], xin.shape[2]),
           "xin [b,S,4d] and s0 [b,4d]")
    _check(r_rec.ndim == 3 and 4 * r_rec.shape[0] * r_rec.shape[1]
           == xin.shape[2] and r_rec.shape[2] == 4 * r_rec.shape[1],
           "r_rec must be [nh, hd, 4hd] with 4*nh*hd == 4d")
    return _slstm_scan(xin, r_rec, bias, s0, _interpret())


@jax.jit
def group_mean(x: Array, mask: Array) -> Array:
    """Masked MAR group mean; x [G, M, D], mask [G, M]."""
    _check(x.ndim == 3 and mask.shape == x.shape[:2], "bad shapes")
    return group_mean_fwd(x, mask, interpret=_interpret())
