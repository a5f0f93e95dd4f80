"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests).

Each function is the semantic ground truth at f32 precision with no
blocking — the kernels must match these for every swept (shape, dtype).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def flash_attention_ref(q: Array, k: Array, v: Array,
                        causal: bool = True) -> Array:
    """q [b,s,h,d]; k,v [b,skv,kvh,d] -> [b,s,h,d] (GQA, causal)."""
    b, s, h, d = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    qg = q.reshape(b, s, kvh, g, d)
    sc = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                    k.astype(jnp.float32)) / np.sqrt(d)
    if causal:
        mask = jnp.arange(s)[:, None] >= jnp.arange(skv)[None, :]
        sc = jnp.where(mask[None, None, None], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(b, s, h, d).astype(q.dtype)


def decode_attention_ref(q: Array, k_cache: Array, v_cache: Array,
                         lengths: Array) -> Array:
    """q [b,h,d]; caches [b,S,kvh,d]; lengths [b] -> [b,h,d].

    Attends to positions < lengths[b] (the filled prefix of the cache).
    """
    b, h, d = q.shape
    s, kvh = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    qg = q.reshape(b, kvh, g, d)
    sc = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                    k_cache.astype(jnp.float32)) / np.sqrt(d)
    valid = jnp.arange(s)[None, :] < lengths[:, None]
    sc = jnp.where(valid[:, None, None, :], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return o.reshape(b, h, d).astype(q.dtype)


def paged_decode_attention_ref(q: Array, k_pages: Array, v_pages: Array,
                               block_tables: Array, lengths: Array) -> Array:
    """q [b,h,d]; pages [nb,kvh,bs,d]; block_tables [b,nblk]; lengths [b].

    Gathers each session's pages into a dense [b, nblk*bs, kvh, d] cache
    (block-table order == position order) and defers to the dense decode
    oracle — the semantic ground truth for the paged kernel.
    """
    b = q.shape[0]
    kvh, bs, d = k_pages.shape[1], k_pages.shape[2], k_pages.shape[3]
    s = block_tables.shape[1] * bs
    k = jnp.swapaxes(k_pages[block_tables], 2, 3).reshape(b, s, kvh, d)
    v = jnp.swapaxes(v_pages[block_tables], 2, 3).reshape(b, s, kvh, d)
    return decode_attention_ref(q, k, v, lengths)


def ssd_scan_ref(q: Array, k: Array, v: Array, log_a: Array,
                 h0: Array) -> Tuple[Array, Array]:
    """Gated linear recurrence (Mamba2 SSD / mLSTM shared primitive).

    q,k [b,nh,S,dk]; v [b,nh,S,dv]; log_a [b,nh,S] (<=0);
    h0 [b,nh,dk,dv].  Sequential-scan ground truth:
        H_t = exp(a_t) H_{t-1} + k_t^T v_t;   y_t = q_t . H_t
    """
    def step(h, xs):
        qt, kt, vt, at = xs
        h = h * jnp.exp(at.astype(jnp.float32))[..., None, None] + \
            jnp.einsum("bhd,bhv->bhdv", kt.astype(jnp.float32),
                       vt.astype(jnp.float32))
        y = jnp.einsum("bhd,bhdv->bhv", qt.astype(jnp.float32), h)
        return h, y

    xs = (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0),
          jnp.moveaxis(v, 2, 0), jnp.moveaxis(log_a, 2, 0))
    h_final, ys = jax.lax.scan(step, h0.astype(jnp.float32), xs)
    return jnp.moveaxis(ys, 0, 2).astype(v.dtype), h_final


def group_mean_ref(x: Array, mask: Array) -> Array:
    """Masked group mean (MAR aggregation hot spot).

    x [G, M, D]; mask [G, M] -> [G, M, D]: every slot receives its
    group's masked mean; empty groups keep their own values.
    """
    m = mask[..., None].astype(jnp.float32)
    num = jnp.sum(x.astype(jnp.float32) * m, axis=1, keepdims=True)
    den = jnp.sum(m, axis=1, keepdims=True)
    mean = num / jnp.maximum(den, 1.0)
    out = jnp.where(den > 0, mean, x.astype(jnp.float32))
    return jnp.broadcast_to(out, x.shape).astype(x.dtype)


def slstm_scan_ref(xin: Array, r_rec: Array, bias: Array,
                   s0: Array) -> Tuple[Array, Array]:
    """sLSTM over a sequence, one ``lax.scan`` step per time step.

    xin [b, S, 4d] pre-activations of the input projection; r_rec
    [nh, hd, 4hd] block-diagonal recurrent weights; bias [4d]; s0 [b, 4d]
    the carry (h, c, n, m) side by side. Returns (h [b, S, d] f32, final
    carry [b, 4d])."""
    nh, hd = r_rec.shape[0], r_rec.shape[1]
    d = nh * hd

    def step(carry, xt):
        h, c, n, m = carry
        rec = jnp.einsum("bnd,ndk->bnk", h.reshape(-1, nh, hd),
                         r_rec).reshape(-1, 4 * d)
        pre = xt.astype(jnp.float32) + rec + bias
        zt, it, ft, ot = jnp.split(pre, 4, axis=-1)
        zt = jnp.tanh(zt)
        ot = jax.nn.sigmoid(ot)
        log_f = jax.nn.log_sigmoid(ft)
        m_new = jnp.maximum(log_f + m, it)
        i_p = jnp.exp(it - m_new)
        f_p = jnp.exp(log_f + m - m_new)
        c_new = f_p * c + i_p * zt
        n_new = f_p * n + i_p
        h_new = ot * c_new / jnp.maximum(n_new, 1e-6)
        return (h_new, c_new, n_new, m_new), h_new

    carry, hs = jax.lax.scan(step, tuple(jnp.split(s0, 4, axis=-1)),
                             jnp.moveaxis(xin, 1, 0))
    return jnp.moveaxis(hs, 0, 1), jnp.concatenate(carry, axis=-1)
