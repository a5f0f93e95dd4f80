"""Recurrent blocks: Mamba2 (SSD), mLSTM and sLSTM (xLSTM) — pure JAX.

Mamba2 and mLSTM share one *chunked gated linear recurrence* primitive
(`chunked_linear_scan`): per-step state update

    H_t = exp(a_t) * H_{t-1} + k_t^T (outer) v_t,     y_t = q_t . H_t

with per-(head, step) scalar log-decay ``a_t <= 0``. Mamba2 maps
(q,k,v,a) = (C, B, dt*x, A*dt); mLSTM maps (q,k,v,a) = (q, k, i*v,
logsigmoid(f)) with the normalizer tracked via an appended ones-column.
The chunked form (intra-chunk parallel, inter-chunk scan) is the reference
for the ``repro.kernels.ssd_scan`` Pallas kernel.

Faithfulness notes (DESIGN.md §8): mLSTM's exponential input gate is
implemented with the max-stabilizer folded into sigmoid gating for scan
stability (standard practice in xLSTM reimplementations); sLSTM keeps the
exact exponential-gating stabilizer (m_t) since it runs a sequential scan.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import ops as kops
from repro.models.layers import dense_init

Array = jax.Array


# ---------------------------------------------------------------------------
# Chunked gated linear recurrence (shared by Mamba2 / mLSTM)
# ---------------------------------------------------------------------------

def chunked_linear_scan(q: Array, k: Array, v: Array, log_a: Array,
                        h0: Array, chunk: int = 256) -> Tuple[Array, Array]:
    """q,k: [b, nh, S, dk]; v: [b, nh, S, dv]; log_a: [b, nh, S] (<= 0).

    Returns (y [b, nh, S, dv], h_final [b, nh, dk, dv]).
    """
    b, nh, s, dk = q.shape
    dv = v.shape[-1]
    if s % chunk != 0:
        chunk = s  # smoke shapes
    nchunks = s // chunk

    qc = q.reshape(b, nh, nchunks, chunk, dk)
    kc = k.reshape(b, nh, nchunks, chunk, dk)
    vc = v.reshape(b, nh, nchunks, chunk, dv)
    ac = log_a.reshape(b, nh, nchunks, chunk).astype(jnp.float32)

    def chunk_fn(h, inputs):
        qi, ki, vi, ai = inputs  # [b, nh, chunk, *]
        cum = jnp.cumsum(ai, axis=-1)                     # A_i = sum_{j<=i} a_j
        total = cum[..., -1]                              # [b, nh]
        # intra-chunk: S_ij = (q_i.k_j) exp(A_i - A_j), j <= i
        qk = jnp.einsum("bhid,bhjd->bhij", qi.astype(jnp.float32),
                        ki.astype(jnp.float32))
        decay = cum[..., :, None] - cum[..., None, :]     # A_i - A_j
        causal = jnp.tril(jnp.ones((chunk, chunk), bool))
        gate = jnp.where(causal, jnp.exp(jnp.minimum(decay, 0.0)), 0.0)
        y_intra = jnp.einsum("bhij,bhjv->bhiv", qk * gate,
                             vi.astype(jnp.float32))
        # inter-chunk: y_i += exp(A_i) q_i . H0
        y_inter = jnp.einsum("bhid,bhdv->bhiv", qi.astype(jnp.float32),
                             h) * jnp.exp(cum)[..., None]
        # state update: H' = exp(A_total) H0 + sum_j exp(A_total - A_j) k_j v_j
        w = jnp.exp(total[..., None] - cum)               # [b, nh, chunk]
        h_new = h * jnp.exp(total)[..., None, None] + jnp.einsum(
            "bhjd,bhjv->bhdv", ki.astype(jnp.float32) * w[..., None],
            vi.astype(jnp.float32))
        return h_new, (y_intra + y_inter).astype(v.dtype)

    xs = (jnp.moveaxis(qc, 2, 0), jnp.moveaxis(kc, 2, 0),
          jnp.moveaxis(vc, 2, 0), jnp.moveaxis(ac, 2, 0))
    h_final, ys = jax.lax.scan(chunk_fn, h0.astype(jnp.float32), xs)
    y = jnp.moveaxis(ys, 0, 2).reshape(b, nh, s, dv)
    return y, h_final


def linear_scan_step(q: Array, k: Array, v: Array, log_a: Array,
                     h: Array) -> Tuple[Array, Array]:
    """Single decode step. q,k: [b, nh, dk]; v: [b, nh, dv]; log_a: [b, nh]."""
    h_new = h * jnp.exp(log_a.astype(jnp.float32))[..., None, None] + \
        jnp.einsum("bhd,bhv->bhdv", k.astype(jnp.float32), v.astype(jnp.float32))
    y = jnp.einsum("bhd,bhdv->bhv", q.astype(jnp.float32), h_new)
    return y.astype(v.dtype), h_new


# ---------------------------------------------------------------------------
# Depthwise causal conv (width-w, shift-add form)
# ---------------------------------------------------------------------------

def causal_conv(x: Array, w: Array, state: Array = None):
    """x: [b, S, c]; w: [width, c] depthwise taps. Returns y same shape.

    If ``state`` [b, width-1, c] is given, runs in streaming mode (decode):
    x is [b, 1, c] and the updated state is returned as well.
    """
    width = w.shape[0]
    if state is not None:
        buf = jnp.concatenate([state, x], axis=1)      # [b, width, c]
        y = jnp.einsum("bwc,wc->bc", buf, w)[:, None, :]
        return jax.nn.silu(y), buf[:, 1:, :]
    acc = x * w[-1]
    for i in range(1, width):
        shifted = jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, :-i, :]
        acc = acc + shifted * w[width - 1 - i]
    return jax.nn.silu(acc)


# ---------------------------------------------------------------------------
# Mamba2 block
# ---------------------------------------------------------------------------

def mamba_dims(cfg: ModelConfig):
    inner = cfg.ssm_expand * cfg.d_model
    headdim = 64
    nheads = inner // headdim
    return inner, headdim, nheads


def mamba_init(key, cfg: ModelConfig) -> dict:
    dt = jnp.dtype(cfg.dtype)
    d, n = cfg.d_model, cfg.ssm_state
    inner, headdim, nheads = mamba_dims(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    zxbcdt = 2 * inner + 2 * n + nheads
    return {
        "in_proj": dense_init(k1, d, zxbcdt, dt),
        "conv_w": (jax.random.normal(k2, (cfg.ssm_conv_width, inner + 2 * n),
                                     jnp.float32) * 0.1).astype(dt),
        "a_log": jnp.log(jnp.linspace(1.0, float(nheads), nheads,
                                      dtype=jnp.float32)),
        "dt_bias": jnp.zeros((nheads,), jnp.float32),
        "d_skip": jnp.ones((nheads,), jnp.float32),
        "out_proj": dense_init(k4, inner, d, dt),
    }


def mamba_split(params, x: Array, cfg: ModelConfig):
    d, n = cfg.d_model, cfg.ssm_state
    inner, headdim, nheads = mamba_dims(cfg)
    zxbcdt = x @ params["in_proj"]
    z, xs, bc, dt_raw = jnp.split(
        zxbcdt, [inner, 2 * inner, 2 * inner + 2 * n], axis=-1)
    return z, xs, bc, dt_raw


def mamba_block(params, x: Array, cfg: ModelConfig,
                h0: Array = None) -> Array:
    """x: [b, S, d] -> (y [b, S, d], h_final, conv_state).

    ``conv_state`` [b, width-1, inner+2n] is the raw conv-input tail
    (zero-padded when S < width-1) — exactly the streaming buffer
    ``causal_conv`` expects, so prefill hands off to
    ``mamba_decode_step`` without replaying the prompt.
    """
    b, s, d = x.shape
    n = cfg.ssm_state
    inner, headdim, nheads = mamba_dims(cfg)
    z, xs, bc, dt_raw = mamba_split(params, x, cfg)
    conv_in = jnp.concatenate([xs, bc], axis=-1)
    cw = cfg.ssm_conv_width
    conv_state = jnp.pad(conv_in, ((0, 0), (cw - 1, 0), (0, 0)))[:, -(cw - 1):]
    conv_out = causal_conv(conv_in, params["conv_w"])
    xs, bmat, cmat = jnp.split(conv_out, [inner, inner + n], axis=-1)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["a_log"])                       # [nheads], < 0
    log_decay = (dt * a).transpose(0, 2, 1)             # [b, nheads, S]

    xh = xs.reshape(b, s, nheads, headdim).transpose(0, 2, 1, 3)
    # B/C shared across heads (ngroups=1)
    kk = jnp.broadcast_to(bmat[:, None], (b, nheads, s, n))
    qq = jnp.broadcast_to(cmat[:, None], (b, nheads, s, n))
    vv = xh * dt.transpose(0, 2, 1)[..., None].astype(xh.dtype)

    if h0 is None:
        h0 = jnp.zeros((b, nheads, n, headdim), jnp.float32)
    if cfg.attn_impl == "pallas":
        y, h_final = kops.ssd_scan(qq, kk, vv, log_decay, h0)
    else:
        y, h_final = chunked_linear_scan(qq, kk, vv, log_decay, h0)
    y = y + xh * params["d_skip"][None, :, None, None].astype(xh.dtype)
    y = y.transpose(0, 2, 1, 3).reshape(b, s, inner)
    y = y * jax.nn.silu(z)
    return y @ params["out_proj"], h_final, conv_state


def mamba_decode_step(params, x: Array, cfg: ModelConfig, conv_state: Array,
                      ssm_state: Array):
    """x: [b, 1, d]. conv_state: [b, w-1, inner+2n]; ssm_state [b,nh,n,hd]."""
    b = x.shape[0]
    n = cfg.ssm_state
    inner, headdim, nheads = mamba_dims(cfg)
    z, xs, bc, dt_raw = mamba_split(params, x, cfg)
    conv_in = jnp.concatenate([xs, bc], axis=-1)
    conv_out, conv_state = causal_conv(conv_in, params["conv_w"], conv_state)
    xs, bmat, cmat = jnp.split(conv_out, [inner, inner + n], axis=-1)

    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["a_log"])
    log_decay = dt * a                                   # [b, nheads]
    xh = xs.reshape(b, nheads, headdim)
    kk = jnp.broadcast_to(bmat[:, None, 0] if bmat.ndim == 3 else bmat[:, None],
                          (b, nheads, n))
    qq = jnp.broadcast_to(cmat[:, None, 0] if cmat.ndim == 3 else cmat[:, None],
                          (b, nheads, n))
    vv = xh * dt[..., None].astype(xh.dtype)
    y, ssm_state = linear_scan_step(qq, kk, vv, log_decay, ssm_state)
    y = y + xh * params["d_skip"][None, :, None].astype(xh.dtype)
    y = y.reshape(b, 1, inner) * jax.nn.silu(z)
    return y @ params["out_proj"], conv_state, ssm_state


# ---------------------------------------------------------------------------
# mLSTM block (xLSTM matrix memory)
# ---------------------------------------------------------------------------

def mlstm_dims(cfg: ModelConfig):
    inner = cfg.ssm_expand * cfg.d_model
    nh = cfg.num_heads
    return inner, inner // nh, nh


def mlstm_init(key, cfg: ModelConfig) -> dict:
    dt = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    inner, hd, nh = mlstm_dims(cfg)
    k1, k2, k3, k4, k5, k6, k7 = jax.random.split(key, 7)
    return {
        "up_proj": dense_init(k1, d, 2 * inner, dt),
        "wq": dense_init(k2, inner, inner, dt),
        "wk": dense_init(k3, inner, inner, dt),
        "wv": dense_init(k4, inner, inner, dt),
        "wi": dense_init(k5, inner, nh, jnp.float32),
        "wf": dense_init(k6, inner, nh, jnp.float32),
        "out_proj": dense_init(k7, inner, d, dt),
    }


def _mlstm_qkvif(params, x: Array, cfg: ModelConfig):
    b, s, _ = x.shape
    inner, hd, nh = mlstm_dims(cfg)
    up = x @ params["up_proj"]
    xi, z = jnp.split(up, 2, axis=-1)
    q = (xi @ params["wq"]).reshape(b, s, nh, hd).transpose(0, 2, 1, 3)
    k = (xi @ params["wk"]).reshape(b, s, nh, hd).transpose(0, 2, 1, 3)
    v = (xi @ params["wv"]).reshape(b, s, nh, hd).transpose(0, 2, 1, 3)
    igate = jax.nn.sigmoid(xi.astype(jnp.float32) @ params["wi"])  # [b,s,nh]
    fgate = jax.nn.log_sigmoid(xi.astype(jnp.float32) @ params["wf"])
    q = q / np.sqrt(hd)
    return q, k, v, igate.transpose(0, 2, 1), fgate.transpose(0, 2, 1), z


def _mlstm_normalize(y_aug: Array) -> Array:
    num, den = y_aug[..., :-1], y_aug[..., -1:]
    return num / jnp.maximum(jnp.abs(den), 1.0)


def mlstm_block(params, x: Array, cfg: ModelConfig, h0: Array = None):
    with jax.named_scope("mlstm"):
        b, s, d = x.shape
        inner, hd, nh = mlstm_dims(cfg)
        q, k, v, i, f, z = _mlstm_qkvif(params, x, cfg)
        # normalizer trick: append ones column to v, scaled by input gate
        v_aug = jnp.concatenate([v, jnp.ones_like(v[..., :1])], axis=-1)
        v_aug = v_aug * i[..., None].astype(v.dtype)
        if h0 is None:
            h0 = jnp.zeros((b, nh, hd, hd + 1), jnp.float32)
        if cfg.attn_impl == "pallas":
            y_aug, h_final = kops.ssd_scan(q, k, v_aug, f, h0)
        else:
            y_aug, h_final = chunked_linear_scan(q, k, v_aug, f, h0)
        y = _mlstm_normalize(y_aug.astype(jnp.float32)).astype(x.dtype)
        y = y.transpose(0, 2, 1, 3).reshape(b, s, inner)
        y = y * jax.nn.silu(z)
        return y @ params["out_proj"], h_final


def mlstm_decode_step(params, x: Array, cfg: ModelConfig, state: Array):
    b = x.shape[0]
    inner, hd, nh = mlstm_dims(cfg)
    q, k, v, i, f, z = _mlstm_qkvif(params, x, cfg)
    v_aug = jnp.concatenate([v, jnp.ones_like(v[..., :1])], axis=-1)
    v_aug = (v_aug * i[..., None].astype(v.dtype))[:, :, 0]
    y_aug, state = linear_scan_step(q[:, :, 0], k[:, :, 0], v_aug,
                                    f[:, :, 0], state)
    y = _mlstm_normalize(y_aug.astype(jnp.float32)).astype(x.dtype)
    y = y.reshape(b, 1, inner) * jax.nn.silu(z)
    return y @ params["out_proj"], state


# ---------------------------------------------------------------------------
# sLSTM block (xLSTM scalar memory, exact exponential gating + stabilizer)
# ---------------------------------------------------------------------------

def slstm_init(key, cfg: ModelConfig) -> dict:
    dt = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    nh = cfg.num_heads
    hd = d // nh
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_in": dense_init(k1, d, 4 * d, dt),           # z, i, f, o pre-acts
        # block-diagonal recurrent weights: per head [nh, hd, 4*hd]
        "r_rec": (jax.random.normal(k2, (nh, hd, 4 * hd), jnp.float32)
                  / np.sqrt(hd)).astype(jnp.float32),
        "bias": jnp.zeros((4 * d,), jnp.float32),
        "out_proj": dense_init(k3, d, d, dt),
    }


def slstm_cell(params, xt: Array, carry, cfg: ModelConfig):
    """One timestep. xt: [b, 4d] pre-activations from input projection."""
    h, c, n, m = carry                                   # [b, d] each (fp32)
    nh = cfg.num_heads
    d = h.shape[-1]
    hd = d // nh
    hh = h.reshape(-1, nh, hd)
    rec = jnp.einsum("bnd,ndk->bnk", hh, params["r_rec"]).reshape(-1, 4 * d)
    pre = xt.astype(jnp.float32) + rec + params["bias"]
    zt, it, ft, ot = jnp.split(pre, 4, axis=-1)
    zt = jnp.tanh(zt)
    ot = jax.nn.sigmoid(ot)
    log_f = jax.nn.log_sigmoid(ft)
    m_new = jnp.maximum(log_f + m, it)                   # stabilizer
    i_p = jnp.exp(it - m_new)
    f_p = jnp.exp(log_f + m - m_new)
    c_new = f_p * c + i_p * zt
    n_new = f_p * n + i_p
    h_new = ot * c_new / jnp.maximum(n_new, 1e-6)
    return (h_new, c_new, n_new, m_new)


def slstm_block(params, x: Array, cfg: ModelConfig, carry=None):
    """x: [b, S, d] -> [b, S, d]; the recurrence over time runs as one
    fused, differentiable op (``kernels/slstm_scan.py``)."""
    with jax.named_scope("slstm"):
        b, s, d = x.shape
        xin = x @ params["w_in"]                             # [b, S, 4d]
        if carry is None:
            zeros = jnp.zeros((b, d), jnp.float32)
            carry = (zeros, zeros, zeros, jnp.full((b, d), -1e30, jnp.float32))
        with jax.named_scope("slstm_recurrence"):
            hs, s_final = kops.slstm_scan(xin, params["r_rec"],
                                          params["bias"],
                                          jnp.concatenate(carry, axis=-1))
        y = hs.astype(x.dtype)                               # [b, S, d]
        return y @ params["out_proj"], tuple(jnp.split(s_final, 4, axis=-1))


def slstm_decode_step(params, x: Array, cfg: ModelConfig, carry):
    xin = (x @ params["w_in"])[:, 0]
    carry = slstm_cell(params, xin, carry, cfg)
    y = carry[0][:, None, :].astype(x.dtype)
    return y @ params["out_proj"], carry
