"""Plain float32 reference of the xLSTM stack as the program implements it.

The equations, with every departure from the paper that the config file
lists (sigmoid input gate, dense q/k/v, no conv, skip or group norm):

  mLSTM block, x [s, d] after RMSNorm:
    [xi, z] = x W_up;  q = xi W_q / sqrt(hd),  k = xi W_k,  v = xi W_v
    i_t = sigmoid(xi_t W_i),  log f_t = log sigmoid(xi_t W_f)   per head
    F_t = sum_{u<=t} log f_u
    S_tj = exp(F_t - F_j) (q_t . k_j) i_j                        j <= t
    y_t = (sum_j S_tj v_j) / max(|sum_j S_tj|, 1)
    out = (y * silu(z)) W_out
  sLSTM block: the exponential-gated cell with its stabilizer m_t, a
    block-diagonal recurrence per head, scanned over time; out = h W_out.
  Residual around every block; RMSNorm; untied head; mean cross entropy.

The mLSTM is computed in its quadratic parallel form, not the program's
chunked scan. Params arrive in the program's tree layout, made by the
benchmark; nothing of the program is imported.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import refmath

# the output head's leaf, as ``jax.tree_util.keystr`` names it
HEAD = "['embedding']['unembed']"


def loss_fn(model: dict, precision: str = "highest"):
    """``loss(params_f32, tokens [mb, s], labels [mb, s]) -> scalar``."""
    ein = refmath.einsum(precision)
    d, nh, eps = model["d_model"], model["num_heads"], model["norm_eps"]
    inner = model.get("ssm_expand", 2) * d
    mhd = inner // nh
    shd = d // nh

    def mlstm(cp, x):
        b, s, _ = x.shape
        up = ein("bsd,de->bse", x, cp["up_proj"])
        xi, z = up[..., :inner], up[..., inner:]
        heads = lambda t: t.reshape(b, s, nh, mhd)
        q = heads(ein("bsi,ij->bsj", xi, cp["wq"])) / np.sqrt(mhd)
        k = heads(ein("bsi,ij->bsj", xi, cp["wk"]))
        v = heads(ein("bsi,ij->bsj", xi, cp["wv"]))
        ig = jax.nn.sigmoid(ein("bsi,ih->bsh", xi, cp["wi"]))
        logf = jax.nn.log_sigmoid(ein("bsi,ih->bsh", xi, cp["wf"]))
        cum = jnp.cumsum(logf, axis=1)                       # [b, s, nh]
        decay = cum[:, :, None, :] - cum[:, None, :, :]      # [b, t, j, nh]
        causal = jnp.tril(jnp.ones((s, s), bool))[None, :, :, None]
        gate = jnp.where(causal, jnp.exp(jnp.where(causal, decay, 0.0)),
                         0.0) * ig[:, None, :, :]
        scores = ein("bthd,bjhd->btjh", q, k) * gate
        num = ein("btjh,bjhd->bthd", scores, v)
        den = jnp.sum(scores, axis=2)[..., None]
        y = (num / jnp.maximum(jnp.abs(den), 1.0)).reshape(b, s, inner)
        return ein("bsi,id->bsd", y * jax.nn.silu(z), cp["out_proj"])

    def slstm(cp, x):
        b, s, _ = x.shape
        xin = ein("bsd,de->bse", x, cp["w_in"])

        def cell(carry, xt):
            h, c, n, m = carry
            rec = ein("bnk,nkj->bnj", h.reshape(b, nh, shd),
                      cp["r_rec"]).reshape(b, 4 * d)
            pre = xt + rec + cp["bias"]
            zt, it, ft, ot = jnp.split(pre, 4, axis=-1)
            log_f = jax.nn.log_sigmoid(ft)
            m_new = jnp.maximum(log_f + m, it)
            i_p = jnp.exp(it - m_new)
            f_p = jnp.exp(log_f + m - m_new)
            c_new = f_p * c + i_p * jnp.tanh(zt)
            n_new = f_p * n + i_p
            h_new = jax.nn.sigmoid(ot) * c_new / jnp.maximum(n_new, 1e-6)
            return (h_new, c_new, n_new, m_new), h_new

        zeros = jnp.zeros((b, d), jnp.float32)
        carry = (zeros, zeros, zeros, jnp.full((b, d), -1e30, jnp.float32))
        _, hs = jax.lax.scan(cell, carry, jnp.moveaxis(xin, 1, 0))
        return ein("bsd,de->bse", jnp.moveaxis(hs, 0, 1), cp["out_proj"])

    @jax.checkpoint
    def mlstm_layer(h, lp):
        return h + mlstm(lp["cell"], refmath.rmsnorm(h, lp["norm"], eps)), None

    def group(h, gp):
        h, _ = jax.lax.scan(mlstm_layer, h, gp["mlstm"])
        sp = gp["slstm"]
        h = h + jax.checkpoint(slstm)(sp["cell"],
                                      refmath.rmsnorm(h, sp["norm"], eps))
        return h, None

    def loss(params, tokens, labels):
        h = params["embedding"]["tok"][tokens]
        h, _ = jax.lax.scan(group, h, params["blocks"])
        h = refmath.rmsnorm(h, params["final_norm"], eps)
        logits = ein("bsd,dv->bsv", h, params["embedding"]["unembed"])
        return refmath.cross_entropy(logits, labels)

    return loss
