"""Decoder stack: scan-over-layers forward, prefill and decode paths.

Layer params are stacked along a leading axis so the whole depth is a
single ``jax.lax.scan`` (HLO size independent of depth; remat per block).
Heterogeneous families use *periodic groups*:

* dense/vlm/audio : one run of L attention blocks
* moe             : one run of L (attention + MoE-FFN) blocks
* ssm (xlstm)     : G groups of (p-1 mLSTM + 1 sLSTM), p = slstm_every
* hybrid (zamba2) : G groups of (p-1 Mamba2 + 1 SHARED attention block),
                    p = attn_every; the attention block's weights are a
                    single copy reused by every group (Zamba2's trick)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S

Array = jax.Array
PyTree = Any


def _maybe_remat(fn, cfg: ModelConfig):
    return jax.checkpoint(fn) if cfg.remat == "block" else fn

PREFIX_LEN = {"vision_patches": 256, "audio_frames": 64}


def _stack(key, n: int, init_fn):
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def group_layout(cfg: ModelConfig) -> Tuple[int, int]:
    """(num_groups, layers_per_group). Uniform families: (1, L)."""
    if cfg.family == "ssm" and cfg.slstm_every:
        p = cfg.slstm_every
        assert cfg.num_layers % p == 0, "num_layers must divide slstm_every"
        return cfg.num_layers // p, p
    if cfg.family == "hybrid" and cfg.attn_every:
        p = cfg.attn_every
        assert cfg.num_layers % p == 0, "num_layers must divide attn_every"
        return cfg.num_layers // p, p
    return 1, cfg.num_layers


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key: Array) -> Dict[str, PyTree]:
    ke, kb, ks = jax.random.split(key, 3)
    params: Dict[str, PyTree] = {"embedding": L.embedding_init(ke, cfg)}
    dt = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    g, p = group_layout(cfg)

    if cfg.family in ("dense", "vlm", "audio"):
        params["blocks"] = _stack(kb, cfg.num_layers, lambda k: {
            "norm1": L.rmsnorm_init(d, dt),
            "attn": L.attention_init(jax.random.fold_in(k, 0), cfg),
            "norm2": L.rmsnorm_init(d, dt),
            "mlp": L.mlp_init(jax.random.fold_in(k, 1), cfg),
        })
    elif cfg.family == "moe":
        params["blocks"] = _stack(kb, cfg.num_layers, lambda k: {
            "norm1": L.rmsnorm_init(d, dt),
            "attn": L.attention_init(jax.random.fold_in(k, 0), cfg),
            "norm2": L.rmsnorm_init(d, dt),
            "moe": M.moe_init(jax.random.fold_in(k, 1), cfg),
        })
    elif cfg.family == "ssm":
        def group_init(k):
            return {
                "mlstm": _stack(jax.random.fold_in(k, 0), p - 1, lambda kk: {
                    "norm": L.rmsnorm_init(d, dt),
                    "cell": S.mlstm_init(kk, cfg),
                }),
                "slstm": {
                    "norm": L.rmsnorm_init(d, dt),
                    "cell": S.slstm_init(jax.random.fold_in(k, 1), cfg),
                },
            }
        params["blocks"] = _stack(kb, g, group_init)
    elif cfg.family == "hybrid":
        def group_init(k):
            return _stack(k, p - 1, lambda kk: {
                "norm": L.rmsnorm_init(d, dt),
                "cell": S.mamba_init(kk, cfg),
            })
        params["blocks"] = _stack(kb, g, group_init)
        params["shared_attn"] = {
            "norm1": L.rmsnorm_init(d, dt),
            "attn": L.attention_init(jax.random.fold_in(ks, 0), cfg),
            "norm2": L.rmsnorm_init(d, dt),
            "mlp": L.mlp_init(jax.random.fold_in(ks, 1), cfg),
        }
    else:
        raise ValueError(cfg.family)

    if cfg.frontend != "none":
        params["frontend_norm"] = L.rmsnorm_init(d, dt)
    params["final_norm"] = L.rmsnorm_init(d, dt)
    return params


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _attn_mlp_block(bp, h, cfg, positions, use_moe: bool,
                    want_kv: bool = False):
    hn = L.rmsnorm(h, bp["norm1"], cfg.norm_eps)
    q, k, v = L._qkv(bp["attn"], hn, cfg, positions)
    att = L.attention_impl(q, k, v, cfg)
    b, s = h.shape[:2]
    h = h + att.reshape(b, s, -1) @ bp["attn"]["wo"]
    hin = L.rmsnorm(h, bp["norm2"], cfg.norm_eps)
    if use_moe:
        out = M.moe_block(bp["moe"], hin, cfg)
        aux = M.load_balance_loss(bp["moe"], hin.reshape(-1, cfg.d_model), cfg)
    else:
        out = L.mlp_block(bp["mlp"], hin)
        aux = jnp.zeros((), jnp.float32)
    kv = (k, v) if want_kv else ()
    return h + out, aux, kv


def forward(params: PyTree, tokens: Array, cfg: ModelConfig,
            prefix_embeds: Optional[Array] = None,
            collect_cache: bool = False):
    """tokens: [b, s_text]. Returns (logits [b, s_text, V], aux_loss, cache).

    ``prefix_embeds`` [b, P, d] (modality stub) is prepended; logits are
    produced for token positions only.
    """
    b, s_text = tokens.shape
    h = L.embed(params["embedding"], tokens)
    if prefix_embeds is not None:
        pre = L.rmsnorm(prefix_embeds.astype(h.dtype), params["frontend_norm"],
                        cfg.norm_eps)
        h = jnp.concatenate([pre, h], axis=1)
    s = h.shape[1]
    positions = jnp.arange(s)
    g, p = group_layout(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    cache = None

    if cfg.family in ("dense", "vlm", "audio", "moe"):
        use_moe = cfg.family == "moe"

        def block(h, bp):
            h, aux, kv = _attn_mlp_block(bp, h, cfg, positions, use_moe,
                                         want_kv=collect_cache)
            return h, (aux, kv)

        h, (auxs, kvs) = jax.lax.scan(_maybe_remat(block, cfg), h,
                                      params["blocks"])
        aux_total = jnp.sum(auxs)
        if collect_cache:
            # kvs: ([L, b, s, kvh, hd], [L, b, s, kvh, hd]) — one pass
            cache = {"k": kvs[0], "v": kvs[1],
                     "pos": jnp.full((b,), s, jnp.int32)}

    elif cfg.family == "ssm":
        def group(h, gp):
            def mblock(h, lp):
                y, hf = S.mlstm_block(
                    lp["cell"], L.rmsnorm(h, lp["norm"], cfg.norm_eps), cfg)
                return h + y, hf
            h, mstates = jax.lax.scan(_maybe_remat(mblock, cfg), h, gp["mlstm"])
            sp = gp["slstm"]
            y, scarry = S.slstm_block(sp["cell"],
                                      L.rmsnorm(h, sp["norm"], cfg.norm_eps),
                                      cfg)
            return h + y, (mstates, scarry)
        h, (mstates, scarries) = jax.lax.scan(group, h, params["blocks"])
        if collect_cache:
            cache = {"mlstm": mstates, "slstm": scarries,
                     "pos": jnp.full((b,), s, jnp.int32)}

    elif cfg.family == "hybrid":
        shared = params["shared_attn"]
        w = min(cfg.shared_attn_window, s)

        def group(h, gp):
            def mblock(h, lp):
                y, hf, ctail = S.mamba_block(
                    lp["cell"], L.rmsnorm(h, lp["norm"], cfg.norm_eps), cfg)
                return h + y, (hf, ctail)
            h, (sstates, convs) = jax.lax.scan(_maybe_remat(mblock, cfg), h,
                                               gp)
            h, _, kv = _attn_mlp_block(shared, h, cfg, positions, False,
                                       want_kv=collect_cache)
            if collect_cache:
                # keep only the last `w` positions (sliding-window cache)
                kv = (kv[0][:, -w:], kv[1][:, -w:])
            return h, (sstates, convs, kv)
        h, (sstates, convs, kvs) = jax.lax.scan(group, h, params["blocks"])
        if collect_cache:
            cache = {"ssm": sstates, "conv": convs,
                     "attn_k": kvs[0], "attn_v": kvs[1],
                     "pos": jnp.full((b,), s, jnp.int32)}

    with jax.named_scope("lm_head"):
        h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
        if prefix_embeds is not None:
            h = h[:, -s_text:]
        logits = L.unembed(params["embedding"], h, cfg)
    return logits, aux_total, cache


def lm_loss(params: PyTree, batch: Dict[str, Array], cfg: ModelConfig,
            aux_coef: float = 0.01) -> Array:
    """Next-token cross entropy (+ MoE aux)."""
    logits, aux, _ = forward(params, batch["tokens"], cfg,
                             prefix_embeds=batch.get("prefix_embeds"))
    targets = batch["labels"]
    with jax.named_scope("lm_head"):
        # one-hot contraction instead of take_along_axis: with vocab-sharded
        # logits this reduces to a tiny psum instead of a logits all-gather
        lse = jax.nn.logsumexp(logits, axis=-1)
        onehot = jax.nn.one_hot(targets, logits.shape[-1], dtype=logits.dtype)
        picked = jnp.einsum("...v,...v->...", logits, onehot)
        nll = lse - picked
        mask = batch.get("loss_mask")
        if mask is None:
            loss = jnp.mean(nll)
        else:
            loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return loss + aux_coef * aux


# ---------------------------------------------------------------------------
# Decode (serve_step)
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> PyTree:
    """Zeroed decode cache pytree (family-dependent; see DESIGN.md §4)."""
    dt = jnp.dtype(cfg.dtype)
    g, p = group_layout(cfg)
    kvh, hd = cfg.num_kv_heads, cfg.head_dim
    if cfg.family in ("dense", "vlm", "audio", "moe"):
        lshape = (cfg.num_layers, batch, max_len, kvh, hd)
        return {"k": jnp.zeros(lshape, dt), "v": jnp.zeros(lshape, dt),
                "pos": jnp.zeros((batch,), jnp.int32)}
    if cfg.family == "ssm":
        inner, mhd, nh = S.mlstm_dims(cfg)
        d = cfg.d_model
        return {
            "mlstm": jnp.zeros((g, p - 1, batch, nh, mhd, mhd + 1), jnp.float32),
            "slstm": tuple(jnp.zeros((g, batch, d), jnp.float32)
                           for _ in range(4)),
            "pos": jnp.zeros((batch,), jnp.int32),
        }
    if cfg.family == "hybrid":
        inner, mhd, nh = S.mamba_dims(cfg)
        n = cfg.ssm_state
        w = min(cfg.shared_attn_window, max_len)
        return {
            "conv": jnp.zeros((g, p - 1, batch, cfg.ssm_conv_width - 1,
                               inner + 2 * n), dt),
            "ssm": jnp.zeros((g, p - 1, batch, nh, n, mhd), jnp.float32),
            "attn_k": jnp.zeros((g, batch, w, kvh, hd), dt),
            "attn_v": jnp.zeros((g, batch, w, kvh, hd), dt),
            "pos": jnp.zeros((batch,), jnp.int32),
        }
    raise ValueError(cfg.family)


def decode_step(params: PyTree, cache: PyTree, token: Array,
                cfg: ModelConfig) -> Tuple[Array, PyTree]:
    """One decode step. token: [b] int32. Returns (logits [b, V], cache)."""
    b = token.shape[0]
    pos = cache["pos"]
    h = L.embed(params["embedding"], token[:, None])      # [b, 1, d]
    g, p = group_layout(cfg)

    if cfg.family in ("dense", "vlm", "audio", "moe"):
        def block(h, xs):
            bp, kc, vc = xs
            hn = L.rmsnorm(h, bp["norm1"], cfg.norm_eps)
            att, kc, vc = L.attention_decode(bp["attn"], hn, cfg, kc, vc, pos)
            h = h + att
            hn = L.rmsnorm(h, bp["norm2"], cfg.norm_eps)
            if cfg.family == "moe":
                h = h + M.moe_block(bp["moe"], hn, cfg)
            else:
                h = h + L.mlp_block(bp["mlp"], hn)
            return h, (kc, vc)

        h, (ks, vs) = jax.lax.scan(block, h,
                                   (params["blocks"], cache["k"], cache["v"]))
        cache = {"k": ks, "v": vs, "pos": pos + 1}

    elif cfg.family == "ssm":
        def group(h, xs):
            gp, mstate, sstate = xs

            def mblock(h, xs2):
                lp, st = xs2
                y, st = S.mlstm_decode_step(
                    lp["cell"], L.rmsnorm(h, lp["norm"], cfg.norm_eps), cfg, st)
                return h + y, st
            h, mstate = jax.lax.scan(mblock, h, (gp["mlstm"], mstate))
            sp = gp["slstm"]
            y, sstate = S.slstm_decode_step(
                sp["cell"], L.rmsnorm(h, sp["norm"], cfg.norm_eps), cfg, sstate)
            return h + y, (mstate, sstate)

        h, (ms, ss) = jax.lax.scan(group, h,
                                   (params["blocks"], cache["mlstm"],
                                    cache["slstm"]))
        cache = {"mlstm": ms, "slstm": ss, "pos": pos + 1}

    elif cfg.family == "hybrid":
        shared = params["shared_attn"]
        w = cache["attn_k"].shape[2]

        def group(h, xs):
            gp, conv_st, ssm_st, kc, vc = xs

            def mblock(h, xs2):
                lp, cst, sst = xs2
                y, cst, sst = S.mamba_decode_step(
                    lp["cell"], L.rmsnorm(h, lp["norm"], cfg.norm_eps),
                    cfg, cst, sst)
                return h + y, (cst, sst)
            h, (conv_st, ssm_st) = jax.lax.scan(mblock, h,
                                                (gp, conv_st, ssm_st))
            hn = L.rmsnorm(h, shared["norm1"], cfg.norm_eps)
            att, kc, vc = L.attention_decode(shared["attn"], hn, cfg, kc, vc,
                                             pos, window=w)
            h = h + att
            h = h + L.mlp_block(shared["mlp"],
                                L.rmsnorm(h, shared["norm2"], cfg.norm_eps))
            return h, (conv_st, ssm_st, kc, vc)

        h, (cs, ss, ks, vs) = jax.lax.scan(
            group, h, (params["blocks"], cache["conv"], cache["ssm"],
                       cache["attn_k"], cache["attn_v"]))
        cache = {"conv": cs, "ssm": ss, "attn_k": ks, "attn_v": vs,
                 "pos": pos + 1}

    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(params["embedding"], h, cfg)[:, 0]
    return logits, cache


# ---------------------------------------------------------------------------
# Prefill -> decode cache handoff
# ---------------------------------------------------------------------------

def prefill_cache_to_decode(cache: PyTree, cfg: ModelConfig, max_len: int,
                            seq_len: int,
                            lengths: Optional[Array] = None) -> PyTree:
    """Convert a ``forward(collect_cache=True)`` cache into the decode
    layout of ``init_cache(cfg, b, max_len)`` — no prompt replay.

    * dense/vlm/audio/moe: pad the KV seq axis out to ``max_len``.
    * ssm: states are O(1) and already decode-shaped — pass through.
    * hybrid: conv/ssm states pass through; the sliding-window KV kept by
      forward (last ``w_f = min(window, s)`` positions, in position
      order) is padded to the decode window ``w_d = min(window,
      max_len)`` and rotated so index ``j`` lands at ring slot
      ``pos % w_d`` expected by ``attention_decode(window=w_d)``.

    ``lengths`` [b] overrides ``pos`` for batches prefilled on
    right-padded prompts (decode then overwrites the first pad slot and
    masks the rest). Only meaningful for KV-cache families — recurrent
    states absorb pad tokens, so ssm/hybrid must prefill at exact
    length.

    Hybrid continuation is bit-exact vs token-by-token replay only while
    ``seq_len <= window``: forward runs the shared block full-causal,
    decode windows it (a pre-existing semantic gap — see
    tests/test_serve.py). The handoff itself is exact either way: the
    converted cache reproduces forward's states and KV placement.
    """
    pos = cache["pos"] if lengths is None else lengths.astype(jnp.int32)

    if cfg.family in ("dense", "vlm", "audio", "moe"):
        s = cache["k"].shape[2]
        assert s <= max_len, (s, max_len)
        pad = ((0, 0), (0, 0), (0, max_len - s), (0, 0), (0, 0))
        return {"k": jnp.pad(cache["k"], pad), "v": jnp.pad(cache["v"], pad),
                "pos": pos}

    if cfg.family == "ssm":
        return {"mlstm": cache["mlstm"], "slstm": cache["slstm"],
                "pos": pos}

    if cfg.family == "hybrid":
        k, v = cache["attn_k"], cache["attn_v"]     # [g, b, w_f, kvh, hd]
        w_f = k.shape[2]
        s = seq_len                   # static prompt length (jit-safe)
        w_d = min(cfg.shared_attn_window, max_len)
        assert w_f <= w_d, (w_f, w_d)
        if w_f < w_d:
            pad = ((0, 0), (0, 0), (0, w_d - w_f), (0, 0), (0, 0))
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        # index j holds position s - w_f + j -> ring slot (s - w_f + j) % w_d
        shift = (s - w_f) % w_d
        if shift:
            k = jnp.roll(k, shift, axis=2)
            v = jnp.roll(v, shift, axis=2)
        return {"conv": cache["conv"], "ssm": cache["ssm"],
                "attn_k": k, "attn_v": v, "pos": pos}

    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# Paged decode (serving tier)
# ---------------------------------------------------------------------------

PAGED_FAMILIES = ("dense", "vlm", "audio", "moe")


def init_paged_cache(cfg: ModelConfig, num_blocks: int,
                     block_size: int) -> PyTree:
    """Zeroed paged KV pool shared by all sessions: ``[L, num_blocks,
    kvh, block_size, hd]`` per tensor (one KV head's page is a
    ``(block_size, hd)`` tile — the paged kernel's block). Block 0 is the engine's scratch
    page (inactive batch rows write there). KV-cache families only —
    ssm/hybrid state is O(1)/O(window) and needs no paging."""
    if cfg.family not in PAGED_FAMILIES:
        raise ValueError(
            f"paged KV serving needs a KV-cache family, got {cfg.family}")
    dt = jnp.dtype(cfg.dtype)
    shape = (cfg.num_layers, num_blocks, cfg.num_kv_heads, block_size,
             cfg.head_dim)
    return {"k_pages": jnp.zeros(shape, dt), "v_pages": jnp.zeros(shape, dt)}


def paged_decode_step(params: PyTree, pages: PyTree, block_tables: Array,
                      pos: Array, token: Array, cfg: ModelConfig
                      ) -> Tuple[Array, PyTree]:
    """One decode step over the paged pool. token [b] int32; block_tables
    [b, nblk]; pos [b] = tokens already in cache (the new token writes at
    slot ``pos`` of its session's pages). Returns (logits [b, V], pages).
    """
    if cfg.family not in PAGED_FAMILIES:
        raise ValueError(cfg.family)
    h = L.embed(params["embedding"], token[:, None])      # [b, 1, d]

    def block(h, xs):
        bp, kp, vp = xs
        hn = L.rmsnorm(h, bp["norm1"], cfg.norm_eps)
        att, kp, vp = L.attention_decode_paged(bp["attn"], hn, cfg, kp, vp,
                                               block_tables, pos)
        h = h + att
        hn = L.rmsnorm(h, bp["norm2"], cfg.norm_eps)
        if cfg.family == "moe":
            h = h + M.moe_block(bp["moe"], hn, cfg)
        else:
            h = h + L.mlp_block(bp["mlp"], hn)
        return h, (kp, vp)

    h, (kps, vps) = jax.lax.scan(
        block, h, (params["blocks"], pages["k_pages"], pages["v_pages"]))
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(params["embedding"], h, cfg)[:, 0]
    return logits, {"k_pages": kps, "v_pages": vps}
