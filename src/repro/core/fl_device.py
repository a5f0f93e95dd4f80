"""Device-backend MAR-FL: the paper's protocol on the production mesh.

A *peer* is a slice of the mesh's DP axes (the whole ``pod`` on the
multi-pod mesh; one ``data`` index on the single-pod mesh — DESIGN.md
§5). Every state leaf carries a leading peer axis sharded over the peer
mesh axes; within a peer, params shard over FSDP/TP axes per
``runtime/sharding.py``.

One FL iteration (Alg. 1, device form):

  1. ``local_steps`` Momentum-SGD steps per peer, each accumulating
     grads over ``n_micro`` microbatches (activation memory control).
     No cross-peer communication — only within-peer FSDP/TP collectives.
  2. Aggregation of (theta, m) through the same composable
     :class:`~repro.core.aggregation.AggregationPipeline` as the sim
     backend: device-backed MAR — ``depth`` masked group-mean rounds
     over the peer grid (``one_shot=True`` fuses them into one global
     all-reduce — beyond-paper variant) — optionally wrapped in wire
     stages (int8-EF compression, ``comm_dtype``), with participation
     masks for churn.

Collective bytes per FL iteration drop by ``local_steps`` x versus
per-step gradient DP — the paper's communication saving, realized on a
TPU mesh as local-SGD cadence (DESIGN.md §2).

``make_serve_step`` / ``make_prefill_step`` cover the inference shapes
(no aggregation — MAR is a training-time protocol).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.aggregation import AggregationPipeline, MarAggregator
from repro.core.moshpit import GridPlan
from repro.core.replan import (MembershipChange, resize_peer_axis,
                               select_survivors)
from repro.models.model import Model
from repro.optim.sgdm import momentum_sgd_step

Array = jax.Array
PyTree = Any


def init_fl_state(model: Model, n_peers: int, key: Array,
                  pipeline: Optional[AggregationPipeline] = None
                  ) -> Dict[str, Any]:
    """Peer-stacked (params, momentum) — every peer starts from the same
    theta^0 (Alg. 1). With a ``pipeline``, its wire-stage state (EF
    residuals etc.) is initialized under ``"pipe"``."""
    params = model.init(key)
    stack = lambda x: jnp.broadcast_to(x[None], (n_peers,) + x.shape)
    params = jax.tree.map(stack, params)
    momentum = jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    state = {"params": params, "momentum": momentum,
             "step": jnp.zeros((), jnp.int32)}
    if pipeline is not None:
        state["pipe"] = pipeline.init_state({"p": params, "m": momentum})
    return state


def resize_fl_state(state: Dict[str, Any], new_n: int,
                    pipeline: Optional[AggregationPipeline] = None
                    ) -> Dict[str, Any]:
    """Elastic membership for the device-backend FL state dict.

    Shrinks/grows the stacked peer axis of params/momentum (and, via
    the pipeline's per-stage hooks, any wire-stage state under
    ``"pipe"``) in place — the same no-restart path as
    ``Federation.resize``; survivors are bit-exact, joiners bootstrap
    from the group mean. The caller re-plans the grid
    (``runtime.fault.elastic_replan``) and rebuilds the train step for
    the new plan.
    """
    old_n = jax.tree.leaves(state["params"])[0].shape[0]
    if new_n == old_n:
        return state
    out = dict(state)
    out["params"] = resize_peer_axis(state["params"], old_n, new_n)
    out["momentum"] = resize_peer_axis(state["momentum"], old_n, new_n)
    if "pipe" in state:
        if pipeline is not None:
            out["pipe"] = pipeline.resize_state(state["pipe"], old_n,
                                                new_n)
        else:
            out["pipe"] = resize_peer_axis(state["pipe"], old_n, new_n)
    return out


def apply_membership(state: Dict[str, Any], change: MembershipChange,
                     pipeline: Optional[AggregationPipeline] = None
                     ) -> Tuple[Dict[str, Any],
                                Optional[AggregationPipeline]]:
    """The device backend's consumer of the unified membership contract
    (DESIGN.md §16): apply one
    :class:`~repro.core.replan.MembershipChange` to the FL state dict
    and re-bind the pipeline to ``change.new_plan``.

    Survivors' params/momentum/pipe state map through the change
    bit-exact (the contiguous-prefix default is the historical slice);
    joiners bootstrap from the group mean, with the per-``WireStage``
    zero rules for wire state (EF residuals, DP bot markers). Returns
    ``(state, pipeline)``; the caller re-jits the train step for the
    new plan (``make_fl_train_step(model, change.new_plan, ...)``) —
    the device aggregator needs an exact grid, so plan the change with
    ``exact_only=True``.
    """
    old_n = jax.tree.leaves(state["params"])[0].shape[0]
    if old_n != change.old_n:
        raise ValueError(f"change was planned for {change.old_n} "
                         f"peers, state has {old_n}")
    new_pipeline = pipeline.with_plan(change.new_plan) \
        if pipeline is not None else None
    if change.same_n:
        return dict(state), new_pipeline
    k = len(change.survivors)
    out = dict(state)
    out["params"] = change.apply_to_tree(state["params"])
    out["momentum"] = change.apply_to_tree(state["momentum"])
    if "pipe" in state:
        # survivor gather is a pure reindex; the joiner bootstrap
        # routes through the per-stage hooks
        pipe = select_survivors(state["pipe"], old_n, change.survivors)
        if pipeline is not None:
            out["pipe"] = pipeline.resize_state(pipe, k, change.new_n)
        else:
            out["pipe"] = resize_peer_axis(pipe, k, change.new_n)
    return out, new_pipeline


def fl_state_shape(model: Model, n_peers: int,
                   momentum_dtype: str = "float32") -> Dict[str, Any]:
    """ShapeDtypeStructs of the FL state (dry-run; no allocation)."""
    pshape = model.init_shape()
    lift = lambda x: jax.ShapeDtypeStruct((n_peers,) + x.shape, x.dtype)
    params = jax.tree.map(lift, pshape)
    mom = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.dtype(momentum_dtype)),
        params)
    return {"params": params, "momentum": mom,
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def make_fl_train_step(model: Model, grid: GridPlan, lr: float = 0.1,
                       mu: float = 0.9, one_shot: bool = False,
                       aggregate: bool = True,
                       comm_dtype: Optional[str] = None,
                       pipeline: Optional[AggregationPipeline] = None
                       ) -> Callable:
    """Returns ``fl_train_step(state, batch, mask=None, agg_mask=None)
    -> (state, metrics)``.

    batch: {"tokens": [P, B, n_micro, mb, s], "labels": ..., optional
    "prefix_embeds": ...} — P peers, B local steps, grad-accumulated
    microbatches.

    ``pipeline`` runs the same composable aggregation as the sim backend
    (device-backed MAR plus wire stages, e.g. ``int8_ef`` compression);
    without one, a plain device-MAR pipeline is built from ``one_shot``
    / ``comm_dtype``. ``mask`` ([P] 0/1 float) is the participation
    mask U_t with the paper's churn semantics: masked peers keep their
    previous state, contribute nothing to their group means, but
    receive them. ``agg_mask`` (default: ``mask``) is the aggregation
    mask A_t — peers in U_t but not A_t keep their local update yet
    miss aggregation (the paper's dropout/straggler path, §3.1).
    When the pipeline carries wire-stage state, build the train state
    with ``init_fl_state(..., pipeline=...)``.
    """
    if pipeline is None and aggregate:
        pipeline = AggregationPipeline(MarAggregator(
            grid, backend="device", one_shot=one_shot,
            comm_dtype=comm_dtype))

    def peer_local_update(params, momentum, peer_batch):
        """One peer: B sequential Momentum-SGD steps."""

        def one_step(carry, step_batch):      # step_batch: [n_micro, mb, ..]
            p, m = carry

            def micro(acc, mb_batch):
                with jax.named_scope("fl.grad"):
                    loss, grads = jax.value_and_grad(model.loss)(p, mb_batch)
                with jax.named_scope("fl.opt"):
                    acc = (jax.tree.map(jnp.add, acc[0], grads),
                           acc[1] + loss)
                return acc, None

            with jax.named_scope("fl.opt"):
                zeros = jax.tree.map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), p)
            (gsum, lsum), _ = jax.lax.scan(
                micro, (zeros, jnp.zeros((), jnp.float32)), step_batch)
            with jax.named_scope("fl.opt"):
                n_micro = jax.tree.leaves(step_batch)[0].shape[0]
                grads = jax.tree.map(lambda g: g / n_micro, gsum)
                p, m = momentum_sgd_step(p, m, grads, lr, mu)
                return (p, m), lsum / n_micro

        (params, momentum), losses = jax.lax.scan(
            one_step, (params, momentum), peer_batch)
        with jax.named_scope("fl.opt"):
            return params, momentum, jnp.mean(losses)

    def fl_train_step(state, batch, mask=None, agg_mask=None):
        params, momentum = state["params"], state["momentum"]
        new_p, new_m, loss = jax.vmap(peer_local_update)(
            params, momentum, batch)
        with jax.named_scope("fl.opt"):
            if mask is not None:
                # churn: masked-out peers carry previous state forward
                sel = lambda new, old: jax.tree.map(
                    lambda a, b: jnp.where(
                        mask.reshape((-1,) + (1,) * (a.ndim - 1)) > 0, a, b),
                    new, old)
                new_p, new_m = sel(new_p, params), sel(new_m, momentum)
            new_state = {"params": new_p, "momentum": new_m,
                         "step": state["step"] + 1}
            metrics = {"loss": jnp.mean(loss)}
        if aggregate:
            if pipeline.stages and "pipe" not in state:
                raise ValueError(
                    "pipeline has wire stages; build the state with "
                    "init_fl_state(..., pipeline=pipeline)")
            with jax.named_scope("fl.aggregate"):
                m = agg_mask if agg_mask is not None else mask
                if m is None:
                    m = jnp.ones((grid.capacity,), jnp.float32)
                key = jax.random.fold_in(jax.random.PRNGKey(0),
                                         state["step"])
                agg, new_pipe = pipeline({"p": new_p, "m": new_m},
                                         state.get("pipe", {}), m, key)
            new_state["params"], new_state["momentum"] = agg["p"], agg["m"]
            if "pipe" in state:
                new_state["pipe"] = new_pipe
        return new_state, metrics

    return fl_train_step


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def make_serve_step(model: Model) -> Callable:
    """One greedy decode step over a request batch (no aggregation)."""

    def serve_step(params, cache, token):
        logits, cache = model.decode_step(params, cache, token)
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_token, cache

    return serve_step


def make_prefill_step(model: Model, max_len: Optional[int] = None
                      ) -> Callable:
    """Prefill: forward over the full prompt, emit last-token logits and
    the populated cache (single pass; see transformer.forward).

    With ``max_len`` the cache is returned *decode-ready* — converted to
    the exact ``init_cache(cfg, b, max_len)`` layout via
    ``prefill_cache_to_decode`` — so ``serve_step`` continues from
    position ``s`` directly, with no token-by-token prompt replay."""

    def prefill_step(params, batch):
        tokens = batch["tokens"]
        logits, _, cache = model.forward(
            params, tokens,
            prefix_embeds=batch.get("prefix_embeds"),
            collect_cache=True)
        if max_len is not None:
            cache = model.prefill_cache_to_decode(
                cache, max_len, tokens.shape[1])
        return logits[:, -1], cache

    return prefill_step


def make_paged_serve_step(model: Model) -> Callable:
    """One greedy decode step over the paged serving pool.

    ``paged_serve_step(params, pages, block_tables, pos, token) ->
    (next_token, logits, pages)`` — logits are exposed so the engine can
    apply per-session sampling/stops host-side."""

    def paged_serve_step(params, pages, block_tables, pos, token):
        logits, pages = model.paged_decode_step(params, pages, block_tables,
                                                pos, token)
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_token, logits, pages

    return paged_serve_step
