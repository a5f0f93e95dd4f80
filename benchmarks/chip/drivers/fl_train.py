"""Driver for federated training cells: one FL iteration per call, on the
cell's devices.

The timed path is the program's own train step
(``core/fl_device.make_fl_train_step``), jitted with its state donated
as ``launch/train.jit_train_step`` does: every peer's local momentum-SGD
steps, then the MAR aggregation of params and momentum. On n > 1 devices
the peers lie on a (data=n, model=1) mesh of the cell's devices, state
and batch laid out by ``runtime/sharding`` (``state_shardings``,
``batch_shardings``), and the jit takes those shardings, so MAR's rounds
cross between chips. Token batches come from a seeded ring made in
set-up and cross to the device(s) each call, shaped
[P, B, n_micro, mb, s] as ``train.py`` feeds them.

Set-up builds the step and its state from the seed, and drives them
through their first ``check_steps`` calls on the first ring entries;
that same step and state then run the window. Once the window has
closed and the state is freed, the plain reference follows those first
calls from the same weights and batches on the cell's devices
(``refmath.fl_readings``), and ``compare.train_checks`` holds the first
call's gradient as the optimizer received it (momentum / (1 - mu)) and
the change of the params over the calls to the cell's limits.

``fault`` (tests and calibration only) breaks the timed path:
  "frozen"     the step returns its state unchanged;
  "half_batch" the second half of the peers train on the first half's
               rows, so the mean is taken over half of the batch;
  "no_mar"     the step leaves the exchange between peers out.
"""
from __future__ import annotations

import collections
import json
import math
import time
from types import SimpleNamespace

import compare
import harness
import refmath
import traffic as traffic_gen
import weights


def _grid(tr: dict):
    from repro.core.moshpit import plan_grid
    dims = tuple(tr["grid"])
    grid = plan_grid(tr["peers"], group_size=dims[0], depth=len(dims))
    if tuple(grid.dims) != dims:
        raise harness.BenchError(f"grid {grid.dims} is not {dims}")
    return grid


def batch_shape(tr: dict) -> tuple:
    """(peers, local steps, micro-batches, rows per micro-batch, seq): the
    [P, B, n_micro, mb, s] layout ``train.py`` feeds the step."""
    return (tr["peers"], tr["local_steps"], tr.get("micro_batches", 1),
            tr["batch"], tr["seq"])


def make_ring(tr: dict, vocab_size: int, seed: int, n: int) -> list:
    """``n`` calls' batches from the seed, every row different."""
    P, B, n_micro, mb, S = batch_shape(tr)
    return traffic_gen.lm_token_ring(vocab_size, P * B * n_micro * mb, S,
                                     seed, n)


def shardings(devices, model, tr: dict):
    """None on one device. On n devices: the state's and the batch's
    ``NamedSharding``s, and a replicated one, over a (data=n, model=1)
    mesh of ``devices``: one peer per device, as ``runtime/sharding`` lays
    a peer-stacked state out."""
    if len(devices) == 1:
        return None
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.runtime.sharding import (batch_shardings, make_shard_plan,
                                        state_shardings)
    mesh = Mesh(np.asarray(devices).reshape(len(devices), 1),
                ("data", "model"))
    plan = make_shard_plan(mesh)
    if plan.n_peers != tr["peers"]:
        raise harness.BenchError(f"{tr['peers']} peers on {plan.n_peers} "
                                 f"devices: the mesh takes one per device")
    cfg = model.cfg
    state, batch = abstract_args(model, tr, None)
    return SimpleNamespace(
        state=state_shardings(state, plan, head_dim=cfg.head_dim,
                              num_heads=cfg.num_heads,
                              num_kv_heads=cfg.num_kv_heads),
        batch=batch_shardings(batch, plan),
        replicated=NamedSharding(mesh, PartitionSpec()))


def abstract_args(model, tr: dict, sh) -> tuple:
    """(state, batch) of a call as ``ShapeDtypeStruct``s, laid out by
    ``sh`` (``shardings``, or None) as the window's arrays are, to lower
    the step without making them: the sLSTM kernels find the mesh they
    run on from their operands' shardings."""
    import jax
    import jax.numpy as jnp
    from repro.core.fl_device import fl_state_shape
    state = fl_state_shape(model, tr["peers"])
    batch = {k: jax.ShapeDtypeStruct(batch_shape(tr), jnp.int32)
             for k in ("tokens", "labels")}
    if sh is None:
        return state, batch
    put = lambda x, s: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, x.dtype, sharding=s)
    return jax.tree.map(put, state, sh.state), jax.tree.map(
        put, batch, sh.batch)


def build_step(ctx, model, grid, fault=None) -> tuple:
    """(jitted step, batch placer, ``shardings``) on ``ctx.devices``."""
    import jax
    from repro.core import fl_device

    tr = ctx.traffic
    shape = batch_shape(tr)
    sh = shardings(ctx.devices, model, tr)

    def feed(raw):
        # on one device, uncommitted to JAX's default one, as train.py's
        return jax.device_put({k: v.reshape(shape) for k, v in raw.items()},
                              None if sh is None else sh.batch)
    if fault is None or fault == "half_batch":
        from repro.core.aggregation import build_pipeline
        fn = fl_device.make_fl_train_step(
            model, grid, lr=tr["lr"],
            pipeline=build_pipeline("mar", grid, backend="device"))
    else:
        inner = fl_device.make_fl_train_step(
            model, grid, lr=tr["lr"], aggregate=fault != "no_mar")

        def frozen(state, batch):
            _, metrics = inner(state, batch)
            return state, metrics
        fn = frozen if fault == "frozen" else inner
    kw = {} if sh is None else dict(in_shardings=(sh.state, sh.batch),
                                    out_shardings=(sh.state, sh.replicated))
    return jax.jit(fn, donate_argnums=0, **kw), feed, sh


def _feed_rows(tr: dict, raw: dict, fault=None) -> dict:
    if fault != "half_batch":
        return raw
    P = tr["peers"]
    out = {}
    for k, v in raw.items():
        v = v.reshape(P, -1, v.shape[-1]).copy()
        v[P // 2:] = v[:P - P // 2][:P // 2]
        out[k] = v.reshape(raw[k].shape)
    return out


def program_readings(ctx, model, grid, ring, fault=None,
                     built=None) -> tuple:
    """Set-up: build the step (or take ``built``, a ``build_step`` result
    for the same fault) and the state, drive the first calls. Returns
    (step, state, feed, readings of those calls). The readings' first
    gradient is the momentum after the first call over (1 - mu); its
    leaves ("grad1_leaves", float32 numpy) are peer 0's: after MAR all
    peers hold the same."""
    import jax
    tr = ctx.traffic
    step, feed, sh = built or build_step(ctx, model, grid, fault)
    shape = model.init_shape()
    state = weights.make_fl_state(shape, tr["peers"], ctx.seed,
                                  None if sh is None else sh.state)
    jax.block_until_ready(state)
    ctx.phase("state made")
    losses, grad1, leaves = [], None, None
    for t in range(tr["check_steps"]):
        state, metrics = step(state, feed(_feed_rows(tr, ring[t], fault)))
        losses.append(float(metrics["loss"]))
        ctx.phase(f"call {t + 1} of the check")
        if t == 0:
            grad1 = {k: v / (1.0 - tr["mu"]) for k, v in refmath.leaf_norms(
                state["momentum"], peer_axis=True).items()}
            leaves = refmath.host_leaves(state["momentum"],
                                         1.0 / (1.0 - tr["mu"]), peer=0)
    # the initial weights again, made from the seed once the step's
    # working memory is free
    theta0 = weights.make_params(shape, ctx.seed,
                                 None if sh is None else sh.replicated)
    dtheta = refmath.leaf_norms(state["params"], peer_axis=True,
                                minus=jax.tree.map(lambda x: x[None], theta0))
    del theta0
    return step, state, feed, {"loss": losses, "grad1": grad1,
                               "dtheta": dtheta, "grad1_leaves": leaves}


def reference_readings(ctx, ring, precision="highest") -> dict:
    """The plain reference over the same first calls, from the same
    weights, on the cell's devices; ``"head"`` names its output head's
    leaf."""
    import jax
    from repro.models.model import Model
    tr = ctx.traffic
    ref = harness.load_module("references", ctx.config["reference"])
    shape = Model(harness.model_config(ctx.config)).init_shape()
    layout = batch_shape(tr)
    batches = [{k: v.reshape(layout) for k, v in ring[t].items()}
               for t in range(tr["check_steps"])]
    out = refmath.fl_readings(
        ref.loss_fn(ctx.config["model"], precision),
        lambda: weights.make_params(shape, ctx.seed), batches,
        tr["grid"], tr["lr"], tr["mu"], tr["check_steps"], ctx.devices)
    out["head"] = ref.HEAD
    return out


def run(ctx, fault=None) -> dict:
    import jax
    from repro.models.model import Model

    tr = ctx.traffic
    model = Model(harness.model_config(ctx.config))
    grid = _grid(tr)
    tokens_per_call = math.prod(batch_shape(tr))
    ring = make_ring(tr, model.cfg.vocab_size, ctx.seed, tr["ring"])
    ctx.phase("ring made")
    step, state, feed, prog = program_readings(ctx, model, grid, ring, fault)
    jax.block_until_ready(state)

    # the window: the same step and state, the ring continued. Calls are
    # sent up to ``dispatch_ahead`` ahead of the oldest one whose loss is
    # read, so that a stall of the host does not leave the chip idle. When
    # the time is up nothing more is sent, all that was sent is waited
    # for, and the window closes after that wait.
    ahead = tr["dispatch_ahead"]
    i, calls, failed, losses, traced = tr["check_steps"], 0, 0, [], 0
    pending, done_s = collections.deque(), []

    def read_oldest() -> bool:
        loss = float(pending.popleft())
        done_s.append(time.perf_counter() - t0)
        losses.append(loss)
        return not math.isfinite(loss)

    t0 = ctx.window_begin()
    while time.perf_counter() - t0 < ctx.seconds:
        traced += ctx.tracing
        with ctx.span("bench.fl.feed"):
            batch = feed(_feed_rows(tr, ring[i % len(ring)], fault))
        with ctx.span("bench.fl.step"):
            state, metrics = step(state, batch)
        pending.append(metrics["loss"])
        calls += 1
        i += 1
        with ctx.span("bench.fl.wait"):
            # one ahead while traced, so the trace holds a few calls
            while len(pending) > (1 if ctx.tracing else ahead):
                failed += read_oldest()
        if ctx.tracing and not ctx.trace_due():
            # every traced call ends inside the traced part
            jax.block_until_ready(state)
            ctx.maybe_stop_trace()
    with ctx.span("bench.fl.wait"):
        jax.block_until_ready(state)
        while pending:
            failed += read_oldest()
    window_s = ctx.window_end()
    ctx.read_memory_peak()
    del state, step, batch
    ctx.counters.update(tokens_per_call=tokens_per_call, traced_calls=traced)

    t_ref = time.perf_counter()
    ref = reference_readings(ctx, ring)
    ctx.log(f"reference {time.perf_counter() - t_ref:.3f} s")
    ctx.log("readings " + json.dumps(compare.loggable(
        {"program": prog, "reference": ref})))
    ctx.log("grad1_diff by leaf " + json.dumps(compare.diff_rels(prog, ref)))
    for c in compare.train_checks(prog, ref, ctx.cell["limits"]):
        ctx.check(*c)
    ctx.log(f"window: {calls} calls in {window_s:.3f} s, losses "
            f"{losses[0]:.4f} .. {losses[-1]:.4f}; calls read at seconds "
            + " ".join(f"{s:.4f}" for s in done_s))
    return {"metrics": {"train_tokens_per_s": (calls * tokens_per_call
                                               / window_s, "tokens/s")},
            "attempted": calls, "failed": failed}
