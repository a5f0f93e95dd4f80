"""Weights from the seed, made on the device in one jitted call.

The benchmark, not the program, makes the weights, in the program's
layout (``Model.init_shape()``) and in the dtype each leaf is served in,
so the reference can start from the very same bits. Each leaf is drawn
from its own fold of the seed's key: norms are ones, biases zeros, the
token embedding N(0, 0.02^2), and every other matrix N(0, 1/fan_in) with
the fan-in its second-to-last dimension.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness import seed_key


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def _draw(key, name: str, shape, dtype):
    if "norm" in name:
        return jnp.ones(shape, dtype)
    if name == "bias" or len(shape) < 2:
        return jnp.zeros(shape, dtype)
    scale = 0.02 if name == "tok" else shape[-2] ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _build(shape_tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(shape_tree)

    def build(key):
        leaves = [_draw(jax.random.fold_in(key, i), _leaf_name(p), x.shape,
                        x.dtype) for i, (p, x) in enumerate(flat)]
        return jax.tree.unflatten(treedef, leaves)
    return build


def make_params(shape_tree, seed: int, shardings=None):
    """Serving weights: the model's params, from ``seed``."""
    return jax.jit(_build(shape_tree), out_shardings=shardings)(
        seed_key(seed))


def make_fl_state(shape_tree, n_peers: int, seed: int, shardings=None):
    """The FL train state as ``core/fl_device.init_fl_state`` lays it out:
    every peer starts from the same params, momentum zero in float32."""
    build = _build(shape_tree)

    def state(key):
        params = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_peers,) + x.shape),
            build(key))
        momentum = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                params)
        return {"params": params, "momentum": momentum,
                "step": jnp.zeros((), jnp.int32)}
    return jax.jit(state, out_shardings=shardings)(seed_key(seed))
