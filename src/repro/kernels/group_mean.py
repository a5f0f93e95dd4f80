"""Pallas TPU masked group mean — the MAR aggregation hot spot.

MAR round g averages each group of M peer states (paper Alg. 1 line 10);
on a host/accelerator that owns several peer replicas this is a masked
mean over the group axis, memory-bound over the full model state. The
kernel fuses mask multiply, group-sum, count, divide and the empty-group
fallback into one VMEM pass over [M, D] tiles — one read of x, one
write of y, instead of the 4 materialized intermediates of the jnp path
(mask-mul, sum, count-div, where).

Grid (G, n_tiles); block [1, M, bd]. The whole mask [G, M] rides in
SMEM and is read one scalar at a time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _group_mean_kernel(mask_ref, x_ref, o_ref, *, m: int):
    gi = pl.program_id(0)
    # the mask is read one SMEM scalar at a time (a vector load from
    # SMEM does not lower); each row is scaled by its own scalar
    num = x_ref[0, 0:1, :].astype(jnp.float32) * mask_ref[gi, 0]
    den = mask_ref[gi, 0]
    for j in range(1, m):
        num = num + x_ref[0, j:j + 1, :].astype(jnp.float32) \
            * mask_ref[gi, j]
        den = den + mask_ref[gi, j]
    mean = num / jnp.maximum(den, 1.0)               # [1, bd]
    x = x_ref[0].astype(jnp.float32)                 # [M, bd]
    out = jnp.where(den > 0, jnp.broadcast_to(mean, x.shape), x)
    o_ref[0] = out.astype(o_ref.dtype)


def group_mean_fwd(x: jax.Array, mask: jax.Array, block_d: int = 2048,
                   interpret: bool = False) -> jax.Array:
    """x [G, M, D]; mask [G, M] -> [G, M, D] (each slot gets its group's
    masked mean; fully-dropped groups keep their own values)."""
    g, m, d = x.shape
    bd = min(block_d, d)
    while d % bd:
        bd //= 2
    nt = d // bd

    kernel = functools.partial(_group_mean_kernel, m=m)
    out = pl.pallas_call(
        kernel,
        grid=(g, nt),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # whole [G, M]
            pl.BlockSpec((1, m, bd), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, m, bd), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((g, m, d), x.dtype),
        interpret=interpret,
    )(mask.astype(jnp.float32), x)
    return out
