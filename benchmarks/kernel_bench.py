"""Kernel micro-benchmarks: interpret-mode correctness-scale timings of
the Pallas kernels vs their jnp references (CPU wall-times are NOT TPU
projections — roofline numbers live in the dry-run)."""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, std_argparser
from repro.kernels import ops, ref


def _time(fn, *args, n=3):
    fn(*args)  # warm
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def main(argv=None) -> int:
    ap = std_argparser(__doc__)
    args = ap.parse_args(argv)
    rng = np.random.default_rng(args.seed)

    b, s, h, kvh, d = 1, 256, 8, 2, 64
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, kvh, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, kvh, d)), jnp.float32)
    emit("kernel", name="flash_attention", shape=f"{b}x{s}x{h}x{d}",
         us_kernel=round(_time(lambda *a: ops.flash_attention(*a), q, k, v)),
         us_ref=round(_time(
             lambda *a: ref.flash_attention_ref(*a), q, k, v)))

    qd = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    lens = jnp.asarray([s], jnp.int32)
    emit("kernel", name="decode_attention", shape=f"{b}x{s}x{h}x{d}",
         us_kernel=round(_time(
             lambda *a: ops.decode_attention(*a), qd, k, v, lens)),
         us_ref=round(_time(
             lambda *a: ref.decode_attention_ref(*a), qd, k, v, lens)))

    # serving-shape decode: dense cache vs paged pool (block table
    # indirection cost on identical KV bytes; bench gate lives in
    # benchmarks/serving.py)
    bsrv, bs = 8, 16
    nblk = s // bs
    qp = jnp.asarray(rng.normal(size=(bsrv, h, d)), jnp.float32)
    kd = jnp.asarray(rng.normal(size=(bsrv, s, kvh, d)), jnp.float32)
    vd = jnp.asarray(rng.normal(size=(bsrv, s, kvh, d)), jnp.float32)
    kp = kd.reshape(bsrv * nblk, bs, kvh, d).swapaxes(1, 2)
    kp = jnp.concatenate([jnp.zeros((1,) + kp.shape[1:], kp.dtype), kp])
    vp = vd.reshape(bsrv * nblk, bs, kvh, d).swapaxes(1, 2)
    vp = jnp.concatenate([jnp.zeros((1,) + vp.shape[1:], vp.dtype), vp])
    bt = jnp.arange(1, 1 + bsrv * nblk, dtype=jnp.int32).reshape(bsrv, nblk)
    lens_p = jnp.full((bsrv,), s - 3, jnp.int32)     # ragged tail
    emit("kernel", name="decode_attention_paged", shape=f"{bsrv}x{s}x{h}x{d}",
         block_size=bs,
         us_dense=round(_time(
             lambda *a: ops.decode_attention(*a), qp, kd, vd, lens_p)),
         us_paged=round(_time(
             lambda *a: ops.paged_decode_attention(*a), qp, kp, vp, bt,
             lens_p)),
         us_ref=round(_time(
             lambda *a: ref.paged_decode_attention_ref(*a), qp, kp, vp, bt,
             lens_p)))

    nh, dk, dv = 2, 16, 32
    qs = jnp.asarray(rng.normal(size=(b, nh, s, dk)), jnp.float32)
    ks = jnp.asarray(rng.normal(size=(b, nh, s, dk)) * 0.3, jnp.float32)
    vs = jnp.asarray(rng.normal(size=(b, nh, s, dv)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.01, 0.5, size=(b, nh, s)), jnp.float32)
    h0 = jnp.zeros((b, nh, dk, dv), jnp.float32)
    emit("kernel", name="ssd_scan", shape=f"{b}x{nh}x{s}x{dk}x{dv}",
         us_kernel=round(_time(lambda *x: ops.ssd_scan(*x),
                               qs, ks, vs, a, h0)),
         us_ref=round(_time(lambda *x: ref.ssd_scan_ref(*x),
                            qs, ks, vs, a, h0)))

    g, m, dd = 8, 5, 4096
    x = jnp.asarray(rng.normal(size=(g, m, dd)), jnp.float32)
    mask = jnp.asarray(rng.random((g, m)) < 0.8, jnp.float32)
    emit("kernel", name="group_mean", shape=f"{g}x{m}x{dd}",
         us_kernel=round(_time(lambda *x: ops.group_mean(*x), x, mask)),
         us_ref=round(_time(lambda *x: ref.group_mean_ref(*x), x, mask)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
