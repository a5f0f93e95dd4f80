"""Arithmetic the plain references share: their matrix products, at the
precision asked for, and the federated iteration they follow.

``precision="highest"`` is the reference: float32 operands, float32
products (``Precision.HIGHEST``, so a TPU does not round to bf16).
``precision="fp8"`` is the control: each operand of each product, in the
forward pass and in the backward pass (the cotangent included), is
rounded to float8 e4m3 under a per-tensor scale, the nearest precision
below the bf16 the configuration states; products accumulate in float32.

Nothing here imports the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _fp8(x):
    """``x`` rounded to float8 e4m3 under a per-tensor scale, in float32."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _ein_hi(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HI)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ein_fp8(spec, a, b):
    return _ein_hi(spec, _fp8(a), _fp8(b))


def _ein_fp8_fwd(spec, a, b):
    qa, qb = _fp8(a), _fp8(b)
    return _ein_hi(spec, qa, qb), (qa, qb)


def _ein_fp8_bwd(spec, res, g):
    # the backward's products take float8 operands too: the cotangent is
    # rounded like any other operand
    _, vjp = jax.vjp(functools.partial(_ein_hi, spec), *res)
    return vjp(_fp8(g))


_ein_fp8.defvjp(_ein_fp8_fwd, _ein_fp8_bwd)


def einsum(precision: str):
    """``ein(spec, a, b)`` at the given precision, float32 out."""
    if precision == "highest":
        return lambda spec, a, b: _ein_hi(
            spec, a.astype(jnp.float32), b.astype(jnp.float32))
    if precision == "default":
        # the backend's default: one bf16 pass per product on a TPU
        return lambda spec, a, b: jnp.einsum(
            spec, a.astype(jnp.float32), b.astype(jnp.float32))
    if precision == "fp8":
        return lambda spec, a, b: _ein_fp8(
            spec, a.astype(jnp.float32), b.astype(jnp.float32))
    raise ValueError(precision)


def rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def cross_entropy(logits, labels):
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def leaf_norms(tree, peer_axis: bool = False, minus=None) -> dict:
    """{leaf path: L2 norm} of ``tree`` (minus ``minus`` leaf by leaf), in
    float32 on the device. With ``peer_axis`` the leading axis holds peers
    and the norm is their root mean square: one peer's norm when all
    agree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    subs = jax.tree.leaves(minus) if minus is not None else [None] * len(flat)

    @jax.jit
    def norms(leaves, subs):
        out = []
        for x, s in zip(leaves, subs):
            x = x.astype(jnp.float32)
            if s is not None:
                x = x - s.astype(jnp.float32)
            sq = jnp.sum(jnp.square(x))
            out.append(jnp.sqrt(sq / x.shape[0]) if peer_axis
                       else jnp.sqrt(sq))
        return jnp.stack(out)
    vals = norms([x for _, x in flat], subs)
    return {jax.tree_util.keystr(p): float(v)
            for (p, _), v in zip(flat, vals.tolist())}


def mar_mean(peers, grid, dtype):
    """Moshpit All-Reduce of one leaf, plainly: the peers laid out on the
    grid, then for each grid axis in turn every group along it takes its
    mean in float32 and keeps it in the leaf's ``dtype``. Under full
    participation every peer ends with the same value, which is
    returned."""
    shape = peers[0].shape
    x = jnp.stack(peers).reshape(tuple(grid) + shape)
    for axis in range(len(grid)):
        mean = jnp.mean(x.astype(jnp.float32), axis=axis, keepdims=True)
        x = jnp.broadcast_to(mean.astype(dtype), x.shape)
    return x.reshape((-1,) + shape)[0]


def host_leaves(tree, scale: float = 1.0, peer: int | None = None) -> dict:
    """{leaf path: float32 numpy copy of the leaf (of one ``peer``'s slice
    of it) times ``scale``}, one leaf at a time."""
    out = {}
    for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.array(x if peer is None else x[peer], np.float32)
        a *= scale
        out[jax.tree_util.keystr(p)] = a
    return out


def fl_readings(loss_fn, make_theta0, batches, grid, lr: float, mu: float,
                steps: int, devices) -> dict:
    """The federated iteration, plainly: every peer takes its local
    momentum-SGD steps (g the mean of its micro-batches' gradients; m = mu
    m + (1 - mu) g; theta = theta - lr m, in float32, theta kept in each
    leaf's own dtype as the configuration states it, momentum in
    float32), then MAR over ``grid`` (``mar_mean``, leaf by leaf)
    averages (theta, m).

    ``make_theta0()`` makes the initial params on the default device
    (called twice: to start, and to measure the change at the end).
    ``batches[t]`` holds {"tokens", "labels"} [n_peers, local_steps,
    n_micro, mb, seq] (numpy) for iteration ``t``.

    Placement: the shared start, MAR and the readings live on
    ``devices[0]``; peer ``p`` computes on ``devices[p % len(devices)]``,
    the peers one after another as the host sends them. While peers
    outnumber the entries of ``devices``, a peer that has finished waits
    in host memory, so a device holds the shared start and one running
    peer at most. (A device may be listed more than once: all state then
    stays on it.) Placement moves bits and changes no arithmetic.

    Returns {"loss": [per iteration, mean over peers and local steps],
    "grad1": {leaf: norm of m / (1 - mu) after the first iteration},
    "grad1_leaves": {leaf: m / (1 - mu) after the first iteration, as a
    float32 numpy array}, "dtheta": {leaf: norm of theta_steps - theta0}}.
    """
    home = devices[0]
    n_peers = int(np.prod(grid))
    park = n_peers > len(devices)
    with jax.default_device(home):
        theta = make_theta0()
        m = jax.jit(lambda t: jax.tree.map(
            lambda x: jnp.zeros(x.shape, jnp.float32), t))(theta)
    dtypes = jax.tree.map(lambda x: x.dtype, theta)

    def local(theta, m, tokens, labels):
        theta32 = jax.tree.map(lambda x: x.astype(jnp.float32), theta)

        def micro(acc, batch):
            loss, g = jax.value_and_grad(loss_fn)(theta32, *batch)
            return (jax.tree.map(jnp.add, acc[0], g), acc[1] + loss), None
        zero = (jax.tree.map(jnp.zeros_like, theta32), jnp.zeros(()))
        (g, loss), _ = jax.lax.scan(micro, zero, (tokens, labels))
        g = jax.tree.map(lambda x: x / tokens.shape[0], g)
        loss = loss / tokens.shape[0]
        m = jax.tree.map(lambda a, b: mu * a + (1.0 - mu) * b, m, g)
        theta = jax.tree.map(
            lambda p, a, dt: (p.astype(jnp.float32) - lr * a).astype(dt),
            theta, m, dtypes)
        return theta, m, loss
    local = jax.jit(local, donate_argnums=(1,))
    copy = jax.jit(lambda t: jax.tree.map(jnp.copy, t))
    mar_leaf = jax.jit(mar_mean, static_argnums=(1, 2))

    def mar(peers):
        """(theta, m) of MAR over the peers' [theta leaves, m leaves], one
        leaf at a time on ``home``; each leaf's inputs are dropped once
        its mean is made."""
        out = []
        for j, dts in enumerate((leaf_dtypes, [jnp.float32] * n_leaves)):
            new = []
            for i, dt in enumerate(dts):
                xs = [jax.device_put(p[j][i], home) for p in peers]
                for p in peers:
                    p[j][i] = None
                new.append(mar_leaf(xs, tuple(grid), dt))
                del xs
            out.append(jax.tree.unflatten(treedef, new))
        return out

    treedef = jax.tree.structure(theta)
    leaf_dtypes = jax.tree.leaves(dtypes)
    n_leaves = len(leaf_dtypes)
    out = {"loss": []}
    for t in range(steps):
        peers, losses = [], []
        for p in range(n_peers):
            dev = devices[p % len(devices)]
            last = p == n_peers - 1
            if dev == home:
                # the last peer takes the shared momentum itself, the
                # others a copy; params are never written in place
                th, mo = theta, (m if last else copy(m))
            else:
                th, mo = jax.device_put((theta, m), dev)
            for b in range(batches[t]["tokens"].shape[1]):
                th, mo, loss = local(th, mo, batches[t]["tokens"][p, b],
                                     batches[t]["labels"][p, b])
                losses.append(loss)
            if park and not last:
                th, mo = jax.device_get((th, mo))
            peers.append([jax.tree.leaves(th), jax.tree.leaves(mo)])
            del th, mo
        del theta, m
        theta, m = mar(peers)
        del peers
        out["loss"].append(float(np.mean([float(x) for x in losses])))
        if t == 0:
            out["grad1"] = {k: v / (1.0 - mu)
                            for k, v in leaf_norms(m).items()}
            out["grad1_leaves"] = host_leaves(m, 1.0 / (1.0 - mu))
    del m
    with jax.default_device(home):
        out["dtheta"] = leaf_norms(theta, minus=make_theta0())
    return out
