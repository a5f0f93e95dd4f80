"""Pallas TPU sLSTM time recurrence (xLSTM scalar memory), differentiable.

The cell of ``repro.models.ssm.slstm_cell``, run over a whole sequence:

    pre_t = xin_t + bias + h_{t-1} R          (R block-diagonal per head)
    z, i, f, o = tanh(pz), pi, log_sigmoid(pf), sigmoid(po)
    m_t = max(f + m_{t-1}, i)
    c_t = exp(f + m_{t-1} - m_t) c_{t-1} + exp(i - m_t) z
    n_t = exp(f + m_{t-1} - m_t) n_{t-1} + exp(i - m_t)
    h_t = o c_t / max(n_t, 1e-6)

Two kernels, each one ``pallas_call`` over time blocks with every step of
a block in an in-kernel loop, the recurrent weights R ([nh, hd, 4hd]
f32) resident in VMEM and the carry in VMEM scratch across blocks:

* forward: writes h_t for every step, the pre-activations and the carry
  before each step (what the backward needs), and the final carry;
* backward: runs the reverse-time recurrence of (dh, dc, dn) and writes
  the pre-activations' cotangent ``dpre`` for every step; it keeps no
  weight-gradient accumulator in the loop.

``slstm_scan`` ties them with a ``jax.custom_vjp`` that takes the weight
gradients after the loop as large products: dR[n] = sum_t h_{t-1}[n]^T
dpre_t[n], dbias = sum_t dpre_t, dxin = dpre.

The recurrent product is a VPU multiply-add in f32 (a matrix-vector
product would use a 128th of the MXU): forward, h_{t-1} broadcast along
lanes (a transpose of 32 vregs per head) times R's rows, summed over
sublanes; backward, dpre_t broadcast along sublanes times R's rows,
summed over lanes by a transpose of the 32-vreg partial sums. Gates and
carry are f32 throughout. Under ``vmap`` (the FL step's peers) the mapped
axis folds into the kernel's rows, each peer with its own R, so that the
peers' dependency chains interleave in one loop (``_folding``).

The stabilizer m only rescales (c, n): with C = c e^m and N = n e^m the
recurrence of (C, N) does not involve m, and h = o C / N. So the
backward treats each m_t as a constant, and adds the two paths left: from
the initial carry's m into (C, N), and from the final carry's m back
along the chain of maxima. This is exact while the clamp max(n, 1e-6)
does not engage, which holds for every carry the model passes: from a
fresh carry n_t >= 1 for every t >= 1, and n never falls below 1 after.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

EPS = 1e-6
_LANES = 128
_BLOCK_T = 128         # time steps per grid step


def _time_block(s: int) -> int:
    """Steps per grid step: a multiple of 16 (xin is bf16), at most
    ``_BLOCK_T``."""
    return min(_BLOCK_T, -(-s // 16) * 16)


def _lane_width(w: int) -> int:
    return _LANES if w % _LANES == 0 else w


def _rec(r_ref, g: int, h, nh: int):
    """h_{t-1} R for one row of group ``g``: ``h(lo, hi)`` loads h[:, lo:hi]
    from VMEM (a fresh load, so that each head's slice broadcasts) ->
    [1, 4d]; per head a sum over sublanes of R[g, n]'s rows scaled by h
    broadcast along lanes."""
    hd = r_ref.shape[2]
    lw = _lane_width(4 * hd)
    out = []
    for n in range(nh):
        hb = jnp.broadcast_to(h(n * hd, (n + 1) * hd), (lw, hd)).T
        for l0 in range(0, 4 * hd, lw):
            out.append(jnp.sum(r_ref[g, n, :, l0:l0 + lw] * hb, axis=0,
                               keepdims=True))
    return jnp.concatenate(out, axis=1)


def _rec_t(r_ref, g: int, dp, nh: int):
    """dpre R^T for one row of group ``g``: ``dp(lo, hi)`` loads
    dpre[:, lo:hi] from VMEM -> [1, d]; per head the lane tiles of R[g, n]'s
    rows scaled by dpre added, then summed over lanes by a transpose and
    a sum over sublanes."""
    hd = r_ref.shape[2]
    lw = _lane_width(4 * hd)
    out = []
    for n in range(nh):
        acc = None
        for l0 in range(0, 4 * hd, lw):
            part = r_ref[g, n, :, l0:l0 + lw] * \
                dp(n * 4 * hd + l0, n * 4 * hd + l0 + lw)
            acc = part if acc is None else acc + part
        out.append(jnp.sum(acc.T, axis=0, keepdims=True))
    return jnp.concatenate(out, axis=1)


def _gates(pre, m_prev, d: int):
    z = jnp.tanh(pre[:, :d])
    it = pre[:, d:2 * d]
    log_f = jax.nn.log_sigmoid(pre[:, 2 * d:3 * d])
    o = jax.nn.sigmoid(pre[:, 3 * d:])
    a = log_f + m_prev
    m = jnp.maximum(a, it)
    return z, it, a, o, m, jnp.exp(it - m), jnp.exp(a - m)


def _fwd_kernel(x_ref, r_ref, b_ref, s0_ref, h_ref, pre_ref, sp_ref, final_ref,
                carry_ref, row_ref, *, nh: int, bt: int, s: int):
    j = pl.program_id(0)
    rows, d = s0_ref.shape[0], s0_ref.shape[1] // 4
    per_group = rows // r_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        carry_ref[...] = s0_ref[...]

    for r in range(rows):
        g = r // per_group
        pre_ref[r] = x_ref[r].astype(jnp.float32) + b_ref[g:g + 1]

    def step(t, carry):
        valid = j * bt + t < s
        out = []
        for r in range(rows):
            h, c, n, m = carry[r]
            sp_ref[r, pl.ds(t, 1), :] = jnp.concatenate([h, c, n, m], axis=1)
            # a static row, so that its slices load broadcast
            row_ref[r:r + 1, :] = h
            pre = pre_ref[r, pl.ds(t, 1), :] + _rec(
                r_ref, r // per_group,
                lambda lo, hi: row_ref[r:r + 1, lo:hi], nh)
            pre_ref[r, pl.ds(t, 1), :] = pre
            z, _, _, o, m_new, ip, fp = _gates(pre, m, d)
            c_new = fp * c + ip * z
            n_new = fp * n + ip
            h_new = o * c_new / jnp.maximum(n_new, EPS)
            h_ref[r, pl.ds(t, 1), :] = h_new
            out.append(tuple(jnp.where(valid, new, old) for new, old in
                             zip((h_new, c_new, n_new, m_new), carry[r])))
        return tuple(out)

    init = tuple(tuple(carry_ref[r:r + 1, k * d:(k + 1) * d] for k in range(4))
                 for r in range(rows))
    final = jax.lax.fori_loop(0, bt, step, init)
    for r in range(rows):
        carry_ref[r:r + 1, :] = jnp.concatenate(final[r], axis=1)

    @pl.when(j == pl.num_programs(0) - 1)
    def _finish():
        final_ref[...] = carry_ref[...]


def _bwd_kernel(dy_ref, pre_ref, sp_ref, r_ref, seed_ref, dpre_ref, ds0_ref,
                carry_ref, row_ref, *, nh: int, bt: int, s: int, nblocks: int):
    j = pl.program_id(0)
    blk = nblocks - 1 - j
    rows, d = seed_ref.shape[0], seed_ref.shape[1] // 4
    per_group = rows // r_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        carry_ref[...] = seed_ref[...]

    # the carry of each row, back in time: dh from the next step's
    # product, dc, dn, g along the chain of maxima, and the share of
    # m_{t-1} in (C, N), which only the initial carry's m takes
    def step(k, carry):
        t = bt - 1 - k
        valid = blk * bt + t < s
        out = []
        for r in range(rows):
            dhr, dc, dn, g = carry[r][:4]
            pre = pre_ref[r, pl.ds(t, 1), :]
            sp = sp_ref[r, pl.ds(t, 1), :]
            cp, np_, mp = sp[:, d:2 * d], sp[:, 2 * d:3 * d], sp[:, 3 * d:]
            z, it, a, o, _, ip, fp = _gates(pre, mp, d)
            c = fp * cp + ip * z
            n = fp * np_ + ip
            nc = jnp.maximum(n, EPS)
            dh = dy_ref[r, pl.ds(t, 1), :] + dhr
            dc = dc + dh * o / nc
            dn = dn - dh * o * c / (nc * nc)       # n >= 1: no clamp
            do = dh * c / nc
            dfp = dc * cp + dn * np_
            dlf = dfp * fp
            dpi = (dc * z + dn) * ip
            # the final carry's m, back along the chain of maxima (a tie
            # splits as jnp.maximum's gradient does)
            above = jnp.where(a > it, 1.0, jnp.where(a == it, 0.5, 0.0))
            dlf = dlf + g * above
            dpi = dpi + g * (1.0 - above)
            dpre = jnp.concatenate([
                dc * ip * (1.0 - z * z), dpi,
                dlf * jax.nn.sigmoid(-pre[:, 2 * d:3 * d]),
                do * o * (1.0 - o)], axis=1)
            dpre = jnp.where(valid, dpre, 0.0)
            dpre_ref[r, pl.ds(t, 1), :] = dpre
            row_ref[r:r + 1, :] = dpre
            dh_prev = _rec_t(r_ref, r // per_group,
                             lambda lo, hi: row_ref[r:r + 1, lo:hi], nh)
            new = (dh_prev, dc * fp, dn * fp, g * above, dfp * fp)
            out.append(tuple(jnp.where(valid, nw, od)
                             for nw, od in zip(new, carry[r])))
        return tuple(out)

    init = tuple(tuple(carry_ref[r:r + 1, k * d:(k + 1) * d] for k in range(4))
                 + (jnp.zeros((1, d), jnp.float32),) for r in range(rows))
    final = jax.lax.fori_loop(0, bt, step, init)
    for r in range(rows):
        carry_ref[r:r + 1, :] = jnp.concatenate(final[r][:4], axis=1)

    @pl.when(j == nblocks - 1)
    def _finish():
        for r in range(rows):
            dh0, dc0, dn0, g0, dm0 = final[r]
            ds0_ref[r:r + 1, :] = jnp.concatenate([dh0, dc0, dn0, g0 + dm0],
                                                 axis=1)


_FOLD_VMEM = 64 * 2 ** 20    # the most a folded batch may keep in VMEM


def _fwd_vmem(rows, groups, bt, d, hd, x_bytes) -> int:
    """VMEM of the forward: R resident, and two buffers of each time
    block of xin, h, pre and the carry before each step."""
    return 16 * groups * d * hd + 2 * rows * bt * (4 * d * x_bytes + 36 * d)


def _bwd_vmem(rows, groups, bt, d, hd) -> int:
    """VMEM of the backward: R resident, and two buffers of each time
    block of dy, pre, the carry before each step and dpre."""
    return 16 * groups * d * hd + 2 * rows * bt * 52 * d


def _vmem_limit(nbytes: int) -> int:
    # room above the blocks for the loop's temporaries
    return int(min(100 * 2 ** 20, nbytes + 16 * 2 ** 20))


def _grouped(r_rec, bias):
    """R as [G, nh, hd, 4hd] and the bias as [G, 4d] f32: one group of
    weights, or one per group of rows."""
    r = r_rec.astype(jnp.float32)
    r = r[None] if r.ndim == 3 else r
    return r, bias.astype(jnp.float32).reshape(r.shape[0], -1)


def _folding(call, weights: dict, fits):
    """``call`` batched by folding the mapped axis into its arrays' rows,
    and into the groups of the weights at the positions ``weights`` maps
    to their rank (R 3, the bias 1): the kernel then runs the mapped
    entries' steps side by side, their dependency chains interleaved,
    where ``pallas_call``'s own rule would run them one after another as
    a grid axis. That rule stays where ``fits(rows, groups)`` says one
    device's share of the folded blocks would not fit VMEM."""
    f = jax.custom_batching.custom_vmap(call)

    @f.def_vmap
    def _rule(axis_size, in_batched, *args):
        args = [a if mapped else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, mapped in zip(args, in_batched)]
        folded = [a.reshape((-1,) + a.shape[-weights[i]:]) if i in weights
                  else a.reshape((-1,) + a.shape[2:])
                  for i, a in enumerate(args)]
        rows, groups = folded[0].shape[0], folded[min(weights)].shape[0]
        k = _mesh_split(folded[0], groups)[2]       # devices sharing them
        if fits(rows // k, groups // k):
            out = jax.tree.map(lambda o: o.reshape((axis_size, -1)
                                                   + o.shape[1:]),
                               call(*folded))
        else:
            out = jax.vmap(call)(*args)
        return out, jax.tree.map(lambda _: True, out)

    return f


def _mesh_split(x, groups: int):
    """(mesh, axes, k): the mesh ``x`` lies on, and its axes, in order,
    whose sizes multiply to a ``k`` that divides ``groups``."""
    mesh = jax.typeof(x).sharding.mesh
    axes, k = [], 1
    if mesh.size > 1:
        for name in mesh.axis_names:
            if groups % (k * mesh.shape[name]) == 0:
                axes.append(name)
                k *= mesh.shape[name]
    return mesh, tuple(axes), k


def _per_device(call, weights: dict):
    """``call`` on each device's share of its rows where the arrays lie on
    a mesh of several devices: XLA cannot partition a Mosaic kernel, so a
    ``shard_map`` hands each device whole rows with their groups of
    weights, over the mesh axes that divide the groups (the FL step's
    peers), or all rows where none does. ``weights`` as in
    ``_folding``."""
    def run(*args):
        i = min(weights)
        groups = 1 if args[i].ndim == weights[i] else args[i].shape[0]
        mesh, axes, _ = _mesh_split(args[0], groups)
        if mesh.size <= 1:
            return call(*args)
        spec = P(axes or None)
        out = jax.eval_shape(call, *args)
        return jax.shard_map(call, mesh=mesh, in_specs=(spec,) * len(args),
                             out_specs=jax.tree.map(lambda _: spec, out),
                             check_vma=False)(*args)
    return run


def slstm_fwd(xin, r_rec, bias, s0, interpret: bool = False):
    """xin [rows, S, 4d]; r_rec [nh, hd, 4hd] f32 (or [G, nh, hd, 4hd]:
    rows in G equal groups, each with its own weights); bias [4d] (or
    [G, 4d]); s0 [rows, 4d] f32, the carry (h, c, n, m) side by side.

    Returns (h [rows, S, d] f32, pre [rows, Sp, 4d] f32, carry before each
    step [rows, Sp, 4d] f32, final carry [rows, 4d] f32), Sp = S padded to
    the time block (the padded steps leave the carry as it was). Under
    ``vmap`` the mapped axis folds into the rows (``_folding``)."""
    d, hd = xin.shape[2] // 4, r_rec.shape[-2]
    fits = lambda rows, groups: _fwd_vmem(
        rows, groups, _time_block(xin.shape[1]), d, hd,
        xin.dtype.itemsize) <= _FOLD_VMEM
    weights = {1: 3, 2: 1}
    call = _per_device(functools.partial(_fwd_call, interpret=interpret),
                       weights)
    return _folding(call, weights, fits)(xin, r_rec, bias, s0)


def slstm_bwd(dy, pre, sprev, r_rec, seed, s: int, interpret: bool = False):
    """The reverse-time recurrence. dy [rows, S, d] f32, the cotangent of
    h; pre, sprev from ``slstm_fwd``; r_rec as there; seed [rows, 4d] the
    final carry's cotangent (dh, dc, dn, and the final m's net of its
    share in c and n). Returns (dpre [rows, Sp, 4d] f32, zero at padded
    steps; the initial carry's cotangent [rows, 4d]). Folds under ``vmap``
    as ``slstm_fwd`` does."""
    d, hd = pre.shape[2] // 4, r_rec.shape[-2]
    fits = lambda rows, groups: _bwd_vmem(
        rows, groups, _time_block(s), d, hd) <= _FOLD_VMEM
    weights = {3: 3}
    call = _per_device(functools.partial(_bwd_call, s=s, interpret=interpret),
                       weights)
    return _folding(call, weights, fits)(dy, pre, sprev, r_rec, seed)


def _fwd_call(xin, r_rec, bias, s0, *, interpret: bool):
    rows, s, d4 = xin.shape
    r_rec, bias = _grouped(r_rec, bias)
    grp, nh, hd = r_rec.shape[:3]
    d = d4 // 4
    bt = _time_block(s)
    nblocks = -(-s // bt)
    sp = nblocks * bt
    if sp != s:
        xin = jnp.pad(xin, ((0, 0), (0, sp - s), (0, 0)))
    kernel = functools.partial(_fwd_kernel, nh=nh, bt=bt, s=s)
    whole = lambda j: (0, 0)
    tblock = lambda j: (0, j, 0)
    h, pre, sprev, s_final = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((rows, bt, d4), tblock),
            pl.BlockSpec(r_rec.shape, lambda j: (0, 0, 0, 0),
                         pipeline_mode=pl.Buffered(1)),
            pl.BlockSpec((grp, d4), whole),
            pl.BlockSpec((rows, d4), whole),
        ],
        out_specs=[
            pl.BlockSpec((rows, bt, d), tblock),
            pl.BlockSpec((rows, bt, d4), tblock),
            pl.BlockSpec((rows, bt, d4), tblock),
            pl.BlockSpec((rows, d4), whole),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, sp, d), jnp.float32),
            jax.ShapeDtypeStruct((rows, sp, d4), jnp.float32),
            jax.ShapeDtypeStruct((rows, sp, d4), jnp.float32),
            jax.ShapeDtypeStruct((rows, d4), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((rows, d4), jnp.float32),
                        pltpu.VMEM((max(rows, 8), d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(_fwd_vmem(
                rows, grp, bt, d, hd, xin.dtype.itemsize))),
        interpret=interpret,
    )(xin, r_rec, bias, s0)
    return h[:, :s], pre, sprev, s_final


def _bwd_call(dy, pre, sprev, r_rec, seed, *, s: int, interpret: bool):
    rows, sp, d4 = pre.shape
    d = d4 // 4
    r_rec = _grouped(r_rec, jnp.zeros((0,)))[0]
    grp, nh, hd = r_rec.shape[:3]
    bt = _time_block(s)
    nblocks = sp // bt
    if dy.shape[1] != sp:
        dy = jnp.pad(dy, ((0, 0), (0, sp - dy.shape[1]), (0, 0)))
    kernel = functools.partial(_bwd_kernel, nh=nh, bt=bt, s=s,
                               nblocks=nblocks)
    whole = lambda j: (0, 0)
    rblock = lambda j: (0, nblocks - 1 - j, 0)
    return pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((rows, bt, d), rblock),
            pl.BlockSpec((rows, bt, d4), rblock),
            pl.BlockSpec((rows, bt, d4), rblock),
            pl.BlockSpec(r_rec.shape, lambda j: (0, 0, 0, 0),
                         pipeline_mode=pl.Buffered(1)),
            pl.BlockSpec((rows, d4), whole),
        ],
        out_specs=[
            pl.BlockSpec((rows, bt, d4), rblock),
            pl.BlockSpec((rows, d4), whole),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, sp, d4), jnp.float32),
            jax.ShapeDtypeStruct((rows, d4), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((rows, d4), jnp.float32),
                        pltpu.VMEM((max(rows, 8), d4), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(_bwd_vmem(rows, grp, bt, d, hd))),
        interpret=interpret,
    )(dy, pre, sprev, r_rec, seed)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def slstm_scan(xin, r_rec, bias, s0, interpret: bool = False):
    """The sLSTM over a sequence: (h [b, S, d] f32, final carry [b, 4d]),
    differentiable in every argument; see ``slstm_fwd``."""
    h, _, _, s_final = slstm_fwd(xin, r_rec, bias, s0, interpret)
    return h, s_final


def _scan_fwd(xin, r_rec, bias, s0, interpret):
    h, pre, sprev, s_final = slstm_fwd(xin, r_rec, bias, s0, interpret)
    return (h, s_final), (pre, sprev, r_rec, s_final,
                          jnp.zeros((0,), xin.dtype))


def _scan_bwd(interpret, res, cts):
    pre, sprev, r_rec, s_final, proto = res
    dy, ds_final = cts
    b, s, d = dy.shape
    sp = pre.shape[1]
    nh, hd = r_rec.shape[0], r_rec.shape[1]
    dh, dc, dn, dm = jnp.split(ds_final, 4, axis=-1)
    c, n = s_final[:, d:2 * d], s_final[:, 2 * d:3 * d]
    seed = jnp.concatenate([dh, dc, dn, dm - c * dc - n * dn], axis=-1)
    dpre, ds0 = slstm_bwd(dy, pre, sprev, r_rec, seed, s, interpret)
    hprev = sprev[:, :, :d].reshape(b, sp, nh, hd)
    d_rec = jnp.einsum("bsnk,bsnj->nkj", hprev,
                       dpre.reshape(b, sp, nh, 4 * hd),
                       precision=jax.lax.Precision.HIGHEST)
    d_bias = jnp.sum(dpre, axis=(0, 1))
    dxin = dpre[:, :s].astype(proto.dtype)
    return dxin, d_rec.astype(r_rec.dtype), d_bias, ds0


slstm_scan.defvjp(_scan_fwd, _scan_bwd)
