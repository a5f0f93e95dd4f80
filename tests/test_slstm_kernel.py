"""The fused sLSTM recurrence (``kernels/slstm_scan.py``, interpret mode
here) against the per-step scan it replaced (``ref.slstm_scan_ref``):
h at every step and the final carry, and the gradients of a scalar loss
with respect to the block's input, its weights and the initial carry."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.kernels import ops, ref
from repro.kernels import slstm_scan
from repro.kernels.slstm_scan import _time_block
from repro.models import ssm

NH, HD = 2, 32
D = NH * HD
M_INIT = -1e30          # the stabilizer of slstm_block's fresh carry
# the block's input and w_in are bf16, so their gradients go through a
# bf16 cotangent of xin; the rest is f32 end to end
TOL = {"x": 2e-3, "w_in": 2e-3, "r_rec": 2e-5, "bias": 2e-5, "s0": 2e-5}


def _weights(seed, b, s, carry):
    rng = np.random.default_rng(seed)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    w = {"x": jnp.asarray(rng.normal(size=(b, s, D)), jnp.bfloat16),
         "w_in": jnp.asarray(rng.normal(size=(D, 4 * D)) / np.sqrt(D),
                             jnp.bfloat16),
         "r_rec": f32(rng.normal(size=(NH, HD, 4 * HD)) / np.sqrt(HD)),
         "bias": f32(rng.normal(size=(4 * D,)) * 0.1)}
    if carry:      # a carry as a previous segment leaves it: n >= 1
        w["s0"] = jnp.concatenate([
            f32(np.tanh(rng.normal(size=(b, D)))),
            f32(rng.normal(size=(b, D))),
            f32(1.0 + rng.random(size=(b, D))),
            f32(rng.normal(size=(b, D)))], axis=-1)
    else:
        w["s0"] = jnp.concatenate([jnp.zeros((b, 3 * D), jnp.float32),
                                   jnp.full((b, D), M_INIT)], axis=-1)
    probe = {"h": f32(rng.normal(size=(b, s, D))),
             "carry": f32(rng.normal(size=(b, 4 * D)))}
    return w, probe


def _run(scan, w):
    return scan(w["x"] @ w["w_in"], w["r_rec"], w["bias"], w["s0"])


def _loss(scan, probe):
    """A scalar of every output: each step's h and the final carry."""
    def loss(w):
        h, s_final = _run(scan, w)
        return jnp.sum(h * probe["h"]) + jnp.sum(s_final * probe["carry"])
    return loss


def _close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = max(float(np.max(np.abs(want))), 1e-6)
    assert np.max(np.abs(got - want)) <= tol * scale


CASES = [
    # (b, S, initial carry)
    (1, 40, False),
    (2, 37, True),      # S pads to a 48-step block
    (1, 150, True),     # two 128-step blocks, the second padded
    (2, 1, False),
    (1, 1, True),
]


@pytest.mark.parametrize("b,s,carry", CASES)
def test_forward_matches_the_scan(b, s, carry):
    w, _ = _weights(s * 10 + b, b, s, carry)
    h, s_final = _run(ops.slstm_scan, w)
    h_ref, s_ref = _run(ref.slstm_scan_ref, w)
    assert h.shape == (b, s, D) and h.dtype == jnp.float32
    np.testing.assert_allclose(h, h_ref, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(s_final, s_ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("b,s,carry", CASES)
def test_gradients_match_the_scans_autodiff(b, s, carry):
    w, probe = _weights(s * 10 + b + 1, b, s, carry)
    got = jax.grad(_loss(ops.slstm_scan, probe))(w)
    want = jax.grad(_loss(ref.slstm_scan_ref, probe))(w)
    for k in w:
        assert got[k].dtype == w[k].dtype, k
        _close(got[k], want[k], TOL[k])


def test_time_block_covers_any_length():
    for s in (1, 15, 16, 17, 128, 129, 2048):
        bt = _time_block(s)
        assert bt % 16 == 0 and bt <= 128 and (bt >= s or bt == 128)


@pytest.mark.parametrize("fold", [True, False])
@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_under_vmap_over_peers_with_their_own_weights(what, fold,
                                                      monkeypatch):
    """Folded into the kernel's rows, or, where that would not fit VMEM,
    one peer after another."""
    if not fold:
        monkeypatch.setattr(slstm_scan, "_FOLD_VMEM", 0)
    rows, call = set(), slstm_scan._fwd_call

    def seen(xin, *args, **kw):
        rows.add(xin.shape[0])
        return call(xin, *args, **kw)

    monkeypatch.setattr(slstm_scan, "_fwd_call", seen)
    jax.clear_caches()      # the batched trace is cached by shape
    pairs = [_weights(seed, 1, 24, carry=seed == 8) for seed in (7, 8)]
    w = jax.tree.map(lambda *a: jnp.stack(a), *[p[0] for p in pairs])
    probe = jax.tree.map(lambda *a: jnp.stack(a), *[p[1] for p in pairs])
    if what == "forward":
        got = jax.vmap(lambda w: _run(ops.slstm_scan, w))(w)
        want = jax.vmap(lambda w: _run(ref.slstm_scan_ref, w))(w)
        for a, b_ in zip(got, want):
            np.testing.assert_allclose(a, b_, atol=1e-5, rtol=1e-5)
        # (the jit round the op traces it once unbatched as well)
        assert max(rows) == (2 if fold else 1)
        return
    grad = lambda scan: jax.vmap(
        lambda w, p: jax.grad(_loss(scan, p))(w))(w, probe)
    got, want = grad(ops.slstm_scan), grad(ref.slstm_scan_ref)
    for k in w:
        for peer in range(2):
            _close(got[k][peer], want[k][peer], TOL[k])


def test_block_hands_prefill_to_the_decode_cell():
    """slstm_block over a prompt leaves the carry that slstm_decode_step,
    one token at a time from the same carry, reaches; and its outputs
    match the decode steps'."""
    cfg = ModelConfig(name="t", family="ssm", num_layers=2, d_model=D,
                      num_heads=NH, num_kv_heads=NH, head_dim=HD, d_ff=0,
                      vocab_size=16, slstm_every=2, dtype="float32")
    params = ssm.slstm_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, D), jnp.float32)
    y, carry = ssm.slstm_block(params, x, cfg)
    zeros = jnp.zeros((2, D), jnp.float32)
    step_carry = (zeros, zeros, zeros, jnp.full((2, D), M_INIT))
    ys = []
    for t in range(x.shape[1]):
        yt, step_carry = ssm.slstm_decode_step(params, x[:, t:t + 1], cfg,
                                               step_carry)
        ys.append(yt)
    np.testing.assert_allclose(y, jnp.concatenate(ys, axis=1), atol=2e-5)
    for a, b_ in zip(carry, step_carry):
        np.testing.assert_allclose(a, b_, atol=2e-5, rtol=1e-5)
