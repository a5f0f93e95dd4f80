"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _arr(shape, dtype, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale).astype(dtype)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,kvh,d", [
    (2, 128, 8, 2, 32),   # GQA 4:1
    (1, 256, 4, 4, 64),   # MHA
    (2, 64, 8, 1, 16),    # MQA
    (1, 96, 6, 2, 32),    # non-power seq
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(b, s, h, kvh, d, dtype, causal):
    q, k, v = (_arr((b, s, h, d), dtype),
               _arr((b, s, kvh, d), dtype), _arr((b, s, kvh, d), dtype))
    out = ops.flash_attention(q, k, v, causal=causal)
    expect = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        atol=_tol(dtype) * 4, rtol=_tol(dtype))


def test_flash_attention_cross_lengths():
    q = _arr((1, 64, 4, 32), jnp.float32)
    k = _arr((1, 128, 4, 32), jnp.float32)
    v = _arr((1, 128, 4, 32), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=False)
    expect = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(out, expect, atol=1e-4)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,kvh,d", [
    (2, 256, 8, 2, 32), (1, 512, 4, 4, 64), (3, 128, 6, 1, 16),
])
def test_decode_attention_sweep(b, s, h, kvh, d, dtype):
    q = _arr((b, h, d), dtype)
    kc, vc = _arr((b, s, kvh, d), dtype), _arr((b, s, kvh, d), dtype)
    lens = jnp.asarray(RNG.integers(1, s + 1, size=(b,)), jnp.int32)
    out = ops.decode_attention(q, kc, vc, lens)
    expect = ref.decode_attention_ref(q, kc, vc, lens)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        atol=_tol(dtype) * 4, rtol=_tol(dtype))


def test_decode_attention_length_one():
    q = _arr((2, 4, 16), jnp.float32)
    kc, vc = _arr((2, 64, 2, 16), jnp.float32), _arr((2, 64, 2, 16),
                                                     jnp.float32)
    lens = jnp.asarray([1, 64], jnp.int32)
    out = ops.decode_attention(q, kc, vc, lens)
    expect = ref.decode_attention_ref(q, kc, vc, lens)
    np.testing.assert_allclose(out, expect, atol=1e-4)


@pytest.mark.parametrize("s,block_k", [(98, 64), (1030, 512), (7, 8),
                                       (513, 512)])
def test_decode_attention_odd_lengths(s, block_k):
    """Regression (ISSUE 8 satellite): non-power-of-two caches used to
    shrink the K block via ``while s % bk: bk //= 2`` — degrading to
    tiny tiles. The fixed path pads the cache view to a block multiple
    and keeps full tiles; results must still match the oracle exactly,
    including a length right at the cache edge."""
    from repro.kernels.decode_attention import decode_attention_fwd
    b, h, kvh, d = 2, 4, 2, 16
    q = _arr((b, h, d), jnp.float32)
    kc, vc = _arr((b, s, kvh, d), jnp.float32), _arr((b, s, kvh, d),
                                                     jnp.float32)
    lens = jnp.asarray([s, max(1, s - 3)], jnp.int32)
    out = decode_attention_fwd(q, kc, vc, lens, block_k=block_k,
                               interpret=True)
    expect = ref.decode_attention_ref(q, kc, vc, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# paged decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,nblk,bs,h,kvh,d", [
    (2, 4, 16, 8, 2, 32), (1, 8, 8, 4, 4, 16), (3, 2, 32, 6, 1, 16),
])
def test_paged_decode_attention_sweep(b, nblk, bs, h, kvh, d, dtype):
    nb = 1 + b * nblk
    q = _arr((b, h, d), dtype)
    kp, vp = _arr((nb, kvh, bs, d), dtype), _arr((nb, kvh, bs, d), dtype)
    bt = jnp.asarray(RNG.permutation(np.arange(1, nb)).reshape(b, nblk),
                     jnp.int32)
    lens = jnp.asarray(RNG.integers(1, nblk * bs + 1, size=(b,)), jnp.int32)
    out = ops.paged_decode_attention(q, kp, vp, bt, lens)
    expect = ref.paged_decode_attention_ref(q, kp, vp, bt, lens)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        atol=_tol(dtype) * 4, rtol=_tol(dtype))


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,nh,s,dk,dv", [
    (2, 3, 128, 16, 32), (1, 2, 64, 8, 8), (2, 1, 96, 32, 16),
])
def test_ssd_scan_sweep(b, nh, s, dk, dv, dtype):
    q = _arr((b, nh, s, dk), dtype)
    k = _arr((b, nh, s, dk), dtype, scale=0.3)
    v = _arr((b, nh, s, dv), dtype)
    a = -jnp.asarray(RNG.uniform(0.01, 0.5, size=(b, nh, s)), jnp.float32)
    h0 = _arr((b, nh, dk, dv), jnp.float32, scale=0.1)
    y, hf = ops.ssd_scan(q, k, v, a, h0)
    yr, hfr = ref.ssd_scan_ref(q, k, v, a, h0)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32),
        atol=_tol(dtype) * 8, rtol=_tol(dtype) * 4)
    np.testing.assert_allclose(hf, hfr, atol=_tol(dtype) * 8,
                               rtol=_tol(dtype) * 4)


def test_ssd_scan_matches_training_reference():
    """The Pallas kernel, the chunked jnp path, and the sequential oracle
    agree (train-path consistency)."""
    from repro.models.ssm import chunked_linear_scan
    q = _arr((1, 2, 64, 8), jnp.float32)
    k = _arr((1, 2, 64, 8), jnp.float32, scale=0.3)
    v = _arr((1, 2, 64, 16), jnp.float32)
    a = -jnp.asarray(RNG.uniform(0.01, 0.3, size=(1, 2, 64)), jnp.float32)
    h0 = jnp.zeros((1, 2, 8, 16), jnp.float32)
    y1, h1 = ops.ssd_scan(q, k, v, a, h0)
    y2, h2 = chunked_linear_scan(q, k, v, a, h0, chunk=16)
    y3, h3 = ref.ssd_scan_ref(q, k, v, a, h0)
    np.testing.assert_allclose(y1, y3, atol=1e-4)
    np.testing.assert_allclose(y2, y3, atol=1e-4)
    np.testing.assert_allclose(h1, h3, atol=1e-4)
    np.testing.assert_allclose(h2, h3, atol=1e-4)


# ---------------------------------------------------------------------------
# group mean
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("g,m,d", [(4, 5, 512), (8, 3, 96), (2, 2, 2048)])
def test_group_mean_sweep(g, m, d, dtype):
    x = _arr((g, m, d), dtype)
    mask = jnp.asarray(RNG.random((g, m)) < 0.7, jnp.float32)
    out = ops.group_mean(x, mask)
    expect = ref.group_mean_ref(x, mask)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_group_mean_empty_group_keeps_values():
    x = _arr((2, 3, 64), jnp.float32)
    mask = jnp.zeros((2, 3)).at[1].set(1.0)
    out = ops.group_mean(x, mask)
    np.testing.assert_allclose(out[0], x[0], atol=1e-6)


@given(st.integers(1, 4), st.integers(2, 5), st.integers(1, 6),
       st.integers(0, 10 ** 6))
@settings(max_examples=20, deadline=None)
def test_group_mean_property(g, m, dpow, seed):
    """Hypothesis: kernel == oracle for arbitrary shapes/masks."""
    d = 2 ** dpow
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(g, m, d)), jnp.float32)
    mask = jnp.asarray(r.integers(0, 2, size=(g, m)), jnp.float32)
    out = ops.group_mean(x, mask)
    expect = ref.group_mean_ref(x, mask)
    np.testing.assert_allclose(out, expect, atol=1e-5)


# ---------------------------------------------------------------------------
# the jnp flash custom-vjp (training attention) vs oracle incl. grads
# ---------------------------------------------------------------------------

def test_flash_custom_vjp_grads():
    from repro.models.attention_flash import flash_attention
    b, s, h, kvh, d = 1, 64, 4, 2, 16
    q, k, v = (_arr((b, s, h, d), jnp.float32),
               _arr((b, s, kvh, d), jnp.float32),
               _arr((b, s, kvh, d), jnp.float32))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 16, 32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(ref.flash_attention_ref(q, k, v, True)
                       .astype(jnp.float32) ** 2)

    gf = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(a, b_, atol=5e-4, rtol=1e-3)
