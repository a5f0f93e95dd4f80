"""Serving tier: paged KV kernel parity, prefill->decode handoff,
continuous-batching scheduler invariants, checkpoint hot-swap.

Parity tests run float32 + xla attention so the paged pool path and the
dense cache path are structurally identical einsums — the ISSUE-8 gate
is logit agreement <= 1e-5 (observed: bit-exact on CPU).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.core.fl_device import (make_paged_serve_step, make_prefill_step,
                                  make_serve_step)
from repro.kernels import ref
from repro.kernels.paged_attention import (gather_dense_decode,
                                           paged_decode_attention_fwd)
from repro.models.model import Model
from repro.serve import (BlockAllocator, DecodeServer, ServeConfig,
                         gather_session_cache, run_sequential,
                         serving_params_from_checkpoint, session_table,
                         write_prefill_to_pages)

MAX_NEW = 6


def _dense_model():
    cfg = get_smoke_config("starcoder2-3b")
    cfg = dataclasses.replace(cfg, attn_impl="xla", dtype="float32")
    return Model(cfg)


@pytest.fixture(scope="module")
def dense():
    model = _dense_model()
    return model, model.init(jax.random.PRNGKey(0))


def _prompts(model, n, lo=1, hi=12, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, model.cfg.vocab_size,
                         rng.integers(lo, hi + 1)).tolist()
            for _ in range(n)]


# ---------------------------------------------------------------------------
# Paged kernel parity
# ---------------------------------------------------------------------------

def _paged_inputs(seed=0, b=3, nblk=4, bs=8, kvh=2, g=4, d=16):
    rng = np.random.default_rng(seed)
    nb = 1 + b * nblk
    q = jnp.asarray(rng.normal(size=(b, kvh * g, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, kvh, bs, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, kvh, bs, d)), jnp.float32)
    bt = jnp.asarray(rng.permutation(np.arange(1, nb))
                     .reshape(b, nblk), jnp.int32)
    lens = jnp.asarray([1, bs * nblk, bs * 2 + 3][:b], jnp.int32)
    return q, kp, vp, bt, lens


def test_paged_kernel_interpret_matches_ref():
    q, kp, vp, bt, lens = _paged_inputs()
    out = paged_decode_attention_fwd(q, kp, vp, bt, lens, interpret=True)
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_gather_dense_fallback_matches_ref():
    q, kp, vp, bt, lens = _paged_inputs(seed=1)
    out = gather_dense_decode(q, kp, vp, bt, lens)
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_paged_decode_logits_match_dense(dense):
    """Full-model parity: paged pool vs dense cache, greedy chains."""
    model, params = dense
    b, bs, nblk = 2, 4, 4
    cache = model.init_cache(b, max_len=bs * nblk)
    pages = model.init_paged_cache(num_blocks=1 + b * nblk, block_size=bs)
    bt = jnp.asarray([[1 + i * nblk + j for j in range(nblk)]
                      for i in range(b)], jnp.int32)
    pos = jnp.zeros((b,), jnp.int32)
    paged = jax.jit(make_paged_serve_step(model))
    serve = jax.jit(make_serve_step(model))
    tok = jnp.asarray([3, 7], jnp.int32)
    for _ in range(bs * nblk):
        ntok, logits, pages = paged(params, pages, bt, pos, tok)
        logits_d, cache = model.decode_step(params, cache, tok)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(logits_d), atol=1e-5)
        tok, pos = ntok, pos + 1


def test_write_prefill_roundtrip(dense):
    """Scattered prefill KV gathers back identically (incl. a ragged
    last block)."""
    model, params = dense
    s, bs = 11, 4                                    # 3 blocks, ragged
    toks = jnp.asarray(np.arange(2 * s).reshape(2, s) % 50, jnp.int32)
    _, _, cache = model.forward(params, toks, collect_cache=True)
    pages = model.init_paged_cache(num_blocks=7, block_size=bs)
    bt = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    pages = write_prefill_to_pages(pages, cache["k"], cache["v"], bt)
    got = gather_session_cache(pages, [4, 5, 6])
    np.testing.assert_array_equal(np.asarray(got["k"][:, 0, :s]),
                                  np.asarray(cache["k"][:, 1]))


# ---------------------------------------------------------------------------
# Prefill -> decode handoff (satellite: no prompt replay)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["starcoder2-3b", "xlstm-350m",
                                  "zamba2-2.7b", "moonshot-v1-16b-a3b"])
def test_prefill_handoff_matches_replay(arch):
    """make_prefill_step(max_len=...) returns a decode-ready cache whose
    continuation equals token-by-token replay from scratch."""
    cfg = get_smoke_config(arch)
    cfg = dataclasses.replace(cfg, attn_impl="xla", dtype="float32")
    if cfg.family == "moe":
        # capacity drops differ between a 12-token prefill and 1-token
        # decode steps; lift the cap so routing is drop-free both ways
        # (the established idiom for MoE exactness tests)
        cfg = dataclasses.replace(cfg, moe_capacity_factor=8.0)
    model = Model(cfg)
    rng = np.random.default_rng(3)
    params = model.init(jax.random.PRNGKey(3))
    S, MAXLEN = 6, 10
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, MAXLEN)),
                       jnp.int32)

    cache = model.init_cache(2, max_len=MAXLEN)
    replay = []
    for i in range(MAXLEN):
        lg, cache = model.decode_step(params, cache, toks[:, i])
        replay.append(lg)

    prefill = jax.jit(make_prefill_step(model, max_len=MAXLEN))
    lg, dcache = prefill(params, {"tokens": toks[:, :S]})
    outs = [lg]
    for i in range(S, MAXLEN):
        lg, dcache = model.decode_step(params, dcache, toks[:, i])
        outs.append(lg)
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                               np.asarray(jnp.stack(replay[S - 1:], 1)),
                               atol=2e-4, rtol=2e-4)


def test_hybrid_handoff_ring_layout():
    """Long-prompt hybrid handoff (prompt > window): the converted ring
    holds position p at slot p % w with bit-exact K/V, conv and ssm
    states (forward's full-causal vs decode's windowed attention is a
    separate, pre-existing semantic gap — layout is what the handoff
    owns)."""
    cfg = get_smoke_config("zamba2-2.7b")
    cfg = dataclasses.replace(cfg, attn_impl="xla", dtype="float32",
                              shared_attn_window=4)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(np.arange(12).reshape(2, 6), jnp.int32)
    S, MAXLEN = 6, 12
    _, raw = jax.jit(make_prefill_step(model))(params, {"tokens": toks})
    _, conv = jax.jit(make_prefill_step(model, max_len=MAXLEN))(
        params, {"tokens": toks})
    w_f, w_d = raw["attn_k"].shape[2], conv["attn_k"].shape[2]
    assert w_d == 4
    for j in range(w_f):                  # raw index j holds pos S-w_f+j
        slot = (S - w_f + j) % w_d
        np.testing.assert_array_equal(
            np.asarray(conv["attn_k"][:, :, slot]),
            np.asarray(raw["attn_k"][:, :, j]))
    np.testing.assert_array_equal(np.asarray(conv["conv"]),
                                  np.asarray(raw["conv"]))
    np.testing.assert_array_equal(np.asarray(conv["ssm"]),
                                  np.asarray(raw["ssm"]))


# ---------------------------------------------------------------------------
# Allocator / scheduler invariants
# ---------------------------------------------------------------------------

def test_block_allocator_invariants():
    al = BlockAllocator(6)
    assert al.free_blocks == 5                     # block 0 reserved
    got = al.alloc(3)
    assert 0 not in got and len(set(got)) == 3
    with pytest.raises(RuntimeError):
        al.alloc(3)                                # only 2 left
    al.free(got)
    with pytest.raises(RuntimeError):
        al.free([got[0]])                          # double free
    assert al.free_blocks == 5
    assert session_table([1, 2], 4) == [1, 2, 0, 0]


def test_engine_matches_sequential_mixed_lengths(dense):
    """Heterogeneous-length continuous batch produces the exact greedy
    tokens of the one-at-a-time baseline."""
    model, params = dense
    scfg = ServeConfig(max_batch=3, block_size=4, num_blocks=40,
                       pad_len=12, max_new=MAX_NEW)
    prompts = _prompts(model, 7)
    srv = DecodeServer(model, params, scfg)
    for p in prompts:
        srv.enqueue(p)
    srv.run()
    srv.assert_quiescent()
    seq = run_sequential(model, params, prompts, max_new=MAX_NEW,
                         pad_len=12)
    eng = {s.sid: s.generated for s in srv.finished}
    assert all(eng[s.sid] == s.generated for s in seq)


def test_no_block_leak_under_pressure(dense):
    """A pool far smaller than the offered load still drains every
    session and reclaims every block."""
    model, params = dense
    scfg = ServeConfig(max_batch=4, block_size=4, num_blocks=11,
                       pad_len=12, max_new=MAX_NEW)
    srv = DecodeServer(model, params, scfg)
    for p in _prompts(model, 8, seed=1):
        srv.enqueue(p)
    peak_free = srv.alloc.free_blocks
    srv.run(max_steps=500)
    assert len(srv.finished) == 8
    srv.assert_quiescent()
    assert srv.alloc.free_blocks == peak_free


def test_fifo_head_of_line(dense):
    """Admission is FIFO: while the (large) queue head doesn't fit, a
    small later arrival must not overtake it."""
    model, params = dense
    scfg = ServeConfig(max_batch=3, block_size=4, num_blocks=10,
                       pad_len=12, max_new=MAX_NEW)
    srv = DecodeServer(model, params, scfg)
    big_a = srv.enqueue([1] * 12)     # needs ceil(18/4)=5 of 9 blocks
    big_b = srv.enqueue([2] * 12)     # head-of-line once A runs
    small = srv.enqueue([3])          # would fit beside A — must wait
    srv.step()
    assert big_a.state == "running"
    assert big_b.state == "queued" and small.state == "queued"
    srv.run()
    srv.assert_quiescent()
    assert [s.sid for s in srv.finished] == [big_a.sid, big_b.sid,
                                             small.sid]


def test_enqueue_rejects_impossible(dense):
    model, params = dense
    scfg = ServeConfig(max_batch=2, block_size=4, num_blocks=4,
                       pad_len=12, max_new=MAX_NEW)
    srv = DecodeServer(model, params, scfg)
    with pytest.raises(ValueError):
        srv.enqueue([1] * 13)                      # > pad_len
    with pytest.raises(ValueError):
        srv.enqueue([1] * 12)                      # footprint > pool
    srv.assert_quiescent()


def test_recurrent_family_rejected():
    cfg = get_smoke_config("xlstm-350m")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        DecodeServer(model, params, ServeConfig())
    with pytest.raises(ValueError):
        run_sequential(model, params, [[1, 2]], max_new=2, pad_len=4)


# ---------------------------------------------------------------------------
# Checkpoint hot-swap
# ---------------------------------------------------------------------------

def test_identity_hot_swap_is_deterministic(dense):
    """Swapping identical weights mid-decode changes nothing and drops
    nothing."""
    model, params = dense
    scfg = ServeConfig(max_batch=3, block_size=4, num_blocks=40,
                       pad_len=12, max_new=MAX_NEW)
    prompts = _prompts(model, 6, seed=2)

    def drain(swap):
        srv = DecodeServer(model, params, scfg)
        for p in prompts:
            srv.enqueue(p)
        if swap:
            for _ in range(3):
                srv.step()
            assert srv.running                     # mid-decode
            srv.swap_params(jax.tree.map(lambda x: x + 0, params),
                            tag="identity")
        srv.run()
        srv.assert_quiescent()
        return srv

    base, swapped = drain(False), drain(True)
    assert len(swapped.finished) == len(prompts)   # zero dropped
    assert {s.sid: s.generated for s in base.finished} == \
           {s.sid: s.generated for s in swapped.finished}
    (entry,) = swapped.swap_log
    assert entry["tag"] == "identity" and entry["in_flight"]


def test_serving_params_peer_mean(dense):
    """FL checkpoints carry a peer axis; serving weights are its mean."""
    model, params = dense
    stacked = jax.tree.map(
        lambda x: jnp.stack([x, 3 * x]), params)   # mean = 2x
    got = serving_params_from_checkpoint(
        {"params": stacked, "momentum": stacked}, params)
    want = jax.tree.map(lambda x: 2 * x, params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6)
    # raw (unstacked) params pass through unchanged
    same = serving_params_from_checkpoint(params, params)
    for a, b in zip(jax.tree.leaves(same), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_hot_swap_mid_run(dense, tmp_path):
    """The engine picks up a newer checkpoint mid-drain, switches its
    token stream to the new weights, and finishes every session."""
    from repro.checkpoint.checkpointer import Checkpointer
    model, params = dense
    other = model.init(jax.random.PRNGKey(99))
    scfg = ServeConfig(max_batch=2, block_size=4, num_blocks=40,
                       pad_len=12, max_new=MAX_NEW)
    prompts = _prompts(model, 4, seed=4)

    ckpt = Checkpointer(str(tmp_path), keep=2)
    ckpt.save(1, {"params": jax.tree.map(
        lambda x: jnp.stack([x, x]), params)}, metadata={"n_peers": 2})

    srv = DecodeServer(model, params, scfg)
    srv.attach_checkpointer(ckpt, params, every=1)
    for p in prompts:
        srv.enqueue(p)
    for _ in range(2):
        srv.step()
    assert not srv.swap_log                        # step 1 already seen
    ckpt.save(2, {"params": jax.tree.map(
        lambda x: jnp.stack([x, x]), other)}, metadata={"n_peers": 2})
    srv.run()
    srv.assert_quiescent()
    assert len(srv.finished) == len(prompts)
    (entry,) = srv.swap_log
    assert entry["tag"] == "ckpt:2"
    # the installed weights are checkpoint 2's peer mean (== other)
    for a, b in zip(jax.tree.leaves(srv.params), jax.tree.leaves(other)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6)
