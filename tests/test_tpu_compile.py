"""Compile for a described TPU v5e, with no chip attached.

The TPU compiler is installed here and compiles for a chip that is
described rather than present: it refuses what interpret mode accepts —
blocks that break the (8, 128) tiling rule, kernels that overrun scoped
VMEM, programs that do not fit HBM. Nothing runs, so these tests say
nothing about results or times; they guard that every Pallas kernel at
the published widths, the paged serve step and the train step still
compile for one v5e chip.

The topology is described only inside a fixture: libtpu may be loaded
by one process at a time, and a description made at import would make
the test workers collect different tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.core.aggregation import build_pipeline
from repro.core.fl_device import fl_state_shape
from repro.core.moshpit import plan_grid
from repro.kernels.decode_attention import decode_attention_fwd
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.group_mean import group_mean_fwd
from repro.kernels.paged_attention import paged_decode_attention_fwd
from repro.kernels.slstm_scan import slstm_bwd, slstm_fwd
from repro.kernels.ssd_scan import ssd_scan_fwd
from repro.launch.train import jit_train_step
from repro.models.model import Model
from repro.serve import ServeConfig

HBM_BYTES = int(15.75 * 2 ** 30)     # what XLA lets one v5e program use

# starcoder2-3b attention: 24 query heads, 2 KV heads, head_dim 128
H, KVH, D = 24, 2, 128
# xlstm-350m mLSTM: 4 heads over an inner width of 2048 -> 512 per head
NH, HD = 4, 512
# xlstm-350m sLSTM as the FL step runs it: 2 peers (vmapped), batch 1,
# seq 2048, d 1024 in 4 heads of 256
P, SEQ, SD = 2, 2048, 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no libtpu, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _with_sharding(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernel_cases(s):
    scfg = ServeConfig(max_batch=4, pad_len=128, max_new=16)
    nb, bs, tw = 1 + 4 * scfg.table_width, scfg.block_size, \
        scfg.table_width
    return {
        "paged_decode": (
            lambda q, k, v, bt, ln: paged_decode_attention_fwd(
                q, k, v, bt, ln),
            [s((4, H, D)), s((nb, KVH, bs, D)), s((nb, KVH, bs, D)),
             s((4, tw), jnp.int32), s((4,), jnp.int32)]),
        "decode": (
            decode_attention_fwd,
            [s((4, H, D)), s((4, 4096, KVH, D)), s((4, 4096, KVH, D)),
             s((4,), jnp.int32)]),
        "flash": (
            lambda q, k, v: flash_attention_fwd(q, k, v, True),
            [s((1, 2048, H, D)), s((1, 2048, KVH, D)),
             s((1, 2048, KVH, D))]),
        "ssd_scan": (
            ssd_scan_fwd,
            [s((1, NH, 2048, HD)), s((1, NH, 2048, HD)),
             s((1, NH, 2048, HD + 1)), s((1, NH, 2048), jnp.float32),
             s((1, NH, HD, HD + 1), jnp.float32)]),
        "slstm_fwd": (
            jax.vmap(lambda x, r, b, s0: slstm_fwd(x, r, b, s0)),
            [s((P, 1, SEQ, 4 * SD)), s((P, 4, SD // 4, SD), jnp.float32),
             s((P, 4 * SD), jnp.float32), s((P, 1, 4 * SD), jnp.float32)]),
        "slstm_bwd": (
            jax.vmap(lambda dy, pre, sp, r, g: slstm_bwd(dy, pre, sp, r, g,
                                                         SEQ)),
            [s((P, 1, SEQ, SD), jnp.float32),
             s((P, 1, SEQ, 4 * SD), jnp.float32),
             s((P, 1, SEQ, 4 * SD), jnp.float32),
             s((P, 4, SD // 4, SD), jnp.float32),
             s((P, 1, 4 * SD), jnp.float32)]),
        # the device MAR mean over one xlstm-350m embedding leaf
        "group_mean": (
            group_mean_fwd,
            [s((2, 2, 50304 * 1024), jnp.float32),
             s((2, 2), jnp.float32)]),
    }


@pytest.mark.parametrize("name", ["paged_decode", "decode", "flash",
                                  "ssd_scan", "group_mean", "slstm_fwd",
                                  "slstm_bwd"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_cases(lambda *a: _spec(one_chip, *a))[name]
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_serve_step_compiles_for_v5e(one_chip, monkeypatch):
    """starcoder2-3b's paged decode step, as ``DecodeServer`` jits it.
    ``ops`` picks the kernel by the attached backend, which is the CPU
    here; the test points it at the kernel for the described chip."""
    from repro.core.fl_device import make_paged_serve_step
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    model = Model(get_config("starcoder2-3b"))
    scfg = ServeConfig(max_batch=4, pad_len=128, max_new=16)
    params = _with_sharding(model.init_shape(), one_chip)
    pages = _with_sharding(jax.eval_shape(
        lambda: model.init_paged_cache(1 + 4 * scfg.table_width,
                                       scfg.block_size)), one_chip)
    mb = scfg.max_batch
    compiled = _compile(
        make_paged_serve_step(model), params, pages,
        _spec(one_chip, (mb, scfg.table_width), jnp.int32),
        _spec(one_chip, (mb,), jnp.int32), _spec(one_chip, (mb,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_xlstm_two_peer_train_step_fits_one_v5e(one_chip, monkeypatch):
    """The jitted train step of ``launch/train.py`` (state donated) for
    xlstm-350m at its published config: 2 peers, 1 local step, batch 1,
    seq 2048 must fit one chip's HBM, with the sLSTM kernels that the
    chip runs (``ops`` is pointed at them, as in the serve step's test)."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    model = Model(get_config("xlstm-350m"))
    n, seq = 2, 2048
    grid = plan_grid(n)
    pipeline = build_pipeline("mar", grid, backend="device")
    state = _with_sharding(fl_state_shape(model, n), one_chip)
    tokens = _spec(one_chip, (n, 1, 1, 1, seq), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}
    step = jit_train_step(model, grid, 0.1, pipeline)
    compiled = step.lower(state, batch).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes
             - mem.alias_size_in_bytes)
    assert mem.alias_size_in_bytes > 0, "the state was not donated"
    assert total <= HBM_BYTES, f"{total / 2 ** 30:.2f} GiB"


def test_xlstm_four_peer_step_runs_the_kernels_per_chip(topo, monkeypatch):
    """The MAR step of ``chip_smoke.py --four-chips``: xlstm-350m, 4 peers
    one per chip of a (data=4, model=1) mesh. XLA cannot partition a
    Mosaic kernel, so the sLSTM kernels must run inside a ``shard_map``,
    each chip on its own peer's row."""
    import re
    import numpy as np
    from repro.core.fl_device import make_fl_train_step
    from repro.kernels import ops
    from repro.runtime.sharding import (batch_shardings, make_shard_plan,
                                        state_shardings)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = get_config("xlstm-350m")
    model, n = Model(cfg), 4
    mesh = jax.sharding.Mesh(np.asarray(topo.devices).reshape(n, 1),
                             ("data", "model"))
    splan = make_shard_plan(mesh)
    shape = fl_state_shape(model, n)
    st_sh = state_shardings(shape, splan, head_dim=cfg.head_dim,
                            num_heads=cfg.num_heads,
                            num_kv_heads=cfg.num_kv_heads)
    state = jax.tree.map(lambda x, sh: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sh), shape, st_sh)
    data = {k: np.zeros((n, 1, 1, 1, 2048), np.int32)
            for k in ("tokens", "labels")}
    b_sh = batch_shardings(data, splan)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=b_sh[k])
             for k, v in data.items()}
    step = make_fl_train_step(model, plan_grid(n, group_size=2, depth=2),
                              lr=0.1, aggregate=True)
    text = jax.jit(step, in_shardings=(st_sh, b_sh)).lower(
        state, batch).compile().as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 2
    # one peer's row per chip: h [1, S, d] forward, dpre [1, S, 4d] back
    for line in kernels:
        assert re.search(r"= \(f32\[1,2048,", line), line[:200]
