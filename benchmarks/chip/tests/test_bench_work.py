"""The xLSTM work model's counts (``work/xlstm.py``, the one xlstm-350m
names) against hand counts, and against the program's own parameter
leaves."""
import numpy as np
import pytest

import tiny
import harness

CFG = harness.load_json("configs", "xlstm-350m")
XL = CFG["model"]
work = harness.load_module("work", CFG["work"])


def test_xlstm_params_by_hand():
    d, inner, nh, V = 1024, 2048, 4, 50304
    mlstm = d * 2 * inner + 3 * inner * inner + 2 * inner * nh + inner * d + d
    slstm = d * 4 * d + nh * 256 * 1024 + 4 * d + d * d + d
    assert work.param_count(XL) == 2 * V * d + 21 * mlstm + 3 * slstm + d \
        == 518_640_640


def test_params_match_the_programs_leaves():
    import jax
    from repro.models.model import Model
    cfg = harness.load_json("configs", "xlstm-350m")
    shape = Model(harness.model_config(cfg)).init_shape()
    leaves = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shape))
    assert work.param_count(cfg["model"]) == leaves \
        == cfg["params_as_implemented"]


def test_xlstm_train_flops_by_hand():
    # per token forward: 21 mLSTM + 3 sLSTM blocks, head; chunk 256
    d, inner, nh, hd, c, V = 1024, 2048, 4, 512, 256, 50304
    mlstm = 2 * (d * 2 * inner + 3 * inner ** 2 + 2 * inner * nh
                 + inner * d) + nh * (2 * c * hd + 2 * c * (hd + 1)
                                      + 4 * hd * (hd + 1))
    slstm = 2 * (d * 4 * d + 4 * 256 * 1024 + d * d)
    fwd = 21 * mlstm + 3 * slstm + 2 * d * V
    assert work.train_flops_per_token(XL, 2048) == pytest.approx(3 * fwd)


def test_train_mfu_reads_the_work_model_the_config_names():
    from types import SimpleNamespace
    from test_bench_trace import hand_trace
    peak = harness.load_peaks("TPU v5 lite")
    inp = SimpleNamespace(trace=hand_trace(), config=CFG, model=XL,
                          traffic={"seq": 2048}, chips=1, peak=peak,
                          counters={"traced_calls": 2,
                                    "tokens_per_call": 4096})
    read = harness.load_module("layer_metrics", "train_mfu").read
    want = 100.0 * work.train_flops_per_token(XL, 2048) * 2 * 4096 / (
        0.1 * peak["bf16_flops_per_s"])
    assert read(inp) == pytest.approx(want)
    with pytest.raises(harness.BenchError):
        read(SimpleNamespace(**{**vars(inp),
                                "config": {**CFG, "work": "no-such"}}))
