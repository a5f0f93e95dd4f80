"""The reader of ``slstm_fused_share.train``: the share of the sLSTM
blocks' time under ``slstm_recurrence``, on a hand-made path map over the
hand trace of ``test_bench_trace.py``, and on the tiny FL step compiled
here, with and without the program's scopes."""
from types import SimpleNamespace

import pytest

import tiny  # noqa: F401  (puts the benchmark's modules on the path)
import harness
import op_paths
from test_bench_op_paths import _inp_over, plain_text, tiny_text  # noqa: F401
from test_bench_trace import hand_trace

METRIC = "slstm_fused_share.train"
REC = "jit(step)/fl.grad/transpose(jvp())/slstm/slstm_recurrence/"
PATHS = {"while.1": "jit(step)/vmap(fl.grad)/mlstm/while",
         "fusion.1": REC + "dot_general",
         "fusion.2": "jit(step)/fl.grad/slstm/dot_general",
         "all-reduce.2": "jit(step)/fl.aggregate/psum"}


def _read(inp, monkeypatch, text=None, paths=None):
    if paths is not None:
        monkeypatch.setattr(op_paths, "op_paths", lambda _: paths)
    monkeypatch.setattr(op_paths, "compiled_step", lambda _: text)
    return harness.load_module("layer_metrics", METRIC).read(inp)


@pytest.mark.parametrize("paths, share", [
    # fusion.1 30 ms of the recurrence (its 20 + 40 ms over two devices)
    # against fusion.2's 7.5 ms in the rest of the sLSTM block
    (PATHS, 80.0),
    ({**PATHS, "fusion.2": REC + "mul"}, 100.0),
    # no op under the scope, as in a program without it: nothing to read
    ({**PATHS, "fusion.1": "jit(step)/fl.grad/slstm/mul"}, None),
    ({k: v for k, v in PATHS.items() if "slstm" not in v}, None),
])
def test_share_of_the_hand_trace(paths, share, monkeypatch):
    inp = SimpleNamespace(trace=hand_trace(), counters={"traced_calls": 2})
    got = _read(inp, monkeypatch, paths=paths)
    assert got == (None if share is None else pytest.approx(share))


def test_no_traced_call_reads_nothing(monkeypatch):
    inp = SimpleNamespace(trace=hand_trace(), counters={})
    assert _read(inp, monkeypatch, paths=PATHS) is None


def test_the_tiny_step_runs_the_recurrence_under_its_scope(tiny_text,
                                                           monkeypatch):
    paths = op_paths.op_paths(tiny_text)
    rec = [p for p in paths.values() if "slstm_recurrence" in p]
    for phase in ("fwd", "bwd"):
        assert any(op_paths.classify(p, op_paths.PHASES) == phase
                   for p in rec), phase
    assert all(op_paths.classify(p, op_paths.BLOCKS) == "slstm" for p in rec)
    got = _read(_inp_over(tiny_text), monkeypatch, text=tiny_text)
    assert 50.0 < got <= 100.0


def test_the_reader_finds_nothing_without_the_scopes(plain_text,
                                                     monkeypatch):
    assert _read(_inp_over(plain_text), monkeypatch, text=plain_text) is None
