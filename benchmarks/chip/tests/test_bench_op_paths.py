"""Op paths: the parser on a hand-made module and on the tiny FL step
compiled here (``remat: "block"``), the phase and block classes of its
ops, the scopes leaving the program as it was, and the split of a trace's
device time by them (the hand trace of ``test_bench_trace.py`` given a
path map)."""
import contextlib
import re
import time
from types import SimpleNamespace

import pytest

import tiny
import harness
import op_paths
from test_bench_trace import MS, hand_trace

CELL = "xlstm-350m.fl.c1"
PHASES = [cls for cls, _ in op_paths.PHASES]
BLOCKS = [cls for cls, _ in op_paths.BLOCKS]
# ops that move or hold data and compute nothing of the program's own
PLUMBING = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
            "copy", "copy-start", "copy-done", "broadcast", "iota", "compare",
            "select", "convert", "reshape", "transpose", "slice",
            "dynamic-slice", "dynamic-update-slice", "concatenate",
            "custom-call", "fusion"}

HAND_HLO = """HloModule jit_step, is_scheduled=true

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %multiply.1 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step)/fl.opt/mul" source_file="a.py" source_line=3}
  ROOT %bitcast.2 = f32[4]{0} bitcast(%multiply.1)
}

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %get-tuple-element.3 = f32[4]{0} get-tuple-element(%p), index=1
  %copy.4 = f32[4]{0} copy(%get-tuple-element.3)
  %multiply_bitcast_fusion = f32[4]{0} fusion(%copy.4), kind=kLoop, calls=%fused_computation
  ROOT %tuple.5 = (s32[], f32[4]{0}) tuple(%get-tuple-element.3, %multiply_bitcast_fusion)
}

ENTRY %main.6 (Arg_0.1: f32[4]) -> f32[4] {
  %Arg_0.1 = f32[4]{0} parameter(0), metadata={op_name="state[\\'params\\']"}
  %copy.9 = f32[4]{0} copy(%Arg_0.1)
  %constant.10 = s32[] constant(0)
  %tuple.11 = (s32[], f32[4]{0}) tuple(%constant.10, %copy.9)
  %while.7 = (s32[], f32[4]{0}) while(%tuple.11), condition=%cond, body=%body, metadata={op_name="jit(step)/vmap(fl.grad)/while"}
  ROOT %get-tuple-element.8 = f32[4]{0} get-tuple-element(%while.7), index=1
}
"""


def test_parser_reads_metadata_and_fills_the_compilers_gaps():
    paths = op_paths.op_paths(HAND_HLO)
    assert paths["Arg_0.1"] == "state[\\'params\\']"
    assert paths["multiply.1"] == "jit(step)/fl.opt/mul"
    # a fusion without metadata: its op nearest the root
    assert paths["multiply_bitcast_fusion"] == "jit(step)/fl.opt/mul"
    # a loop's bookkeeping: the loop's own path
    assert paths["copy.4"] == paths["tuple.5"] == \
        "jit(step)/vmap(fl.grad)/while"
    # an op the compiler put in elsewhere: its first operand's path
    assert paths["get-tuple-element.8"] == "jit(step)/vmap(fl.grad)/while"
    assert paths["copy.9"] == paths["tuple.11"] == "state[\\'params\\']"
    # the entry computation's plumbing that nothing named feeds has none
    assert "constant.10" not in paths


@pytest.mark.parametrize("path, phase, block", [
    ("jit(s)/vmap()/while/body/fl.grad/mlstm/dot_general", "fwd", "mlstm"),
    ("jit(s)/vmap(fl.grad)/transpose(jvp())/slstm/mul", "bwd", "slstm"),
    ("jit(s)/fl.grad/transpose(jvp())/checkpoint/rematted_computation/"
     "mlstm/exp", "remat", "mlstm"),
    ("jit(s)/fl.grad/transpose(jvp(lm_head))/dot_general", "bwd", "head"),
    ("jit(s)/vmap()/fl.opt/mul", "opt", op_paths.UNSCOPED),
    ("jit(s)/fl.aggregate/reduce_sum", "aggregate", op_paths.UNSCOPED),
    ("jit(s)/fl.gradient/mlstms/add", op_paths.UNSCOPED, op_paths.UNSCOPED),
    ("", op_paths.UNSCOPED, op_paths.UNSCOPED),
])
def test_classes_match_whole_scope_names(path, phase, block):
    assert op_paths.classify(path, op_paths.PHASES) == phase
    assert op_paths.classify(path, op_paths.BLOCKS) == block


def _tiny_inp():
    c = tiny.cell(CELL)
    assert c.config["model"]["remat"] == "block"
    return SimpleNamespace(traffic=c.traffic, model=c.config["model"],
                           chips=c.chips)


@pytest.fixture(scope="module")
def tiny_text():
    return op_paths.compiled_step(_tiny_inp())


@pytest.fixture(scope="module")
def plain_text():
    """The tiny step compiled with every ``jax.named_scope`` a no-op: the
    program as it is without the scopes."""
    import jax
    real = jax.named_scope
    jax.named_scope = lambda name: contextlib.nullcontext()
    try:
        return op_paths.compiled_step(_tiny_inp())
    finally:
        jax.named_scope = real


def _computations(text):
    """{computation: [(instruction, opcode)]} and the set of computations
    whose instructions never run as ops of their own (fused computations
    and reducers)."""
    comps, inner, comp = {}, set(), None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            comp = re.match(r"(?:ENTRY )?%?([\w.\-]+)", line).group(1)
            comps[comp] = []
        elif " = " in line and comp is not None and line.startswith(" "):
            name = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = ", line).group(1)
            rest = line.split(" = ", 1)[1]
            if rest.startswith("("):         # a tuple shape
                depth = 0
                for i, ch in enumerate(rest):
                    depth += (ch == "(") - (ch == ")")
                    if depth == 0:
                        break
                rest = rest[i + 1:]
            else:
                rest = rest.split(" ", 1)[1]
            opcode = re.match(r"\s*([\w\-]+)", rest).group(1)
            comps[comp].append((name, opcode))
            for callee in re.findall(r"\b(?:calls|to_apply)=%?([\w.\-]+)",
                                     line):
                if opcode == "fusion" or "to_apply=" in line:
                    inner.add(callee)
    return comps, inner


def test_parser_maps_the_compiled_step(tiny_text):
    paths = op_paths.op_paths(tiny_text)
    named = dict(re.findall(
        r'^\s+(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{op_name="([^"]*)"',
        tiny_text, re.M))
    assert len(named) > 1000
    assert {k: paths[k] for k in named} == named
    assert op_paths.is_scoped(paths)


def test_every_op_that_runs_falls_in_one_phase(tiny_text):
    paths = op_paths.op_paths(tiny_text)
    comps, inner = _computations(tiny_text)
    ops = [(n, oc) for c, insts in comps.items() if c not in inner
           for n, oc in insts]
    by = {}
    for name, opcode in ops:
        by.setdefault(op_paths.classify(paths.get(name, ""),
                                        op_paths.PHASES), []).append(
            (name, opcode))
    assert set(by) <= set(PHASES) | {op_paths.UNSCOPED}
    assert sum(map(len, by.values())) == len(ops)
    for cls in ("fwd", "bwd", "remat", "aggregate"):
        assert by.get(cls), cls
    # what no phase holds computes nothing of the program: the compiler's
    # plumbing, and fusions of it without a dot or a reduction
    leftover = by.get(op_paths.UNSCOPED, [])
    assert {oc for _, oc in leftover} <= PLUMBING
    fused = dict(re.findall(r"^\s+(?:ROOT )?%?([\w.\-]+) = .*\bfusion\(.*"
                            r"\bcalls=%?([\w.\-]+)", tiny_text, re.M))
    for name, opcode in leftover:
        if opcode == "fusion":
            inside = {oc for _, oc in comps[fused[name]]}
            assert not inside & {"dot", "convolution", "reduce"}, name


def test_every_scope_reaches_the_compiled_step(tiny_text):
    paths = op_paths.op_paths(tiny_text).values()
    for rules in (op_paths.PHASES, op_paths.BLOCKS):
        found = {op_paths.classify(p, rules) for p in paths}
        assert found >= {cls for cls, _ in rules}, found


def _canonical(text):
    """The module without metadata and source tables, its instruction and
    computation names numbered by first appearance (XLA draws the names
    from the source locations)."""
    text = re.sub(r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n(?:.+\n?)*", "", text, flags=re.M)
    text = re.sub(r', metadata=\{(?:[^{}"\n]|"(?:[^"\\\n]|\\.)*")*\}', "",
                  text)
    ids = {}
    return re.sub(r"%?\b[A-Za-z_][\w\-]*\.\d+(?:\.[\w\-]+)*\b|%[\w.\-]+",
                  lambda m: ids.setdefault(m.group(0).lstrip("%"),
                                           f"%n{len(ids)}"), text)


def test_scopes_leave_the_program_as_it_was(tiny_text, plain_text):
    assert not op_paths.is_scoped(op_paths.op_paths(plain_text))
    assert _canonical(tiny_text) == _canonical(plain_text)


def test_the_lowering_is_the_step_that_ran(tiny_text):
    import jax
    from repro.models.model import Model
    fl = harness.load_module("drivers", "fl_train")
    c = tiny.cell(CELL)
    tr = c.traffic
    ctx = harness.Ctx(c, 7, 0.0, False, jax.devices()[:1],
                      time.perf_counter(), lambda msg: None)
    ring = fl.make_ring(tr, 512, 7, tr["check_steps"])
    step, state, feed, _ = fl.program_readings(
        ctx, Model(harness.model_config(c.config)), fl._grid(tr), ring)
    ran = step.lower(state, feed(ring[0])).compile().as_text()
    assert op_paths.op_paths(ran) == op_paths.op_paths(tiny_text)


PATHS = {"while.1": "jit(step)/vmap(fl.grad)/while",
         "fusion.1": "jit(step)/fl.grad/transpose(jvp())/mlstm/dot",
         "fusion.2": "jit(step)/fl.grad/checkpoint/rematted_computation/"
                     "slstm/mul",
         "all-reduce.2": "jit(step)/fl.aggregate/psum"}


def test_split_of_the_hand_trace():
    t = hand_trace()
    top = t.top_ops()
    phase = op_paths.split(t, PATHS, op_paths.PHASES)
    # device 0: the loop keeps 5 ms (fwd), fusion.1 20 (bwd), fusion.2 15
    # (remat), the all-reduce 20, fusion.3 10 (not in the map); device 1:
    # fusion.1 40, the all-reduce 20; averaged over the two
    assert phase == pytest.approx({"fwd": 0.0025, "bwd": 0.030,
                                   "remat": 0.0075, "aggregate": 0.020,
                                   op_paths.UNSCOPED: 0.005})
    assert sum(phase.values()) == pytest.approx(t.busy_s())
    block = op_paths.split(t, PATHS, op_paths.BLOCKS)
    assert block == pytest.approx({"mlstm": 0.030, "slstm": 0.0075,
                                   op_paths.UNSCOPED: 0.0275})
    assert sum(block.values()) == pytest.approx(t.busy_s())
    # the breakdown's keys and numbers are those test_bench_trace reads
    assert t.top_ops() == top
    assert dict(top)["jit(step)/dot"] == pytest.approx(0.030)


def test_an_op_missing_from_the_map_is_unscoped():
    t = hand_trace()
    without = {k: v for k, v in PATHS.items() if k != "all-reduce.2"}
    phase = op_paths.split(t, without, op_paths.PHASES)
    assert "aggregate" not in phase
    assert phase[op_paths.UNSCOPED] == pytest.approx(0.025)
    assert op_paths.split(t, {}, op_paths.PHASES) == pytest.approx(
        {op_paths.UNSCOPED: t.busy_s()})


def test_trace_names_resolve_to_instructions():
    assert op_paths.instruction("%fusion.9 = f32[4]{0} fusion(%p)") == \
        "fusion.9"
    assert op_paths.instruction("fusion.9") == "fusion.9"


READERS = ["fwd_ms.train", "bwd_ms.train", "remat_ms.train", "opt_ms.train",
           "aggregate_ms.train", "mlstm_ms.train", "slstm_ms.train",
           "head_ms.train", "unscoped_share.train"]


def _inp_over(text, calls=2):
    """Layer inputs whose trace runs every op of ``text`` that runs on its
    own once, 1 ms each, on one device, over ``calls`` calls."""
    import trace_reduce
    comps, inner = _computations(text)
    names = [n for c, insts in comps.items() if c not in inner
             for n, _ in insts]
    ops = {0: [(n, i * MS, (i + 1) * MS, n) for i, n in enumerate(names)]}
    t = trace_reduce.Trace(ops, {"bench.window": [(0, len(names) * MS)]},
                           (0, len(names) * MS))
    inp = _tiny_inp()
    inp.trace, inp.counters = t, {"traced_calls": calls}
    return inp


def _read(inp, monkeypatch, text):
    monkeypatch.setattr(op_paths, "compiled_step", lambda _: text)
    return {m: harness.load_module("layer_metrics", m).read(inp)
            for m in READERS}


def test_readers_split_each_call(tiny_text, monkeypatch):
    inp = _inp_over(tiny_text)
    got = _read(inp, monkeypatch, tiny_text)
    assert all(v is not None for v in got.values()), got
    busy_ms = inp.trace.busy_s() * 1e3 / 2
    phases = sum(got[f"{p}_ms.train"] for p in PHASES)
    assert phases == pytest.approx(
        busy_ms * (1 - got["unscoped_share.train"] / 100))
    blocks = got["mlstm_ms.train"] + got["slstm_ms.train"] + \
        got["head_ms.train"]
    assert 0 < blocks <= phases
    assert got["unscoped_share.train"] < 50


def test_readers_find_nothing_without_the_scopes(plain_text, monkeypatch):
    got = _read(_inp_over(plain_text), monkeypatch, plain_text)
    assert got == {m: None for m in READERS}
