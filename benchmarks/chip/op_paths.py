"""Where each device op of a traced window sits in the program, and the
split of the window's device time by phase of the FL step and by block
of the model.

The v5e trace names an op by its HLO instruction's text alone
(``%fusion.749 = ...``; no stat of the event holds a path), and
``trace_reduce.load`` keeps no more of it. The op's path in
the program is the instruction's ``op_name`` in the compiled module's
metadata: the ``jax.named_scope`` names the program sets, inside the
transforms JAX puts round them (``vmap(...)``, ``transpose(jvp(...))``
for the backward pass, ``checkpoint/rematted_computation`` for the
recompute). ``op_paths`` reads them from the module's text;
``compiled_step`` compiles the cell's own step for the window's shapes to
get that text, and so finds the program the window ran in the persistent
compilation cache. Readers call it after the window, in traced runs only.

Each partition is an ordered table of rules, the first match winning, so
its classes exclude each other and add up to busy time. A fused op counts
whole to the class of the op XLA names it after (the fusion's root). An
op with no path, or one that matches no rule, is ``unscoped``.
"""
from __future__ import annotations

import re
import sys
import time
from collections import defaultdict
from types import SimpleNamespace

import harness

UNSCOPED = "unscoped"
# (class, token): a token ending in "(" matches anywhere in the path, any
# other token matches a whole scope name
PHASES = (("aggregate", "fl.aggregate"), ("opt", "fl.opt"),
          ("remat", "rematted_computation"), ("bwd", "transpose("),
          ("fwd", "fl.grad"))
BLOCKS = (("mlstm", "mlstm"), ("slstm", "slstm"), ("head", "lm_head"))

_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) ")
_INSTR = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
_FUSION_OF = re.compile(r"\bfusion\(.*\bcalls=%?([\w.\-]+)")
_RUNS = re.compile(r"\b(?:calls|to_apply|body|condition|true_computation|"
                   r"false_computation)=%?([\w.\-]+)|"
                   r"\bbranch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.\-]+)")
_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")


def op_paths(hlo_text: str) -> dict:
    """{instruction name: op_name} of a compiled module's text.

    An instruction whose metadata names no op (one the compiler put in or
    rewrote: a copy, a bitcast, a loop's bookkeeping) takes a path from
    the program all the same where it can: a fusion the op_name of the
    last instruction of its fused computation that has one (the one
    nearest its root); else any instruction the path of the op that runs
    its computation (the ``while`` of a loop body), else that of its first
    operand that has one. What is left is the entry computation's own
    plumbing: its parameters, and what only they feed."""
    paths, last, comp = {}, {}, None
    comp_of, operands, runner = {}, {}, {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            if line.endswith("{") and (h := _HEADER.match(line)):
                comp = h.group(1)
            continue
        name = m.group(1)
        comp_of[name] = comp
        operands[name] = _OPERAND.findall(line.split(" = ", 1)[1])
        for one, many in _RUNS.findall(line):
            for callee in [one] if one else re.findall(r"[\w.\-]+", many):
                runner[callee] = name
        op = _OP_NAME.search(line)
        path = op.group(1) if op else None
        if path is None and (f := _FUSION_OF.search(line)):
            path = last.get(f.group(1))
        if path:
            paths[name] = last[comp] = path

    def inherited(c, seen=()):
        inst = runner.get(c)
        if inst is None or c in seen:
            return None
        return paths.get(inst) or inherited(comp_of[inst], seen + (c,))

    for name, c in comp_of.items():
        if name not in paths:
            path = inherited(c) or next(
                (paths[o] for o in operands[name] if o in paths), None)
            if path:
                paths[name] = path
    return paths


def instruction(event_name: str) -> str:
    """A trace event's instruction: ``%fusion.1 = ...`` -> ``fusion.1``."""
    return event_name.split(" = ")[0].lstrip("%")


def _scopes(path: str) -> set:
    """The path's scope names, the transforms round each taken off:
    ``vmap(fl.grad)`` -> ``fl.grad``."""
    out = set()
    for seg in path.split("/"):
        while (m := _WRAPPED.match(seg)):
            seg = m.group(1)
        out.add(seg)
    return out


def classify(path: str, rules) -> str:
    scopes = _scopes(path)
    for cls, token in rules:
        if (token in path) if token.endswith("(") else (token in scopes):
            return cls
    return UNSCOPED


def is_scoped(paths: dict) -> bool:
    """True where the program names its FL step's phases (a program
    without the scopes gives every op to ``unscoped``)."""
    return any("fl.grad" in _scopes(p) for p in paths.values())


def split(trace, paths: dict, rules) -> dict:
    """{class: self seconds}, averaged over the trace's devices, of the
    ops whose instruction ``paths`` maps, by ``rules``."""
    tot, memo = defaultdict(float), {}
    n = max(len(trace.ops), 1)
    for evs in trace.ops.values():
        for name, _, _, _, self_ns, _ in evs:
            cls = memo.get(name)
            if cls is None:
                cls = memo[name] = classify(
                    paths.get(instruction(name), ""), rules)
            tot[cls] += self_ns * 1e-9 / n
    return dict(tot)


def compiled_step(inp) -> str:
    """The text of the cell's FL step as its module under ``drivers/``
    builds it on the cell's ``inp.chips`` devices (the mesh program on
    several), compiled for the window's shapes and layouts."""
    import jax
    from repro.models.model import Model
    tr = inp.traffic
    fl = harness.load_module("drivers", tr["driver"])
    model = Model(harness.model_config({"model": inp.model}))
    devices = jax.devices()[:inp.chips]
    step, _, sh = fl.build_step(
        SimpleNamespace(traffic=tr, devices=devices), model, fl._grid(tr))
    return step.lower(*fl.abstract_args(model, tr, sh)).compile().as_text()


def splits(inp):
    """{"phase": split, "block": split} of the traced window, or None where
    the program has no phase scopes. Computed once per run and kept on
    the readers' input, which every reader of the run shares."""
    if not hasattr(inp, "op_splits"):
        t0 = time.perf_counter()
        paths = op_paths(compiled_step(inp))
        names = {instruction(n) for evs in inp.trace.ops.values()
                 for n, *_ in evs}
        print(f"op paths: {len(paths)} instructions mapped in "
              f"{time.perf_counter() - t0:.3f} s; {len(names - set(paths))}"
              f" of the window's {len(names)} ops unresolved",
              file=sys.stderr)
        inp.op_splits = {"phase": split(inp.trace, paths, PHASES),
                         "block": split(inp.trace, paths, BLOCKS)} \
            if is_scoped(paths) else None
    return inp.op_splits


def per_call_ms(inp, partition: str, cls: str):
    """Device self time of class ``cls`` of ``partition`` ("phase" or
    "block") per traced call, in ms."""
    calls = inp.counters.get("traced_calls")
    out = splits(inp) if calls else None
    if out is None:
        return None
    return out[partition].get(cls, 0.0) / calls * 1e3
