"""The control at a tiny size: the plain reference computed in float8, put
in the program's place, has to come out not correct against the limits
that pass the program. (The same readings at each cell's own size, on the
chip, set the cells' limits; PERF.md gives them.)"""
import time

import pytest

import tiny
import compare
import harness

SEEDS = [1, 2, 2 ** 31 + 5]


@pytest.mark.parametrize("seed", SEEDS)
def test_train_control_fails(seed):
    import jax
    from repro.models.model import Model
    fl = harness.load_module("drivers", "fl_train")
    cell = tiny.cell("xlstm-350m.fl.c1")
    ctx = harness.Ctx(cell, seed, 0.0, False, jax.devices()[:1],
                      time.perf_counter(), lambda msg: None)
    tr = cell.traffic
    ring = fl.make_ring(tr, 512, seed, tr["check_steps"])
    ref = fl.reference_readings(ctx, ring)
    limits = cell.cell["limits"]
    control = compare.train_checks(
        fl.reference_readings(ctx, ring, precision="fp8"), ref, limits)
    assert any(v > lim for _, v, lim in control), control
    model = Model(harness.model_config(cell.config))
    *_, prog = fl.program_readings(ctx, model, fl._grid(tr), ring)
    program = compare.train_checks(prog, ref, limits)
    assert all(v <= lim for _, v, lim in program), program
