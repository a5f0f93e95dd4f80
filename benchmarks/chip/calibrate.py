"""Readings that set a cell's limits: the program, the control and the
planted faults, over many seeds in one process. The benchmark's own runs
never run this.

  python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1,2,3 \
      --modes program,control,half_batch,no_mar

Per seed, without a window: the reference's readings of the first calls
(float32), then for each mode the readings it is compared with:
  program     the timed path as it stands;
  control     the reference itself computed in float8 (``refmath``), put
              in the program's place;
  default     the reference with the backend's default precision of
              products (one bf16 pass on a TPU): a witness of what
              rounding alone does;
  half_batch, no_mar, frozen
              the timed path with that fault planted (``drivers/fl_train``).

Prints one JSON line per seed and mode: {"seed", "mode", numbers...};
a training mode's line also holds its per-leaf readings, and each seed
has a line with the reference's.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import harness  # noqa: E402


def train_seed(cell, seed, modes, devices, log, built: dict) -> list:
    """The rows of one seed. ``built`` keeps each fault's step across
    seeds (``half_batch`` breaks only the feed, so it shares the sound
    step), so that each is traced and compiled once a process."""
    import jax
    from repro.models.model import Model
    import compare
    fl = harness.load_module("drivers", "fl_train")
    ctx = harness.Ctx(cell, seed, 0.0, False, devices, time.perf_counter(),
                      log)
    tr = ctx.traffic
    model = Model(harness.model_config(ctx.config))
    grid = fl._grid(tr)
    ring = fl.make_ring(tr, model.cfg.vocab_size, seed, tr["check_steps"])
    t0 = time.perf_counter()
    ref = fl.reference_readings(ctx, ring)
    log(f"seed {seed}: reference {time.perf_counter() - t0:.3f} s")
    rows = [{"seed": seed, "mode": "reference",
             "readings": compare.loggable(ref)}]
    for mode in modes:
        t0 = time.perf_counter()
        if mode in ("control", "default"):
            got = fl.reference_readings(
                ctx, ring, precision="fp8" if mode == "control" else mode)
        else:
            fault = None if mode == "program" else mode
            key = None if fault == "half_batch" else fault
            if key not in built:
                built[key] = fl.build_step(ctx, model, grid, key)
            step, state, _, got = fl.program_readings(
                ctx, model, grid, ring, fault, built=built[key])
            del step, state
            gc.collect()
        nums = compare.train_numbers(got, ref)
        rows.append({"seed": seed, "mode": mode,
                     "seconds": time.perf_counter() - t0,
                     **{k: v[0] for k, v in nums.items()},
                     "detail": {k: v[1] for k, v in nums.items()},
                     "grad1_diff_leaves": compare.diff_rels(got, ref),
                     "readings": compare.loggable(got)})
        del got
        gc.collect()
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--modes", default="program,control")
    args = ap.parse_args(argv)
    cell = harness.resolve_cell(args.workload)
    devices = harness.require_chip(cell.chips)
    harness.use_bench_cache()
    log = lambda msg: print(f"[calibrate] {msg}", file=sys.stderr,
                            flush=True)
    built = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        rows = train_seed(cell, seed, args.modes.split(","), devices, log,
                          built)
        for r in rows:
            print(json.dumps(r), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
