"""Tiny cells for the CPU tests: a cell's own files with the model cut to
smoke widths and the traffic to a few short rows, so a whole run (set-up,
window, check) takes seconds in interpret mode."""
from __future__ import annotations

import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH.parents[1] / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

TRAIN_MODEL = dict(num_layers=4, slstm_every=2, d_model=128, num_heads=4,
                   num_kv_heads=4, head_dim=32, vocab_size=512)
# limits at this size, read on the CPU over nine seeds: the program's
# readings stay under them (head_grad1_diff at most 0.0184, the norm gaps
# at most 0.0231), the float8 control's head_grad1_diff does not (at least
# 0.2075; test_bench_control.py), nor any planted fault's (at least 0.83;
# test_bench_faults.py)
TRAIN_LIMITS = {"grad1_gap": 0.1, "dtheta_gap": 0.1, "head_grad1_diff": 0.12}


def cell(name: str):
    """The cell ``name`` from its own files (whether or not BENCHMARK.json
    lists it), cut to the tiny size."""
    w = harness.load_json("workloads", name)
    entry = {"name": name, **{k: w[k] for k in ("config", "traffic", "chips")}}
    c = harness.resolve_cell(name, {"workloads": [entry], "end_to_end": [],
                                    "per_layer": []})
    c.config["model"].update(TRAIN_MODEL)
    c.traffic.update(seq=64, ring=4)
    c.cell["limits"] = dict(TRAIN_LIMITS)
    return c


def run(name: str, seed: int = 2 ** 31 + 11, fault=None, seconds=0.5):
    """One run of the tiny cell on the CPU, the chip check skipped."""
    import jax
    import run as runner
    c = cell(name)
    return runner.run_cell(c, seed, seconds, False, jax.devices()[:c.chips],
                           harness.load_peaks("TPU v5 lite"),
                           time.perf_counter(), fault=fault,
                           log=lambda msg: None)
