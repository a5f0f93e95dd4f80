"""Runs in a child process on four virtual CPU devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``; the device
count is fixed when JAX starts) and prints one JSON line of what
``test_bench_mesh.py`` asserts: the tiny four-chip cell run whole, sound
and with each fault; the reference's readings under three placements;
the step that ran against the one the readers lower; and the readers
over that step's ops.

  python3 benchmarks/chip/tests/mesh_child.py
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import tiny  # noqa: E402  (puts the benchmark's modules on the path)
import harness  # noqa: E402

CELL = "xlstm-350m.mar.c4"
FAULTS = ("frozen", "half_batch", "no_mar")
READERS = ("fwd_ms.train", "bwd_ms.train", "remat_ms.train", "opt_ms.train",
           "aggregate_ms.train", "mlstm_ms.train", "slstm_ms.train",
           "head_ms.train", "unscoped_share.train", "train_mfu",
           "idle_share.train", "slstm_fused_share.train")


def runs() -> dict:
    out = {}
    for fault in (None,) + FAULTS:
        r = tiny.run(CELL, fault=fault)
        out[fault or "sound"] = {"correct": r["correct"],
                                 "checks": r["checks"],
                                 "attempted": r["attempted"],
                                 "count": r["device"]["count"]}
    return out


def placements(seed: int = 2 ** 31 + 3) -> dict:
    """The reference's readings with all state on one device, with
    finished peers in host memory (one device, and two), and with one
    peer per device: {placement: True where its readings equal the
    first placement's bit for bit}."""
    import jax
    import numpy as np
    fl = harness.load_module("drivers", "fl_train")
    c = tiny.cell(CELL)
    tr = c.traffic
    ring = fl.make_ring(tr, c.config["model"]["vocab_size"], seed,
                        tr["check_steps"])
    d = jax.devices()
    got = {}
    for name, devs in (("one_device", [d[0]] * tr["peers"]),
                       ("host_parked", [d[0]]), ("two_devices", d[:2]),
                       ("one_per_device", d[:4])):
        ctx = harness.Ctx(c, seed, 0.0, False, devs, time.perf_counter(),
                          lambda msg: None)
        got[name] = fl.reference_readings(ctx, ring)
    first = got["one_device"]

    def same(a, b) -> bool:
        if a["loss"] != b["loss"] or a["grad1"] != b["grad1"] or \
                a["dtheta"] != b["dtheta"]:
            return False
        return all(np.array_equal(a["grad1_leaves"][k], b["grad1_leaves"][k])
                   for k in a["grad1_leaves"])
    return {name: same(first, r) for name, r in got.items()}


def lowering(seed: int = 7) -> dict:
    """The op paths of the mesh step that ran against those of the step
    ``op_paths.compiled_step`` lowers for four chips, and the readers
    over the ops of that step (each run once, 1 ms, on each of four
    devices, over two calls)."""
    import jax
    from types import SimpleNamespace
    from repro.models.model import Model
    import op_paths
    import trace_reduce
    from test_bench_op_paths import MS, _computations
    fl = harness.load_module("drivers", "fl_train")
    c = tiny.cell(CELL)
    tr = c.traffic
    ctx = harness.Ctx(c, seed, 0.0, False, jax.devices()[:4],
                      time.perf_counter(), lambda msg: None)
    ring = fl.make_ring(tr, c.config["model"]["vocab_size"], seed,
                        tr["check_steps"])
    step, state, feed, _ = fl.program_readings(
        ctx, Model(harness.model_config(c.config)), fl._grid(tr), ring)
    ran = step.lower(state, feed(ring[0])).compile().as_text()
    inp = SimpleNamespace(traffic=tr, model=c.config["model"],
                          config=c.config, chips=c.chips,
                          peak=harness.load_peaks("TPU v5 lite"))
    text = op_paths.compiled_step(inp)
    comps, inner = _computations(text)
    names = [n for cmp, insts in comps.items() if cmp not in inner
             for n, _ in insts]
    span = (0, len(names) * MS)
    ops = {dev: [(n, i * MS, (i + 1) * MS, n) for i, n in enumerate(names)]
           for dev in range(4)}
    inp.trace = trace_reduce.Trace(ops, {"bench.window": [span]}, span)
    inp.counters = {"traced_calls": 2, "tokens_per_call": 4 * tr["seq"]}
    read = {m: harness.load_module("layer_metrics", m).read(inp)
            for m in READERS}
    return {"same_paths": op_paths.op_paths(ran) == op_paths.op_paths(text),
            "all_reduces": text.count(" all-reduce("),
            "busy_ms": inp.trace.busy_s() * 1e3, "readers": read}


def main() -> int:
    import jax
    if len(jax.devices()) != 4:
        raise SystemExit(f"four devices wanted, JAX found "
                         f"{len(jax.devices())}")
    print(json.dumps({"runs": runs(), "placements": placements(),
                      "lowering": lowering()}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
