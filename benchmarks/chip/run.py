"""Run one cell of the chip benchmark once, and print its result line.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
      --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic,
driver and per-layer metrics are files found by name (``harness.py``).
The run makes weights and traffic from the seed, warms up the cell's own
shapes (set-up), measures for ``--seconds``, checks the timed path's
output against the plain reference, and prints one JSON line last on
standard output. ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window's first ``trace_seconds``.

It exits non-zero, printing no line, when JAX finds no TPU, fewer chips
than the cell asks for, or a device kind that ``peaks.json`` lacks.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import harness  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the profiler trace to this directory")
    return ap.parse_args(argv)


def per_layer_metrics(ctx, cell, peak: dict, keep_trace=None) -> tuple:
    """(metrics, device extras, breakdown) of a traced run."""
    import trace_reduce
    if ctx.trace_file is None:
        raise harness.BenchError("the traced run left no trace")
    tr = trace_reduce.load(ctx.trace_file)
    ctx.discard_trace(keep_trace)
    inp = harness.layer_inputs(ctx, tr, peak)
    metrics = {}
    for m in cell.per_layer:
        value = harness.load_module("layer_metrics", m["name"]).read(inp)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    extras = {"busy_s": tr.busy_s(), "window_s": tr.window_s}
    breakdown = {"device_ops": tr.top_ops(), "idle_gaps": tr.idle_gaps()}
    return metrics, extras, breakdown


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             peak: dict, t_start: float, fault=None, keep_trace=None,
             log=None) -> dict:
    """One run of ``cell`` on ``devices``; returns the result dict."""
    ctx = harness.Ctx(cell, seed, seconds, trace, devices, t_start, log)
    driver = harness.load_module("drivers", cell.traffic["driver"])
    out = driver.run(ctx) if fault is None else driver.run(ctx, fault=fault)
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    result = {"correct": ctx.correct, "attempted": out["attempted"],
              "failed": out["failed"]}
    if trace:
        metrics, extras, breakdown = per_layer_metrics(ctx, cell, peak,
                                                       keep_trace)
        device.update(extras)
    else:
        metrics = {name: {"value": float(v), "unit": unit}
                   for name, (v, unit) in out["metrics"].items()}
        metrics["setup_s"] = {"value": ctx.setup_s, "unit": "s"}
        breakdown = None
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in ctx.checks}
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        cell = harness.resolve_cell(args.workload)
        devices = harness.require_chip(cell.chips)
    except harness.BenchError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    d0 = devices[0]
    tag = f"[bench {d0.platform} {d0.device_kind} x{len(devices)}]"
    log = lambda msg: print(f"{tag} {msg}", file=sys.stderr, flush=True)
    log(f"{args.workload} seed {args.seed} seconds {args.seconds} trace "
        f"{args.trace}; compile cache {harness.use_bench_cache()}")
    peak = harness.load_peaks(d0.device_kind)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, peak, T_START, keep_trace=args.keep_trace,
                      log=log)
    for name, c in result["checks"].items():
        print(f"{tag} check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
