"""Shared machinery of the chip benchmark.

Everything that belongs to one configuration, traffic mix, cell, driver or
per-layer metric sits in a file of its own under this directory and is
found by the name ``BENCHMARK.json`` gives it:

  configs/<config>.json        sizes as run, source, departures
  traffic/<traffic>.json       the mix: driver name and its parameters
  workloads/<cell>.json        config, traffic, chips, why, the limits
  drivers/<driver>.py          ``run(ctx) -> dict``
  layer_metrics/<metric>.py    ``read(inp) -> float | None``
  references/<reference>.py    the plain float32 model the check runs
  work/<work>.py               the configuration's operations per token

This module imports no JAX at import time, so the tests can collect it
without touching an accelerator.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
# JAX's persistent compilation cache, at a fixed path inside the checkout
# (the path is part of the cache key, so it must not move between runs)
CACHE_DIR = ROOT / ".jax_cache" / "bench"


class BenchError(RuntimeError):
    """A run that cannot produce a result: no chip, unknown chip, a cell
    that does not resolve. ``run.py`` exits non-zero and prints no line."""


def load_json(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no {kind} file for {name!r} ({path})")
    return json.loads(path.read_text())


def load_module(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no {kind} module for {name!r} ({path})")
    mod_name = "bench_" + kind + "_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark_spec() -> dict:
    path = ROOT / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no BENCHMARK.json at {ROOT}")
    return json.loads(path.read_text())


def resolve_cell(name: str, spec: dict | None = None) -> SimpleNamespace:
    """The cell's entry in BENCHMARK.json with its files, and the metrics
    it reports: end-to-end (``--trace 0``) and per-layer (``--trace 1``)."""
    spec = spec or benchmark_spec()
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = load_json("workloads", name)
    for key in ("config", "traffic", "chips"):
        if cell.get(key) != entry[key]:
            raise BenchError(f"workloads/{name}.json says {key}="
                             f"{cell.get(key)!r}, BENCHMARK.json "
                             f"{entry[key]!r}")
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return SimpleNamespace(
        name=name, chips=entry["chips"], cell=cell,
        config=load_json("configs", entry["config"]),
        traffic=load_json("traffic", entry["traffic"]),
        end_to_end=e2e, per_layer=per_layer)


def load_peaks(kind: str) -> dict:
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in peaks.json")
    return peaks[kind]


def require_chip(chips: int):
    """The platform check, run before anything else touches the chip's
    program: a TPU, at least ``chips`` of them, of a kind in peaks.json."""
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {d0.platform!r})")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    load_peaks(d0.device_kind)
    return devices[:chips]


def use_bench_cache() -> str:
    """Point JAX's persistent compilation cache at the checkout's fixed
    directory, whatever the environment says, and cache every program."""
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


def seed_key(seed: int):
    """A PRNG key from any whole seed, also one of more than 31 bits."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0xFFFFFFFF)


def model_config(config: dict):
    """The program's ``ModelConfig`` for a config file's ``model`` sizes."""
    from repro.configs.base import ModelConfig
    return ModelConfig(**config["model"])


class Ctx:
    """What a driver gets: the cell's files, the seed and window, the
    devices, and the hooks that mark set-up, the window and the trace."""

    def __init__(self, cell: SimpleNamespace, seed: int, seconds: float,
                 trace: bool, devices, t_start: float, log=None):
        self.name = cell.name
        self.cell = cell.cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.chips = cell.chips
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.devices = devices
        self.t_start = t_start
        self.log = log or (lambda msg: print(msg, file=sys.stderr))
        self.setup_s = None
        self.window_s = None
        self.counters: dict = {}
        self.checks: list = []
        self.memory_peak_bytes = None
        self.trace_file = None
        self._w0 = None
        self._tracing = False
        self._trace_dir = None
        self._window_ann = None

    # -- set-up / window ---------------------------------------------------
    def phase(self, name: str) -> None:
        """Log how far set-up has come: seconds since the process began."""
        self.log(f"set-up {name} at {time.perf_counter() - self.t_start:.3f} s")

    def window_begin(self) -> float:
        """End of set-up; the measured window starts now."""
        now = time.perf_counter()
        self.setup_s = now - self.t_start
        self._w0 = now
        if self.trace:
            self._start_trace()
        return now

    def elapsed(self) -> float:
        return time.perf_counter() - self._w0

    def trace_due(self) -> bool:
        """True while the traced part of the window has time left."""
        return self._tracing and \
            self.elapsed() < self.traffic.get("trace_seconds", self.seconds)

    @property
    def tracing(self) -> bool:
        return self._tracing

    def maybe_stop_trace(self) -> None:
        if self._tracing and not self.trace_due():
            self._stop_trace()

    def window_end(self) -> float:
        self.window_s = self.elapsed()
        if self._tracing:
            self._stop_trace()
        return self.window_s

    def read_memory_peak(self) -> int:
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        self.memory_peak_bytes = max(peaks) if peaks else 0
        return self.memory_peak_bytes

    # -- spans and the trace ------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str):
        """A host span around one call into the program; it reaches the
        profiler's trace only while the window is traced."""
        if self._tracing:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield

    def _start_trace(self) -> None:
        import jax
        self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self._trace_dir)
        self._window_ann = jax.profiler.TraceAnnotation("bench.window")
        self._window_ann.__enter__()
        self._tracing = True

    def _stop_trace(self) -> None:
        import jax
        self._window_ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._tracing = False
        found = sorted(Path(self._trace_dir).rglob("*.xplane.pb"))
        self.trace_file = str(found[-1]) if found else None

    def discard_trace(self, keep_to: str | None = None) -> None:
        if self._trace_dir is None:
            return
        if keep_to:
            shutil.copytree(self._trace_dir, keep_to, dirs_exist_ok=True)
        shutil.rmtree(self._trace_dir, ignore_errors=True)
        self._trace_dir = None

    # -- correctness --------------------------------------------------------
    def check(self, name: str, value: float, limit: float) -> None:
        """One number compared, with its limit (``value <= limit``)."""
        self.checks.append({"name": name, "value": float(value),
                            "limit": float(limit)})

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            c["value"] <= c["limit"] for c in self.checks)


def layer_inputs(ctx: Ctx, trace_obj, peak: dict) -> SimpleNamespace:
    """What a per-layer metric reader gets."""
    return SimpleNamespace(trace=trace_obj, counters=ctx.counters,
                           peak=peak, config=ctx.config,
                           model=ctx.config["model"], traffic=ctx.traffic,
                           chips=ctx.chips)
