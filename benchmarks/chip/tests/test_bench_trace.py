"""The trace reduction: interval arithmetic on a hand-made trace, and
loading a small trace recorded here (host spans only: the CPU has no
device plane)."""
import glob

import pytest

import tiny
import trace_reduce

MS = 1_000_000      # ns


def hand_trace():
    # window 0..100 ms. Device 0: a while op 0-40 holding two fusions
    # (0-20, 25-40), a blocking all-reduce 40-60, a fusion 70-80, and an
    # async all-reduce 10-30 under the loop. Device 1: a fusion 10-50 and
    # an all-reduce 50-70.
    ops = {
        0: [("while.1", 0, 40 * MS, "jit(step)/while"),
            ("fusion.1", 0, 20 * MS, "jit(step)/dot"),
            ("fusion.2", 25 * MS, 40 * MS, "jit(step)/mul"),
            ("all-reduce.2", 40 * MS, 60 * MS, "jit(step)/psum"),
            ("fusion.3", 70 * MS, 80 * MS, "jit(step)/add")],
        1: [("fusion.1", 10 * MS, 50 * MS, "jit(step)/dot"),
            ("all-reduce.2", 50 * MS, 70 * MS, "jit(step)/psum")],
    }
    async_ops = {0: [("all-reduce-start.5", 10 * MS, 30 * MS)]}
    spans = {"bench.window": [(0, 100 * MS)],
             "bench.step": [(0, 85 * MS)],
             "bench.feed": [(85 * MS, 100 * MS)]}
    return trace_reduce.Trace(ops, spans, (0, 100 * MS), async_ops)


def test_busy_idle_and_collectives():
    t = hand_trace()
    assert t.window_s == pytest.approx(0.1)
    # device 0 busy 0-60 and 70-80 = 70 ms; device 1 busy 10-70 = 60 ms
    assert t.busy_s() == pytest.approx(0.065)
    coll, exposed = t.collective_exposed()
    # device 0: 40 ms of all-reduce (10-30 async, 40-60), 5 + 20 of it
    # with no leaf op running; device 1: 20 ms, all of it exposed
    assert coll == pytest.approx(0.030)
    assert exposed == pytest.approx(0.0225)
    assert t.op_seconds("all-reduce") == pytest.approx(0.020)


def test_idle_by_host_span_and_top_ops():
    t = hand_trace()
    total, idle = t.idle_within("bench.step")
    assert total == pytest.approx(0.085)
    assert idle == pytest.approx(0.015)      # 60-70 and 80-85 on device 0
    gaps = dict((k.split(" (")[0], v) for k, v in t.idle_gaps())
    # each gap goes whole to the span over its middle: 60-70 to the step,
    # 80-100 to the feed
    assert gaps == pytest.approx({"bench.step": 0.010, "bench.feed": 0.020})
    top = dict(t.top_ops())
    # self times, averaged over the two devices; the loop keeps 5 ms
    assert top["jit(step)/dot"] == pytest.approx(0.030)
    assert top["jit(step)/psum"] == pytest.approx(0.020)
    assert top["jit(step)/while"] == pytest.approx(0.0025)


def test_load_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    t = trace_reduce.load(path[0])
    assert len(t.spans["bench.step"]) == 3
    assert t.window_s > 0
    w0, w1 = t.window
    assert all(w0 <= a <= b <= w1 for a, b in t.spans["bench.step"])


def four_device_trace():
    # window 0..100 ms, four devices running one step each. Devices 0
    # and 2: compute 0-40, a blocking all-reduce 40-60 (exposed), an
    # async all-reduce 10-30 under the compute (hidden). Device 1:
    # compute 0-50, all-reduce 50-60. Device 3: as device 0 without the
    # async one, then a fusion that reads the all-reduce's result, 60-70:
    # its text names the all-reduce, yet it computes.
    def dev(compute_end, ar, extra=()):
        return [("%fusion.1 = f32[4]{0} fusion(%p)", 0, compute_end * MS,
                 "jit(step)/fl.grad/dot"),
                ("%all-reduce.1 = f32[4]{0} all-reduce(%fusion.1)",
                 ar[0] * MS, ar[1] * MS, "jit(step)/fl.aggregate/psum"),
                *extra]
    reads = ("%fusion.9 = f32[4]{0} fusion(%all-reduce.1), kind=kLoop",
             60 * MS, 70 * MS, "jit(step)/fl.aggregate/convert")
    ops = {0: dev(40, (40, 60)), 1: dev(50, (50, 60)), 2: dev(40, (40, 60)),
           3: dev(40, (40, 60), (reads,))}
    async_ops = {d: [("%all-reduce-start.2 = f32[4]{0} all-reduce-start("
                      "%p)", 10 * MS, 30 * MS)] for d in (0, 2)}
    return trace_reduce.Trace(ops, {"bench.window": [(0, 100 * MS)]},
                              (0, 100 * MS), async_ops)


def _collective_readers(trace, calls=2):
    import harness
    from types import SimpleNamespace
    inp = SimpleNamespace(trace=trace, counters={"traced_calls": calls})
    return {m: harness.load_module("layer_metrics", m).read(inp)
            for m in ("mar_collective_ms.train", "mar_exposed_share.train")}


def test_collective_readers_on_four_devices():
    t = four_device_trace()
    coll, exposed = t.collective_exposed()
    # collective ms by device 40, 10, 40, 20; exposed 20, 10, 20, 20
    assert coll == pytest.approx(0.0275)
    assert exposed == pytest.approx(0.0175)
    got = _collective_readers(t)
    assert got["mar_collective_ms.train"] == pytest.approx(13.75)
    assert got["mar_exposed_share.train"] == pytest.approx(100 * 17.5 / 27.5)


def test_an_op_that_reads_a_collective_is_not_one():
    assert trace_reduce.is_collective(
        "%all-reduce-start.2 = f32[4]{0} all-reduce-start(%p)")
    assert trace_reduce.is_collective("all-gather.7")
    assert not trace_reduce.is_collective(
        "%fusion.9 = f32[4]{0} fusion(%all-reduce.1), kind=kLoop")


def test_collective_readers_read_nothing_without_a_collective():
    one = trace_reduce.Trace(
        {0: [("%fusion.1 = f32[4]{0} fusion(%p)", 0, 50 * MS, "dot")]},
        {"bench.window": [(0, 100 * MS)]}, (0, 100 * MS))
    assert _collective_readers(one) == {"mar_collective_ms.train": None,
                                        "mar_exposed_share.train": None}
    assert _collective_readers(four_device_trace(), calls=0) == {
        "mar_collective_ms.train": None, "mar_exposed_share.train": None}
