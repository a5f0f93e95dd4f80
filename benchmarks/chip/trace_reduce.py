"""Reduction of a profiler trace to what the per-layer metrics read.

A trace is the ``.xplane.pb`` the JAX profiler writes. Device planes are
``/device:TPU:<n>``. Their ``XLA Ops`` line holds one event per HLO
operation that ran; an op that holds others (a ``while`` and its body,
a fusion's callees) encloses their events, so each event gets its self
time (its span less its children's) and only leaves count as work that
overlaps a collective. The ``Async XLA Ops`` line holds the spans of
asynchronous ops (``all-reduce-start`` to its done, copies). The host
plane holds the benchmark's spans (``bench.*`` annotations);
``bench.window`` marks the traced part of the window, and everything is
clipped to it.

Busy time of a device is the union of its ``XLA Ops`` intervals; idle
share is one minus busy over the window.
"""
from __future__ import annotations

import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


def is_collective(event_name: str) -> bool:
    """A collective by its instruction's name (``%all-reduce.3 = ...``),
    not by the operands of the text after it: a fusion that reads an
    all-reduce's result names it there."""
    return bool(COLLECTIVE.search(event_name.split(" = ")[0]))


KEY_CHARS = 160


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _length(intervals) -> float:
    return float(sum(b - a for a, b in intervals))


def _intersect(xs, ys) -> list:
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append([a, b])
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def _nest(events) -> list:
    """events [(name, t0, t1, key)] -> [(name, t0, t1, key, self_ns,
    leaf)]: self time is the span less the direct children's spans."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    self_ns = [e[2] - e[1] for e in evs]
    leaf = [True] * len(evs)
    stack = []
    for i, (_, a, b, _) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            parent = stack[-1]
            leaf[parent] = False
            self_ns[parent] -= min(b, evs[parent][2]) - a
        stack.append(i)
    return [e + (s, lf) for e, s, lf in zip(evs, self_ns, leaf)]


class Trace:
    """Op intervals per device and host spans, in ns, inside the window.

    ``ops``: device -> [(name, t0, t1, key)] from ``XLA Ops``, ``key`` the
    op's path in the program where the trace gives one; ``async_ops``:
    device -> [(name, t0, t1)] from ``Async XLA Ops``."""

    def __init__(self, ops: dict, spans: dict, window: tuple,
                 async_ops: dict | None = None):
        self.window = window
        self.spans = spans
        self.ops = {d: _nest(evs) for d, evs in ops.items()}
        self.async_ops = async_ops or {}
        self.busy = {d: _union([(a, b) for _, a, b, *_ in evs])
                     for d, evs in self.ops.items()}

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices in the trace."""
        if not self.busy:
            return 0.0
        return sum(_length(b) for b in self.busy.values()) * 1e-9 / \
            len(self.busy)

    def op_seconds(self, pattern: str) -> float:
        """Self seconds of ops whose name or path matches, averaged over
        devices."""
        rx = re.compile(pattern)
        if not self.ops:
            return 0.0
        tot = sum(s for evs in self.ops.values()
                  for n, a, b, k, s, _ in evs if rx.search(n) or rx.search(k))
        return tot * 1e-9 / len(self.ops)

    def collective_exposed(self) -> tuple:
        """(collective seconds, seconds of it in which no other leaf op
        runs), averaged over devices. A collective's span is its op in
        ``XLA Ops``, or its start-to-done span in ``Async XLA Ops``."""
        tot = exposed = 0.0
        for d, evs in self.ops.items():
            spans = [(a, b) for n, a, b, k, s, leaf in evs
                     if leaf and is_collective(n)]
            spans += [(a, b) for n, a, b in self.async_ops.get(d, [])
                      if is_collective(n)]
            coll = _union(spans)
            comp = _union([(a, b) for n, a, b, k, s, leaf in evs
                           if leaf and not is_collective(n)])
            tot += _length(coll)
            exposed += _length(coll) - _length(_intersect(coll, comp))
        n = max(len(self.ops), 1)
        return tot * 1e-9 / n, exposed * 1e-9 / n

    def idle_within(self, span_name: str) -> tuple:
        """(seconds of the spans named ``span_name``, seconds of them in
        which the first device ran nothing)."""
        spans = _union(self.spans.get(span_name, []))
        if not spans or not self.busy:
            return 0.0, 0.0
        busy = self.busy[min(self.busy)]
        return (_length(spans) * 1e-9,
                (_length(spans) - _length(_intersect(spans, busy))) * 1e-9)

    def top_ops(self, n: int = 10) -> list:
        """The ops with the most self time, grouped by op path (or name),
        averaged over devices."""
        tot = defaultdict(float)
        for evs in self.ops.values():
            for name, a, b, key, s, _ in evs:
                tot[key[:KEY_CHARS]] += s * 1e-9 / len(self.ops)
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10) -> list:
        """Idle seconds of the first device in the window, summed by the
        innermost host span that covers each gap's middle."""
        if not self.busy:
            return []
        busy = self.busy[min(self.busy)]
        w0, w1 = self.window
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        flat = sorted((b - a, a, b, name) for name, ss in self.spans.items()
                      if name != "bench.window" for a, b in ss)
        tot, cnt, longest = defaultdict(float), defaultdict(int), \
            defaultdict(float)
        for a, b in gaps:
            mid = (a + b) / 2
            who = next((name for _, s0, s1, name in flat if s0 <= mid <= s1),
                       "no span")
            tot[who] += (b - a) * 1e-9
            cnt[who] += 1
            longest[who] = max(longest[who], (b - a) * 1e-9)
        rows = [[f"{w} (gaps {cnt[w]}, longest {longest[w] * 1e3:.4f} ms)",
                 tot[w]] for w in tot]
        return sorted(rows, key=lambda kv: -kv[1])[:n]


def _op_key(event) -> str:
    """The op's path in the program (``tf_op``) where the trace has it,
    else its HLO name without the operand list."""
    try:
        stats = dict(event.stats)
    except Exception:       # noqa: BLE001 — stats are optional
        stats = {}
    if stats.get("tf_op"):
        return str(stats["tf_op"])
    return event.name.split(" = ")[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans = defaultdict(list)
    raw_ops, raw_async = {}, {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    raw_ops[int(m.group(1))] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns,
                         _op_key(e)) for e in line.events]
                elif line.name == ASYNC_LINE:
                    raw_async[int(m.group(1))] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if is_collective(e.name)]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans[e.name].append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    if not spans.get("bench.window"):
        raise ValueError(f"no bench.window span in {path}")
    w0 = min(a for a, _ in spans["bench.window"])
    w1 = max(b for _, b in spans["bench.window"])
    clip = lambda evs: [(e[0], max(e[1], w0), min(e[2], w1)) + e[3:]
                        for e in evs if e[2] > w0 and e[1] < w1]
    return Trace({d: clip(evs) for d, evs in raw_ops.items()}, dict(spans),
                 (w0, w1), {d: clip(evs) for d, evs in raw_async.items()})
