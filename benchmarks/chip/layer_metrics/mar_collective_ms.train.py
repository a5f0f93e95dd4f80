"""Collective device time per traced call, in ms: the union of the spans
of the collective ops on each device (``trace_reduce.Trace
.collective_exposed``), averaged over the devices, over the traced calls.
On a mesh of one peer per chip these are MAR's all-reduces (and the
loss's mean over peers). None where no collective ran, as on one chip."""


def read(inp):
    calls = inp.counters.get("traced_calls")
    if not calls:
        return None
    coll, _ = inp.trace.collective_exposed()
    if coll <= 0:
        return None
    return coll / calls * 1e3
