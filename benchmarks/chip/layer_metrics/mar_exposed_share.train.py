"""Share of the collective device time (``mar_collective_ms.train``) in
which the device runs no other op: the collectives' time not hidden
behind compute, averaged over the devices. In percent. None where no
collective ran, as on one chip."""


def read(inp):
    if not inp.counters.get("traced_calls"):
        return None
    coll, exposed = inp.trace.collective_exposed()
    if coll <= 0:
        return None
    return 100.0 * (exposed / coll)
