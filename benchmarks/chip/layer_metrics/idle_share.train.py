"""Share of the traced training window in which the chips ran no
operation (1 - busy / window, busy the union of op intervals, averaged
over the chips). In percent."""


def read(inp):
    if inp.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - inp.trace.busy_s() / inp.trace.window_s)
