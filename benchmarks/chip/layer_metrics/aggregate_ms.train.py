"""Device self time per traced call of the MAR aggregation, in ms: ops
under the ``fl.aggregate`` scope (``op_paths.PHASES``)."""
import op_paths


def read(inp):
    return op_paths.per_call_ms(inp, "phase", "aggregate")
