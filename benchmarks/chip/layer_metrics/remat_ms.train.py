"""Device self time per traced call of the forward recomputed inside the
backward pass (``rematted_computation``), in ms (``op_paths.PHASES``)."""
import op_paths


def read(inp):
    return op_paths.per_call_ms(inp, "phase", "remat")
