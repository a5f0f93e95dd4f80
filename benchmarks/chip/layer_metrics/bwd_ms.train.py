"""Device self time per traced call of the FL step's backward pass, in
ms: transposed ops (``transpose(``) under ``fl.grad``, the recompute left
out (``op_paths.PHASES``)."""
import op_paths


def read(inp):
    return op_paths.per_call_ms(inp, "phase", "bwd")
