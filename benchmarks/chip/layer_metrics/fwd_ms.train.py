"""Device self time per traced call of the FL step's forward pass, in
ms: ops under the ``fl.grad`` scope, neither transposed (backward) nor
rematerialized (``op_paths.PHASES``)."""
import op_paths


def read(inp):
    return op_paths.per_call_ms(inp, "phase", "fwd")
