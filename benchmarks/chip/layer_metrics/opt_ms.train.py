"""Device self time per traced call of the optimizer, in ms: ops under the
``fl.opt`` scope (micro-batch accumulation and mean, momentum SGD, the
churn mask's select; ``op_paths.PHASES``)."""
import op_paths


def read(inp):
    return op_paths.per_call_ms(inp, "phase", "opt")
