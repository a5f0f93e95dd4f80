"""Device self time per traced call of the output head and the loss, in
every phase, in ms: ops under the ``lm_head`` scope
(``op_paths.BLOCKS``)."""
import op_paths


def read(inp):
    return op_paths.per_call_ms(inp, "block", "head")
