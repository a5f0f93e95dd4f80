"""Device self time per traced call of the mLSTM blocks, in every phase,
in ms: ops under the ``mlstm`` scope (``op_paths.BLOCKS``)."""
import op_paths


def read(inp):
    return op_paths.per_call_ms(inp, "block", "mlstm")
