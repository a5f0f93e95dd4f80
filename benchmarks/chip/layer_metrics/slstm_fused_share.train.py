"""Share of the sLSTM blocks' device time (``slstm_ms.train``) spent in
ops under the ``slstm_recurrence`` scope: the fused recurrence's forward
and backward kernels and the weight products after its loop. In percent,
per traced window. None on a program without that scope."""
import op_paths

RULES = (("recurrence", "slstm_recurrence"), ("slstm", "slstm"))


def read(inp):
    if not inp.counters.get("traced_calls"):
        return None
    paths = op_paths.op_paths(op_paths.compiled_step(inp))
    out = op_paths.split(inp.trace, paths, RULES)
    fused = out.get("recurrence")
    total = (fused or 0.0) + out.get("slstm", 0.0)
    if fused is None or total <= 0:
        return None
    return 100.0 * fused / total
