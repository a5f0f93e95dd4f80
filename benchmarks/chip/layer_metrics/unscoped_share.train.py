"""Share of the traced window's busy device time in no phase of the FL
step (``op_paths.PHASES``): ops with no path in the program, or a path
under none of its phase scopes. In percent. It rises where a refactor
loses a scope, so that no phase metric reads 0 in silence."""
import op_paths


def read(inp):
    out = op_paths.splits(inp)
    busy = inp.trace.busy_s()
    if out is None or busy <= 0:
        return None
    return 100.0 * out["phase"].get(op_paths.UNSCOPED, 0.0) / busy
