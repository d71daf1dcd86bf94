"""Device: share of the traced window in which no operation ran on the
chip (1 - union of device-op intervals / window)."""

import trace_reduce as TR


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window()
    ops = run.trace.ops.get(run.device, ())
    if not ops:
        return None
    return 100.0 * (1.0 - TR.busy_ns(ops, lo, hi) / (hi - lo))
