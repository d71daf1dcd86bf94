"""Backend step: device time of one execution of the decode program
(XLA module ``jit_step``, the backend's batched decode), mean over the
traced window."""

import trace_reduce as TR


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window()
    runs = TR.module_runs(run.trace, run.device, "jit_step", lo, hi)
    return sum(m.dur for m in runs) / len(runs) * 1e-6 if runs else None
