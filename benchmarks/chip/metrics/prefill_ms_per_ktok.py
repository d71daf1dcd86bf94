"""Backend step: device time of the prefill programs (XLA modules
``jit_ext_part`` and ``jit_ext_commit``) per 1,000 prompt positions
committed in the traced window."""

import trace_reduce as TR


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window()
    runs = (TR.module_runs(run.trace, run.device, "jit_ext_part", lo, hi)
            + TR.module_runs(run.trace, run.device, "jit_ext_commit", lo,
                             hi))
    rows = sum(p for s in run.traced_steps() for p, _ in s.prefills)
    if not runs or not rows:
        return None
    return sum(m.dur for m in runs) * 1e-6 / (rows / 1000)
