"""Engine tick: mean wall time of Engine.step (the benchmark's
``bench.engine_step`` span) less the device-busy time inside it."""

import trace_reduce as TR


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window()
    ops = run.trace.ops.get(run.device, ())
    host = [s.dur - TR.busy_ns(ops, s.start, s.end)
            for s in run.trace.spans
            if s.name == "bench.engine_step" and lo <= s.start
            and s.end <= hi]
    return sum(host) / len(host) * 1e-6 if host else None
