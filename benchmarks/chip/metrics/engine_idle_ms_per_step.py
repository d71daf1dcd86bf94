"""Engine tick: device-idle time of each engine step
(``repro.engine.step``) outside the backend's side of it (the spans
`launch_idle_ms_per_step` reads), mean over the traced window's steps:
the engine's own host work (planning, emitting tokens, committing).
`launch_idle_ms_per_step` plus this is the step's whole idle time."""

import program_spans as PS


def read(run):
    spans = PS.of_run(run)
    if spans is None:
        return None
    lo, hi = run.trace_window()
    split = PS.idle_split(run.trace, run.device, spans, lo, hi)
    return sum(e for _, e in split) / len(split) * 1e-6 if split else None
