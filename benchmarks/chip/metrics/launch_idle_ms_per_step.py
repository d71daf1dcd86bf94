"""Backend step: device-idle time inside the backend's side of each
engine step (``repro.engine.step``), mean over the traced window's
steps: the host copying a program's arguments
(``repro.backend.<program>.put``), launching it (``.launch``) and
reading its result back (``repro.engine.fetch``) while the chip waits."""

import program_spans as PS


def read(run):
    spans = PS.of_run(run)
    if spans is None:
        return None
    lo, hi = run.trace_window()
    split = PS.idle_split(run.trace, run.device, spans, lo, hi)
    return sum(b for b, _ in split) / len(split) * 1e-6 if split else None
