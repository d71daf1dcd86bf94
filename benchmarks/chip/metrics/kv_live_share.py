"""KV pool: share of the pool's KV bytes that active slots hold, over
the decode steps of the traced window. Each decode launch's span
(``repro.backend.step.launch``) carries ``kv_pool_bytes`` (every slot
at max_len: hot ring, int8 cold tier and scales, which the decode
program takes and rewrites whole) and ``kv_live_bytes`` (the active
slots at their positions)."""

import program_spans as PS


def read(run):
    spans = PS.of_run(run)
    if spans is None:
        return None
    lo, hi = run.trace_window()
    return PS.kv_live_share(spans, lo, hi)
