"""p95 over every gap between two consecutive tokens of one request,
both emitted inside the window."""

import numpy as np


def read(run):
    gaps = []
    for r in run.records:
        ts = [t for t in r.req.token_times if run.t0 <= t < run.t1]
        gaps.extend(np.diff(ts))
    if not gaps:
        return None
    return float(np.percentile(gaps, 95)) * 1e3
