"""Scheduler: p90 of Request.admit_s - arrival_s (submit to slot
admission) over the requests due in the window."""

import numpy as np


def read(run):
    w = [r.req.admit_s - r.req.arrival_s for r in run.due_in_window()
         if r.req.admit_s]
    return float(np.percentile(w, 90)) * 1e3 if w else None
