"""p90 of time to first token over every request due in the window,
timed from when it was due (an open loop's schedule, or a closed-loop
client's send)."""

import numpy as np


def read(run):
    due = run.due_in_window()
    if not due:
        return None
    ttft = [(r.req.first_token_s - r.due) if r.req.first_token_s
            else float("inf") for r in due]
    return float(np.percentile(ttft, 90)) * 1e3
