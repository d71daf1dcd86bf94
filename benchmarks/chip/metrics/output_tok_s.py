"""Output tokens emitted inside the window over the window's seconds
(every request's tokens, whenever it was sent)."""


def read(run):
    n = sum(1 for r in run.records for t in r.req.token_times
            if run.t0 <= t < run.t1)
    return n / run.seconds
