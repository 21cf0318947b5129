"""Load generator: 99th percentile of how late a request was submitted
after it was due (host clock).  The loop submits between steps, so this is
at most about one step when the generator keeps up."""
from harness.stats import percentile


def read(ctx):
    vals = [r.submit_s - r.arrival.due_s for r in ctx.window.records
            if r.submit_s is not None]
    p = percentile(vals, 99)
    return None if p is None else p * 1e3
