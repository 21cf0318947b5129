"""90th percentile time to first token over every request due in the
window, timed from its due time; requests are followed past the window's
close to their first token.  A shed request, or one that never got a
first token, ranks as infinitely late; where the rank lands on one, the
time it waited to the end of the run is reported, a lower bound."""
from harness.stats import percentile


def read(ctx):
    w = ctx.window
    vals = [(r.token_s[0] - r.arrival.due_s) if r.token_s and not r.shed
            else float("inf") for r in w.records]
    p = percentile(vals, 90)
    if p is None:
        return None
    if p == float("inf"):
        p = max(w.end_s - r.arrival.due_s for r in w.records
                if r.shed or not r.token_s)
    return p * 1e3
