"""Front end: 90th percentile of the time a request waits in the
batcher's queue, from its arrival (``submit``) to its admission into a
decode slot, over every request due in the window.  The batcher stamps
both times on the request's ``SeqState`` (``arrive_us``, ``admit_us``), on
the harness's wall clock; a recorder's ``req.queued`` span is the same
interval.  A request shed or never admitted ranks as infinitely late;
where the rank lands on one, the time it had waited when the run ended
is reported, a lower bound (as in ``ttft_p90_ms``)."""
from harness.stats import percentile


def read(ctx):
    w = ctx.window
    vals = [(r.seq.admit_us - r.seq.arrive_us) / 1e6 if r.seq is not None
            else float("inf") for r in w.records]
    p = percentile(vals, 90)
    if p is None:
        return None
    if p == float("inf"):
        p = max(w.end_s - (r.submit_s if r.submit_s is not None
                           else r.arrival.due_s)
                for r in w.records if r.seq is None)
    return p * 1e3
